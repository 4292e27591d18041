"""Multi-host data-movement helpers, exercised on the 8-device CPU mesh
(single-process: the callbacks see every shard, so the same code paths run
as on a pod — SURVEY §4.4's oversubscription strategy)."""

import numpy as np
import pytest

import jax

from acg_tpu.parallel.mesh import PARTS_AXIS, make_mesh
from acg_tpu.parallel.multihost import (gather_to_host, init_multihost,
                                        make_global_array)

# Known environment debt (triaged, PR 8): this container's jaxlib builds
# the CPU client WITHOUT cross-process collectives (no gloo/mpi
# collectives module), so any two-REAL-process computation dies with
# exactly this message from the runtime.  The two subprocess tests below
# skip on that precise witness rather than fail — they self-heal the
# moment a jaxlib with CPU multiprocess support is installed, and any
# OTHER failure (coordination, shard construction, wrong results) still
# fails loudly.
_CPU_MULTIPROC_UNSUPPORTED = (
    "Multiprocess computations aren't implemented on the CPU backend")


def _skip_if_cpu_multiprocess_unsupported(outs):
    if any(_CPU_MULTIPROC_UNSUPPORTED in o for o in outs):
        pytest.skip("jaxlib CPU backend lacks multiprocess collectives "
                    f"({_CPU_MULTIPROC_UNSUPPORTED!r}); real two-process "
                    "paths need a gloo-enabled build")


def test_init_multihost_single_process_noop():
    init_multihost()                 # must not raise without a cluster
    assert jax.process_count() == 1


@pytest.mark.parametrize("env,raises", [
    ({"TPU_WORKER_HOSTNAMES": "localhost"}, False),
    ({"TPU_WORKER_HOSTNAMES": "h0,h1,h2,h3"}, True),
    ({"TPU_WORKER_HOSTNAMES": "h0", "MEGASCALE_NUM_SLICES": "2"}, True),
    ({"SLURM_NTASKS": "4"}, True),
])
def test_init_multihost_without_coordinator(monkeypatch, env, raises):
    """No coordinator given: a host the environment names as single runs
    single-process; an environment naming several processes raises
    instead of degrading to independent single-host runs (and JAX's
    metadata-server autodetection is never tried)."""
    from acg_tpu.errors import AcgError, Status

    monkeypatch.delenv("JAX_COORDINATOR_ADDRESS", raising=False)
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    if raises:
        with pytest.raises(AcgError) as ei:
            init_multihost()
        assert ei.value.status == Status.ERR_NOT_SUPPORTED
        assert "JAX_COORDINATOR_ADDRESS" in str(ei.value)
    else:
        init_multihost()
        assert jax.process_count() == 1


def test_make_global_array_roundtrip():
    mesh = make_mesh(8)
    shard = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec(PARTS_AXIS))
    a = np.arange(8 * 16, dtype=np.float32).reshape(8, 16)
    g = make_global_array(a.shape, shard, lambda idx: a[idx])
    assert g.sharding == shard
    np.testing.assert_array_equal(gather_to_host(g), a)


def test_make_mesh_full_device_count_uses_topology_order():
    mesh = make_mesh(8)
    assert mesh.devices.shape == (8,)
    assert set(d.id for d in mesh.devices.flat) == set(range(8))


def test_reduce_stats_single_process_identity():
    from acg_tpu.solvers.base import SolveStats
    from acg_tpu.utils.stats import reduce_stats_across_processes

    st = SolveStats(tsolve=1.5)
    st.gemv.t = 0.5
    assert reduce_stats_across_processes(st) is st


_TWO_PROC_WORKER = r"""
import os, sys
sys.path.insert(0, {repo!r})
os.environ.pop("PYTHONSTARTUP", None)
import jax
jax.config.update("jax_platforms", "cpu")
pid = int(sys.argv[1])
jax.distributed.initialize(coordinator_address="127.0.0.1:{port}",
                           num_processes=2, process_id=pid)
assert jax.process_count() == 2
from acg_tpu.solvers.base import SolveStats
from acg_tpu.utils.stats import reduce_stats_across_processes
st = SolveStats(nsolves=1, ntotaliterations=10, niterations=10,
                nflops=100, tsolve=1.0 + pid)   # rank1 slower
st.gemv.t = 0.2 + 0.2 * pid                      # means: t=0.3
st.gemv.n = 4
st.gemv.bytes = 1000 * (pid + 1)                 # mean 1500
st.nhalomsgs = 3
out = reduce_stats_across_processes(st)
assert abs(out.tsolve - 2.0) < 1e-12, out.tsolve          # MAX
assert abs(out.gemv.t - 0.3) < 1e-12, out.gemv.t          # per-proc mean
assert out.gemv.bytes == 1500
assert out.gemv.n == 4
# nflops/nhalomsgs are recorded globally on every process -> MAX, not sum
assert out.nflops == 100
assert out.nhalomsgs == 3
print("proc", pid, "ok")
"""


def test_reduce_stats_two_real_processes(tmp_path):
    """The reference's MPI stats reduction semantics, on two REAL
    processes over the JAX distributed runtime (ref acgsolver_fwritempi,
    acg/cg.c:757-794: MAX tsolve, per-proc means) — the multi-host path
    the single-process tests cannot reach."""
    import socket
    import subprocess
    import sys as _sys

    with socket.socket() as s:      # free port for the coordinator
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    script = tmp_path / "worker.py"
    script.write_text(_TWO_PROC_WORKER.format(
        repo=str(__import__("pathlib").Path(__file__).parent.parent),
        port=port))
    env = dict(__import__("os").environ)
    env.pop("XLA_FLAGS", None)          # workers need no 8-device forcing
    env["JAX_PLATFORMS"] = "cpu"
    procs = [subprocess.Popen([_sys.executable, str(script), str(i)],
                              stdout=subprocess.PIPE,
                              stderr=subprocess.STDOUT, env=env, text=True)
             for i in range(2)]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=120)
            outs.append(out)
    finally:
        for p in procs:
            p.kill()
    _skip_if_cpu_multiprocess_unsupported(outs)
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"proc {i} failed:\n{out}"
        assert f"proc {i} ok" in out


_TWO_PROC_SOLVE_WORKER = r"""
import os, sys
sys.path.insert(0, {repo!r})
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)
pid = int(sys.argv[1])
jax.distributed.initialize(coordinator_address="127.0.0.1:{port}",
                           num_processes=2, process_id=pid)
assert jax.process_count() == 2 and jax.device_count() == 8
import numpy as np
from acg_tpu.config import SolverOptions
from acg_tpu.solvers.cg_dist import cg_dist, cg_pipelined_dist
from acg_tpu.sparse import poisson2d_5pt
from acg_tpu.sparse.csr import manufactured_rhs
A = poisson2d_5pt(16)
xstar, b = manufactured_rhs(A, seed=0)
opts = SolverOptions(maxits=1000, residual_rtol=1e-10)
for fn in (cg_dist, cg_pipelined_dist):
    res = fn(A, b, options=opts, nparts=8)
    err = float(np.linalg.norm(res.x - xstar))
    assert res.converged and err < 1e-7, (fn.__name__, err)
print("proc", pid, "solve ok")
"""


def test_two_process_distributed_solve(tmp_path):
    """A COMPLETE distributed solve on two REAL processes sharing one
    8-device mesh (4 local CPU devices each): shard construction touches
    only addressable shards, halo ppermutes and psums cross the process
    boundary through gloo, and the gathered solution matches the
    manufactured one on both ranks — the `mpirun -np 2` analog of the
    reference's multi-rank operation (ref cuda/acg-cuda.c:2242)."""
    import os as _os
    import socket
    import subprocess
    import sys as _sys

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    script = tmp_path / "solve_worker.py"
    script.write_text(_TWO_PROC_SOLVE_WORKER.format(
        repo=str(__import__("pathlib").Path(__file__).parent.parent),
        port=port))
    env = dict(_os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    procs = [subprocess.Popen([_sys.executable, str(script), str(i)],
                              stdout=subprocess.PIPE,
                              stderr=subprocess.STDOUT, env=env, text=True)
             for i in range(2)]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=240)
            outs.append(out)
    finally:
        for p in procs:
            p.kill()
    _skip_if_cpu_multiprocess_unsupported(outs)
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"proc {i} failed:\n{out}"
        assert f"proc {i} solve ok" in out
