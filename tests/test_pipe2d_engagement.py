"""pipe2d engagement at production shapes (VERDICT r5 "Next round" #4).

The single-kernel pipelined iteration (cg_pipelined_iter_pallas) is the
pipelined solver's headline tier; its gate (pipe2d_rt_for) can silently
disengage — probe off, VMEM plan rejection, fault injection — and the
solve still returns correct numbers through a slower kernel.  These
tests pin, by INVOCATION COUNT (the fuzzer's forced-tier idiom), that
the flagship single-chip 128³ geometry and a distributed pipelined
solve actually run the kernel: they fail if the path silently
disengages.  The kernel body is stubbed with its exact jnp formulation
(the probe's own oracle, pallas_kernels._probe_pipe2d_group) so the
engagement question is answered at full production shape without
interpret-mode cost.
"""

import unittest.mock as mock

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from acg_tpu.config import SolverOptions  # noqa: E402
from acg_tpu.ops import pallas_kernels as pk  # noqa: E402
from acg_tpu.ops.dia import dia_matvec  # noqa: E402


def _jnp_padded_spmv(counter):
    """jnp twin of dia_matvec_pallas_2d_padded on the padded layout
    (zero halo bands make the plain shifted-multiply exact there); the
    bands arrive as the kernels take them, (D, Rp, 128)."""

    def spmv(bands_pad, offsets, x_pad, rows_tile=512, with_dot=False,
             interpret=False, scales=None):
        counter["spmv"] = counter.get("spmv", 0) + 1
        assert bands_pad.ndim == 3 and bands_pad.shape[-1] == pk.LANES
        bref = bands_pad.reshape(bands_pad.shape[0], -1).astype(x_pad.dtype)
        if scales is not None:
            bref = bref * scales.astype(x_pad.dtype)[:, None]
        y = dia_matvec(bref, offsets, x_pad)
        if with_dot:
            return y, jnp.vdot(x_pad, y)
        return y

    return spmv


def _jnp_pipe2d_iter(counter):
    """jnp twin of cg_pipelined_iter_pallas (the probe oracle's
    formulation, pallas_kernels._probe_pipe2d_group), counting
    invocations."""

    def iter_step(bands_pad, offsets, w, z, r, p, s, x, alpha, beta,
                  rows_tile=512, interpret=False, scales=None):
        counter["pipe2d"] = counter.get("pipe2d", 0) + 1
        assert bands_pad.ndim == 3 and bands_pad.shape[-1] == pk.LANES
        bref = bands_pad.reshape(bands_pad.shape[0], -1).astype(w.dtype)
        if scales is not None:
            bref = bref * scales.astype(w.dtype)[:, None]
        q = dia_matvec(bref, offsets, w)
        z2 = q + beta * z
        p2 = r + beta * p
        s2 = w + beta * s
        x2 = x + alpha * p2
        r2 = r - alpha * s2
        w2 = w - alpha * z2
        return (z2, p2, s2, x2, r2, w2,
                jnp.vdot(r2, r2), jnp.vdot(w2, r2))

    return iter_step


def test_pipe2d_engages_at_single_chip_128cubed():
    """The flagship 128³ geometry must select AND invoke the pipe2d
    kernel in the pipelined solve (probes forced green; the VMEM plan
    and plan-divisibility math run for real at the production shape)."""
    import importlib

    from acg_tpu.ops.dia import DeviceDia
    from acg_tpu.sparse.poisson import poisson3d_7pt_dia

    cg_mod = importlib.import_module("acg_tpu.solvers.cg")

    Dm = poisson3d_7pt_dia(128, dtype=np.float32, row_align=1024)
    dev = DeviceDia.from_dia(Dm, dtype=np.float32, mat_dtype="auto")
    n = dev.nrows
    rng = np.random.default_rng(3)
    b = jnp.asarray(np.pad(rng.standard_normal(n).astype(np.float32),
                           (0, dev.nrows_padded - n)))
    counter = {}
    try:
        pk._SPMV_PROBE["fused2d"] = True
        pk._SPMV_PROBE["pipe2d"] = True
        cg_mod._cg_pipelined_device_fused.clear_cache()
        # the gate itself must pass at this geometry — a None here IS
        # the silent-disengagement failure this test exists to catch
        plan = cg_mod._fused_plan(dev)
        assert plan is not None and plan[0] == "resident", plan
        assert cg_mod._pipe2d_rt(dev, plan) is not None
        with mock.patch.object(pk, "dia_matvec_pallas_2d_padded",
                               _jnp_padded_spmv(counter)), \
             mock.patch.object(pk, "cg_pipelined_iter_pallas",
                               _jnp_pipe2d_iter(counter)):
            res = cg_mod.cg_pipelined(dev, b,
                                      options=SolverOptions(maxits=3, residual_rtol=0.0))
    finally:
        pk._SPMV_PROBE.pop("fused2d", None)
        pk._SPMV_PROBE.pop("pipe2d", None)
        cg_mod._cg_pipelined_device_fused.clear_cache()
    assert counter.get("pipe2d", 0) >= 1, \
        "pipe2d kernel was not invoked at 128^3"
    assert res.kernel == "pallas-pipe2d"
    assert res.kernel_note == ""
    assert np.all(np.isfinite(res.x))


def test_pipe2d_engages_in_distributed_pipelined_solve():
    """A distributed pipelined solve whose shards take the resident DIA
    tier must run the per-shard pipe2d kernel inside shard_map (with the
    interface correction folded in afterwards, cg_dist.py iter_step)."""
    from acg_tpu.solvers.cg_dist import (_dist_fused_plan, _dist_pipe_rt,
                                         build_sharded, cg_pipelined_dist)
    from acg_tpu.sparse import poisson3d_7pt
    from acg_tpu.sparse.csr import manufactured_rhs
    from acg_tpu.utils.backend import force_cpu_mesh

    force_cpu_mesh(8)
    A = poisson3d_7pt(64, dtype=np.float32)
    xstar, b = manufactured_rhs(A, seed=11)
    counter = {}
    try:
        pk._SPMV_PROBE["fused2d"] = True
        pk._SPMV_PROBE["pipe2d"] = True
        ss = build_sharded(A, nparts=8, dtype=np.float32)
        plan = _dist_fused_plan(ss)
        assert plan is not None and plan[0] == "resident", plan
        assert _dist_pipe_rt(ss, plan) is not None
        with mock.patch.object(pk, "dia_matvec_pallas_2d_padded",
                               _jnp_padded_spmv(counter)), \
             mock.patch.object(pk, "cg_pipelined_iter_pallas",
                               _jnp_pipe2d_iter(counter)):
            res = cg_pipelined_dist(ss, b,
                                    options=SolverOptions(maxits=3, residual_rtol=0.0))
    finally:
        pk._SPMV_PROBE.pop("fused2d", None)
        pk._SPMV_PROBE.pop("pipe2d", None)
    assert counter.get("pipe2d", 0) >= 1, \
        "pipe2d kernel was not invoked in the distributed solve"
    assert res.kernel == "pallas-pipe2d"
    assert np.all(np.isfinite(res.x))


def test_pipe2d_stays_engaged_under_replacement():
    """Residual replacement runs after the single-kernel step (PR 21:
    fp32 pipelined solves replace by default), so ``replace_every`` must
    keep the pipe2d kernel in the loop — and give the open-coded body's
    answer with the same replacement schedule."""
    import importlib

    from acg_tpu.ops.dia import DeviceDia
    from acg_tpu.sparse.poisson import poisson3d_7pt_dia
    from acg_tpu.utils.stats import format_solver_stats

    cg_mod = importlib.import_module("acg_tpu.solvers.cg")

    Dm = poisson3d_7pt_dia(16, dtype=np.float32, row_align=1024)
    dev = DeviceDia.from_dia(Dm, dtype=np.float32, mat_dtype="auto")
    rng = np.random.default_rng(5)
    b = jnp.asarray(np.pad(
        rng.standard_normal(dev.nrows).astype(np.float32),
        (0, dev.nrows_padded - dev.nrows)))
    counter = {}
    opts = SolverOptions(maxits=10, replace_every=4, residual_rtol=0.0)
    plain = cg_mod.cg_pipelined(dev, b, options=opts, fmt="dia")
    try:
        pk._SPMV_PROBE["fused2d"] = True
        pk._SPMV_PROBE["pipe2d"] = True
        cg_mod._cg_pipelined_device_fused.clear_cache()
        with mock.patch.object(pk, "dia_matvec_pallas_2d_padded",
                               _jnp_padded_spmv(counter)), \
             mock.patch.object(pk, "cg_pipelined_iter_pallas",
                               _jnp_pipe2d_iter(counter)):
            res = cg_mod.cg_pipelined(dev, b, options=opts)
    finally:
        pk._SPMV_PROBE.pop("fused2d", None)
        pk._SPMV_PROBE.pop("pipe2d", None)
        cg_mod._cg_pipelined_device_fused.clear_cache()
    assert counter.get("pipe2d", 0) >= 1          # really engaged
    assert res.kernel == "pallas-pipe2d"
    assert res.kernel_note == ""
    block = format_solver_stats(res.stats, res=res, options=opts)
    assert "kernel: pallas-pipe2d" in block and "disengaged" not in block
    np.testing.assert_allclose(res.x, plain.x, rtol=1e-4, atol=1e-5)


def test_forced_format_is_reported():
    """A forced --format pins the tier; the note must say the tier was
    forced, not chosen (the stats block is how a benchmark proves what
    it measured)."""
    from acg_tpu.solvers.cg import cg
    from acg_tpu.sparse import poisson3d_7pt
    from acg_tpu.sparse.csr import manufactured_rhs

    A = poisson3d_7pt(8, dtype=np.float32)
    xstar, b = manufactured_rhs(A, seed=2)
    res = cg(A, b, options=SolverOptions(maxits=200, residual_rtol=1e-5),
             fmt="ell", dtype=np.float32)
    assert res.kernel == "xla-gather"
    assert res.kernel_note == "format forced: ell"
