"""The distributed interface correction as slices over contiguous
border-row runs (ShardedSystem.iruns): which partitions take it, parity
with the ELL gather it replaces and with the host oracle, on every
distance-1 interface site of the shard program, and the compiled loop
it leaves behind (no interface gather, no scatter).  CPU mesh."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from acg_tpu.config import SolverOptions
from acg_tpu.obs.hlo import parse_hlo, while_body_profile
from acg_tpu.ops.spmv import ell_matvec
from acg_tpu.parallel.mesh import PARTS_AXIS
from acg_tpu.partition import partition_graph
from acg_tpu.solvers.cg_dist import (build_sharded, cg_dist,
                                     cg_pipelined_dist, lowered_step)
from acg_tpu.sparse import poisson3d_7pt, poisson3d_27pt
from acg_tpu.sparse.csr import manufactured_rhs
from acg_tpu.sparse.poisson import grid_partition_vector

IFACE = "acg.spmv.iface"

# (generator, grid shape (slowest axis first), block grid, runs per shard)
SLABS = {
    "7pt-z4": (poisson3d_7pt, (12, 6, 6), (4, 1, 1), 2),
    "7pt-z2": (poisson3d_7pt, (8, 6, 6), (2, 1, 1), 1),
    "7pt-z4-uneven": (poisson3d_7pt, (10, 6, 6), (4, 1, 1), 2),
    "27pt-z4": (poisson3d_27pt, (12, 6, 6), (4, 1, 1), 2),
}


def _slab(case, dtype=np.float64):
    gen, shape, grid, nruns = SLABS[case]
    A = gen(*shape, dtype=dtype)
    ss = build_sharded(A, part=grid_partition_vector(shape, grid),
                       dtype=dtype)
    return A, ss, nruns


def _as_ell(ss):
    """The same system with the ELL interface form (a fresh instance, so
    it compiles its own shard programs)."""
    return dataclasses.replace(ss, iruns=None, iruns_pad=(0, 0))


def _unstructured(case):
    A = poisson3d_7pt(8, 8, 8)
    if case == "multilevel":
        part = partition_graph(A, 4, method="multilevel", seed=3)
    elif case == "random":
        part = np.random.default_rng(5).integers(0, 4, A.nrows)
    else:                                        # x-split: strided rows
        part = grid_partition_vector((8, 8, 8), (1, 1, 4))
    return A, build_sharded(A, part=part, dtype=np.float64)


def _shard_apply(ss, x, front=None):
    """The shard program's matvec and interface correction, per shard,
    through the closures the solver uses; the ELL correction of the same
    ghosts beside them.  ``front`` adds the correction into a vector with
    that many leading pad slots (the fused path's padded carries)."""
    halo_fn = ss.shard_halo_fn()
    local_mv = ss.local_matvec_fn()
    add, vec = ss.iface_fns()

    def shard(lops, iops, iv, ic, sidx, ridx, ptnr, pidx, gsp, gpp, xs):
        xo = xs[0]
        iops = tuple(a[0] for a in iops)
        gh = halo_fn(xo, sidx[0], ridx[0], ptnr[0], pidx[0], gsp[0],
                     gpp[0])
        yl = local_mv(xo, tuple(a[0] for a in lops))
        if front is None:
            y = add(yl, iops, gh)
        else:
            pads = [(0, 0)] * (yl.ndim - 1) + [(front, 3)]
            yp = add(jnp.pad(yl, pads), iops, gh, front)
            y = jax.lax.slice_in_dim(yp, front, front + yl.shape[-1],
                                     axis=-1)
        return y[None], vec(iops, gh)[None], ell_matvec(iv[0], ic[0],
                                                         gh)[None]

    fn = jax.jit(jax.shard_map(
        shard, mesh=ss.mesh, in_specs=(P(PARTS_AXIS),) * 11,
        out_specs=(P(PARTS_AXIS),) * 3, check_vma=False))
    return fn(ss.local_op_arrays(), ss.iface_op_arrays(), ss.ivals,
              ss.icols, ss.send_idx, ss.recv_idx, ss.partner, ss.pack_idx,
              ss.ghost_src_part, ss.ghost_src_pos, ss.to_sharded(x))


@pytest.mark.parametrize("case", sorted(SLABS))
def test_slab_partitions_take_the_runs_form(case):
    _, ss, nruns = _slab(case)
    assert ss.iface_fmt == "runs"
    assert ss.iface_note == f"iface runs({nruns})"
    rows, cols, lens, vals = ss.iface_op_arrays()
    assert rows.shape == lens.shape == (ss.nparts, nruns)
    # a z-slab's border runs are whole planes
    gen, shape, _, _ = SLABS[case]
    plane = shape[1] * shape[2]
    lens = np.asarray(lens)
    assert set(np.unique(lens)) <= {0, plane, 2 * plane}
    if nruns > 1:
        # the end slabs have one neighbour: one run is padded empty
        assert (lens[0] == 0).any() and (lens[-1] == 0).any()
    # 27-point rows see up to 9 ghosts of the neighbouring plane
    assert cols.shape[-1] == (9 if gen is poisson3d_27pt else 1)


@pytest.mark.parametrize("front", [None, 5])
@pytest.mark.parametrize("case", sorted(SLABS))
def test_runs_matvec_matches_oracle_and_ell(case, front):
    A, ss, _ = _slab(case)
    x = np.random.default_rng(11).standard_normal(A.nrows)
    y, corr, corr_ell = _shard_apply(ss, x, front)
    np.testing.assert_allclose(ss.from_sharded(y), ss.ps.matvec(x),
                               rtol=1e-12, atol=1e-12)
    np.testing.assert_allclose(np.asarray(corr), np.asarray(corr_ell),
                               rtol=1e-14, atol=1e-14)


@pytest.mark.parametrize("case", ["7pt-z4", "27pt-z4"])
def test_runs_matvec_batched(case):
    A, ss, _ = _slab(case)
    X = np.random.default_rng(12).standard_normal((4, A.nrows))
    y, corr, corr_ell = _shard_apply(ss, X)
    want = np.stack([ss.ps.matvec(x) for x in X])
    np.testing.assert_allclose(ss.from_sharded(y), want, rtol=1e-12,
                               atol=1e-12)
    np.testing.assert_allclose(np.asarray(corr), np.asarray(corr_ell),
                               rtol=1e-14, atol=1e-14)


OPTS = SolverOptions(maxits=300, residual_rtol=1e-10)


@pytest.mark.parametrize("program", ["cg", "cg-pipelined", "cg-nrhs4"])
@pytest.mark.parametrize("case", ["7pt-z4", "7pt-z4-uneven", "27pt-z4"])
def test_runs_solve_matches_ell_solve(case, program):
    """Same products and sums per border row: the same iterations as the
    solve that applies the ELL correction, and x to rounding."""
    A, ss, nruns = _slab(case)
    xstar, b = manufactured_rhs(A, seed=7)
    solve = cg_pipelined_dist if program == "cg-pipelined" else cg_dist
    if program == "cg-nrhs4":
        b = np.stack([b, 2 * b, -b, b + 1.0])
    res = solve(ss, b, options=OPTS)
    ref = solve(_as_ell(ss), b, options=OPTS)
    assert res.converged and ref.converged
    assert res.iface == f"iface runs({nruns})" and ref.iface == "iface ell"
    if program == "cg-nrhs4":
        np.testing.assert_array_equal(res.iterations_per_system,
                                      ref.iterations_per_system)
    else:
        assert res.niterations == ref.niterations
        np.testing.assert_allclose(res.x, xstar, atol=1e-8)
    np.testing.assert_allclose(res.x, ref.x, rtol=1e-10, atol=1e-12)


def _interpret_pallas(monkeypatch, pipe2d: bool):
    """Force the fused padded DIA path (and the single-kernel pipelined
    iteration) through interpret mode on the CPU; returns the use log."""
    from acg_tpu.ops import pallas_kernels as pk

    used = {}
    orig_pad = pk.dia_matvec_pallas_2d_padded
    orig_iter = pk.cg_pipelined_iter_pallas

    def interp_pad(*a, **k):
        used["fused"] = True
        k["interpret"] = True
        return orig_pad(*a, **k)

    def interp_iter(*a, **k):
        used["pipe2d"] = True
        k["interpret"] = True
        return orig_iter(*a, **k)

    monkeypatch.setattr(pk, "dia_matvec_pallas_2d_padded", interp_pad)
    monkeypatch.setitem(pk._SPMV_PROBE, "fused2d", True)
    if pipe2d:
        monkeypatch.setattr(pk, "cg_pipelined_iter_pallas", interp_iter)
        monkeypatch.setitem(pk._SPMV_PROBE, "pipe2d", True)
    return used


@pytest.mark.parametrize("program", ["fused-cg", "pipe2d"])
def test_runs_fused_dia_paths_match_ell(monkeypatch, program):
    """The fused padded DIA matvec/coupled step and the single-kernel
    pipelined iteration (which needs the correction vector itself, for
    <I, r>) with the runs form: the iterations of the ELL form."""
    import importlib

    cgd = importlib.import_module("acg_tpu.solvers.cg_dist")
    used = _interpret_pallas(monkeypatch, program == "pipe2d")
    # 4096-row shards: the resident plan needs >= 2048
    shape = (32, 16, 16)
    A = poisson3d_7pt(*shape, dtype=np.float32)
    ss = build_sharded(A, part=grid_partition_vector(shape, (2, 1, 1)),
                       dtype=np.float32)
    assert ss.iface_fmt == "runs" and cgd._dist_fused_plan(ss) is not None
    xstar, b = manufactured_rhs(A, seed=9)
    opts = SolverOptions(maxits=200, residual_rtol=1e-5)
    solve = cg_pipelined_dist if program == "pipe2d" else cg_dist
    res = solve(ss, b, options=opts)
    ref = solve(_as_ell(ss), b, options=opts)
    assert used.get("pipe2d" if program == "pipe2d" else "fused")
    assert res.converged and ref.converged
    assert res.niterations == ref.niterations
    np.testing.assert_allclose(res.x, ref.x, rtol=1e-5,
                               atol=1e-5 * np.abs(ref.x).max())


@pytest.mark.parametrize("case", ["multilevel", "random", "x-split"])
def test_unstructured_partitions_keep_the_ell_form(case):
    A, ss = _unstructured(case)
    assert ss.iface_fmt == "ell" and ss.iface_note == "iface ell"
    assert ss.iruns is None
    assert ss.iface_op_arrays() == (ss.ivals, ss.icols)
    xstar, b = manufactured_rhs(A, seed=4)
    res = cg_dist(ss, b, options=OPTS)
    assert res.converged and res.iface == "iface ell"
    np.testing.assert_allclose(res.x, xstar, atol=1e-8)


@pytest.mark.parametrize("case", ["slab", "multilevel"])
def test_solve_path_names_the_interface_form(case):
    if case == "slab":
        A, ss, _ = _slab("7pt-z4")
        want = "iface runs(2)"
    else:
        A, ss = _unstructured(case)
        want = "iface ell"
    _, b = manufactured_rhs(A, seed=6)
    res = cg_dist(ss, b, options=OPTS)
    assert res.path == (res.operator_format, res.kernel, want)
    assert res.kernel_note == ""


def _iface_gathers(txt):
    comps = parse_hlo(txt)
    return [name for insts in comps.values()
            for name, v in insts.items()
            if not name.startswith("__") and v[0] == "gather"
            and IFACE in v[2]]


def _iface_ops(txt):
    comps = parse_hlo(txt)
    return {v[0] for insts in comps.values()
            for name, v in insts.items()
            if not name.startswith("__") and IFACE in v[2]}


def _compiled_cg(ss):
    o = SolverOptions(maxits=5, residual_rtol=1e-6)
    return lowered_step(ss, options=o).compile().as_text()


@pytest.mark.parametrize("case", ["7pt-z4", "27pt-z4"])
def test_slab_program_has_no_interface_gather(case):
    """The optimized cg_dist program of a z-slab: no gather carries the
    interface scope, the correction is dynamic slices, and the while body
    lowers no scatter (contract C5)."""
    _, ss, _ = _slab(case, dtype=np.float32)
    txt = _compiled_cg(ss)
    assert not _iface_gathers(txt)
    assert {"dynamic-slice", "dynamic-update-slice"} & _iface_ops(txt)
    assert while_body_profile(txt).scatters == 0


@pytest.mark.parametrize("case", ["multilevel", "x-split"])
def test_unstructured_program_keeps_the_interface_gather(case):
    """The fallback is the parent's program: the interface gather is
    still there."""
    _, ss = _unstructured(case)
    assert _iface_gathers(_compiled_cg(ss))
