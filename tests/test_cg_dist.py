"""Distributed CG integration tests on the 8-device CPU mesh (SURVEY §7.4,
BASELINE.md milestone: 8-way partitioned Poisson with ppermute halo)."""

import dataclasses

import numpy as np
import pytest

from acg_tpu.config import HaloMethod, SolverOptions
from acg_tpu.errors import AcgError, Status
from acg_tpu.solvers import cg_host
from acg_tpu.solvers.cg_dist import build_sharded, cg_dist, cg_pipelined_dist
from acg_tpu.sparse import poisson2d_5pt, poisson3d_7pt, coo_to_csr
from acg_tpu.sparse.csr import manufactured_rhs
from acg_tpu.sparse.poisson import grid_partition_vector

OPTS = SolverOptions(maxits=1000, residual_rtol=1e-10)


@pytest.mark.parametrize("nparts", [2, 4, 8])
def test_cg_dist_manufactured(nparts):
    A = poisson3d_7pt(6)
    xstar, b = manufactured_rhs(A, seed=0)
    res = cg_dist(A, b, options=OPTS, nparts=nparts)
    assert res.converged
    np.testing.assert_allclose(res.x, xstar, atol=1e-8)
    assert res.relative_residual < 1e-10


def test_cg_dist_matches_host_iterations():
    A = poisson2d_5pt(12)
    _, b = manufactured_rhs(A, seed=1)
    res_h = cg_host(A, b, options=OPTS)
    res_d = cg_dist(A, b, options=OPTS, nparts=8)
    assert abs(res_d.niterations - res_h.niterations) <= 2
    np.testing.assert_allclose(res_d.x, res_h.x, atol=1e-8)


@pytest.mark.parametrize("method", [HaloMethod.PPERMUTE, HaloMethod.ALLGATHER])
def test_cg_dist_halo_methods_agree(method):
    A = poisson3d_7pt(5)
    xstar, b = manufactured_rhs(A, seed=2)
    res = cg_dist(A, b, options=OPTS, nparts=8, method=method)
    np.testing.assert_allclose(res.x, xstar, atol=1e-8)


@pytest.mark.parametrize("nparts", [4, 8])
def test_cg_pipelined_dist(nparts):
    A = poisson3d_7pt(6)
    xstar, b = manufactured_rhs(A, seed=3)
    res = cg_pipelined_dist(A, b, options=OPTS, nparts=nparts)
    assert res.converged
    np.testing.assert_allclose(res.x, xstar, atol=1e-7)


def test_cg_dist_grid_partition():
    # structured partition via grid blocks (the METIS-free structured path)
    A = poisson2d_5pt(16)
    xstar, b = manufactured_rhs(A, seed=4)
    part = grid_partition_vector((16, 16), (4, 2))
    res = cg_dist(A, b, options=OPTS, part=part)
    np.testing.assert_allclose(res.x, xstar, atol=1e-8)


def test_cg_dist_prebuilt_system_reuse():
    # init/solve split (ref acgsolvercuda_init + repeated solves)
    A = poisson2d_5pt(10)
    ss = build_sharded(A, nparts=4)
    for seed in (5, 6):
        xstar, b = manufactured_rhs(A, seed=seed)
        res = cg_dist(ss, b, options=OPTS)
        np.testing.assert_allclose(res.x, xstar, atol=1e-8)
    assert res.stats.nsolves == 1  # fresh stats object per call


def test_cg_dist_not_converged():
    A = poisson2d_5pt(12)
    b = np.ones(A.nrows)
    with pytest.raises(AcgError) as ei:
        cg_dist(A, b, nparts=4,
                options=SolverOptions(maxits=3, residual_rtol=1e-12))
    assert ei.value.status == Status.ERR_NOT_CONVERGED
    assert ei.value.result.x.shape == (A.nrows,)


def test_cg_dist_x0():
    A = poisson2d_5pt(10)
    xstar, b = manufactured_rhs(A, seed=7)
    x0 = np.random.default_rng(8).standard_normal(A.nrows)
    res = cg_dist(A, b, x0=x0, options=OPTS, nparts=4)
    np.testing.assert_allclose(res.x, xstar, atol=1e-8)


def test_cg_dist_fp32():
    A = poisson2d_5pt(10)
    xstar, b = manufactured_rhs(A, seed=9)
    res = cg_dist(A, b, nparts=4, dtype=np.float32,
                  options=SolverOptions(maxits=2000, residual_rtol=1e-5))
    assert res.converged
    np.testing.assert_allclose(res.x, xstar, atol=1e-3)


def test_cg_dist_irregular_sizes():
    # n not divisible by nparts -> uneven shards exercise padding
    A = poisson2d_5pt(7, 9)   # 63 rows over 4 parts
    xstar, b = manufactured_rhs(A, seed=10)
    res = cg_dist(A, b, options=OPTS, nparts=4)
    np.testing.assert_allclose(res.x, xstar, atol=1e-8)


def test_sharded_auto_mat_dtype_narrows_and_matches():
    """mat_dtype="auto" compresses the distributed operator storage
    exactly (lossless-bf16 tier for Poisson stencil bands — preferred
    over int8 per BENCH_r02) with an identical solve trajectory; vectors
    stay at the requested dtype."""
    import jax.numpy as jnp

    from acg_tpu.solvers.cg_dist import build_sharded

    A = poisson3d_7pt(6, dtype=np.float64)
    xstar, b = manufactured_rhs(A, seed=0)
    opts = SolverOptions(maxits=500, residual_rtol=1e-10)
    ss8 = build_sharded(A, nparts=4, dtype=np.float64, mat_dtype="auto")
    assert ss8.local_fmt == "dia"
    assert ss8.lbands.dtype == jnp.bfloat16 and ss8.lscales is None
    assert ss8.vec_dtype == "float64"
    ssfull = build_sharded(A, nparts=4, dtype=np.float64, mat_dtype=None)
    assert ssfull.lbands.dtype == np.float64 and ssfull.lscales is None
    r8 = cg_dist(ss8, b, options=opts)
    rfull = cg_dist(ssfull, b, options=opts)
    assert r8.niterations == rfull.niterations
    # storage tiers are value-exact, but the differently-typed compiled
    # programs may reassociate fma chains: agreement to ~1 ulp
    np.testing.assert_allclose(r8.x, rfull.x, atol=1e-13)
    # the ELL gather form still narrows to bf16 and agrees
    ss16 = build_sharded(A, nparts=4, dtype=np.float64, mat_dtype="auto",
                         fmt="ell")
    assert ss16.local_fmt == "ell" and ss16.lvals.dtype == jnp.bfloat16
    r16 = cg_dist(ss16, b, options=opts)
    assert r16.niterations == rfull.niterations


# ── the DIA (gather-free) distributed fast path ──────────────────────────

def test_dist_auto_picks_dia_for_stencil():
    """Structured operators stream per-shard bands, not gathers: the local
    SpMV of the compiled distributed solver must contain no gather op (the
    VERDICT round-2 'fast distributed SpMV' requirement; ref overlapped
    split SpMV acg/cgcuda.c:847-883)."""
    import jax

    A = poisson3d_7pt(8)
    ss = build_sharded(A, nparts=4)
    assert ss.local_fmt == "dia"
    # auto partitioning detects the 8^3 grid and cuts it into boxes;
    # box-local band offsets are {0, ±1, ±zbox, ±ybox*zbox} — exactly 7
    # diagonals, symmetric, with ±1 present (the z-runs stay contiguous)
    offs = ss.loffsets
    assert len(offs) == 7 and offs == tuple(sorted(offs))
    assert {0, 1, -1} <= set(offs)
    assert all(-o in offs for o in offs)
    mv = ss.local_matvec_fn()
    ops = tuple(np.asarray(a)[0] for a in ss.local_op_arrays())
    x = np.zeros(ss.nown_max, dtype=ss.vec_dtype)
    hlo = jax.jit(lambda xv: mv(xv, ops)).lower(x).as_text()
    assert "gather" not in hlo


def test_dist_dia_matches_ell_exactly():
    A = poisson2d_5pt(16)
    xstar, b = manufactured_rhs(A, seed=11)
    rd = cg_dist(A, b, options=OPTS, nparts=8, fmt="dia")
    re = cg_dist(A, b, options=OPTS, nparts=8, fmt="ell")
    assert rd.niterations == re.niterations
    np.testing.assert_allclose(rd.x, re.x, atol=1e-12)
    np.testing.assert_allclose(rd.x, xstar, atol=1e-8)


def test_dist_dia_matches_single_chip_iterations():
    from acg_tpu.solvers.cg import cg

    A = poisson3d_7pt(8)
    xstar, b = manufactured_rhs(A, seed=12)
    rs = cg(A, b, options=OPTS)
    rd = cg_dist(A, b, options=OPTS, nparts=8)
    assert abs(rd.niterations - rs.niterations) <= 2
    np.testing.assert_allclose(rd.x, xstar, atol=1e-8)


def test_dist_auto_rcm_recovers_band_per_part():
    """Scrambled banded operator: global ordering is scattered, so parts
    come from rb — but per-part RCM recovers banded local blocks and the
    DIA path engages (distributed extension of the single-chip RCM
    route)."""
    from acg_tpu.sparse.rcm import permute_symmetric

    n = 1024
    i = np.arange(n - 1)
    r = np.r_[np.arange(n), i, i + 1]
    c = np.r_[np.arange(n), i + 1, i]
    v = np.r_[np.full(n, 4.0), np.full(n - 1, -1.0), np.full(n - 1, -1.0)]
    A = coo_to_csr(r, c, v, n, n)
    As = permute_symmetric(A, np.random.default_rng(13).permutation(n))
    ss = build_sharded(As, nparts=4, dtype=np.float64)
    assert ss.local_fmt == "dia"
    xstar, b = manufactured_rhs(As, seed=14)
    res = cg_dist(ss, b, options=SolverOptions(maxits=4000,
                                               residual_rtol=1e-10))
    np.testing.assert_allclose(res.x, xstar, atol=1e-7)


def test_dist_auto_keeps_ell_for_scattered():
    rng = np.random.default_rng(15)
    n, nnz = 400, 2000
    r = rng.integers(0, n, nnz)
    c = rng.integers(0, n, nnz)
    A = coo_to_csr(np.r_[r, np.arange(n)], np.r_[c, np.arange(n)],
                   np.r_[rng.standard_normal(nnz) * 0.01, np.full(n, 20.0)],
                   n, n, symmetrize=True)
    ss = build_sharded(A, nparts=4, dtype=np.float64)
    assert ss.local_fmt == "ell"
    xstar, b = manufactured_rhs(A, seed=16)
    res = cg_dist(ss, b, options=OPTS)
    np.testing.assert_allclose(res.x, xstar, atol=1e-7)


def test_halo_rdma_refused():
    """--halo rdma is unverified across chips: refused on every platform
    with an immediate, actionable error, never a wrong or silent result."""
    A = poisson2d_5pt(8)
    with pytest.raises(AcgError) as ei:
        build_sharded(A, nparts=4, method=HaloMethod.RDMA)
    assert ei.value.status == Status.ERR_NOT_SUPPORTED
    assert "rdma" in str(ei.value).lower()


def test_dist_rcm_localized_allgather_halo():
    """Per-part RCM relabeling must keep the ALLGATHER halo tables
    consistent too (pack positions are searchsorted over relabeled local
    indices — the order-sensitive path)."""
    from acg_tpu.sparse.rcm import permute_symmetric

    n = 512
    i = np.arange(n - 1)
    r = np.r_[np.arange(n), i, i + 1]
    c = np.r_[np.arange(n), i + 1, i]
    v = np.r_[np.full(n, 4.0), np.full(n - 1, -1.0), np.full(n - 1, -1.0)]
    A = coo_to_csr(r, c, v, n, n)
    As = permute_symmetric(A, np.random.default_rng(17).permutation(n))
    ss = build_sharded(As, nparts=4, dtype=np.float64,
                       method=HaloMethod.ALLGATHER)
    assert ss.local_fmt == "dia"          # rcm_localize engaged
    xstar, b = manufactured_rhs(As, seed=18)
    res = cg_dist(ss, b, options=SolverOptions(maxits=4000,
                                               residual_rtol=1e-10))
    np.testing.assert_allclose(res.x, xstar, atol=1e-7)


def test_cg_dist_single_part_degeneration():
    """nparts=1 must run unpartitioned on one device — the reference's
    single-process degeneration (every multi-rank path short-circuits,
    SURVEY §4.4; ref acgcomm commsize==1 special cases)."""
    A = poisson2d_5pt(9)
    xstar, b = manufactured_rhs(A, seed=19)
    res = cg_dist(A, b, options=OPTS, nparts=1)
    assert res.converged
    np.testing.assert_allclose(res.x, xstar, atol=1e-8)


def test_cg_dist_prebuilt_partitioned_system():
    """Library users can hand cg_dist a prebuilt PartitionedSystem (the
    offline-partition workflow); fmt=auto still resolves (with RCM
    recovery if its local order is scattered)."""
    from acg_tpu.partition.graph import partition_system
    from acg_tpu.partition.partitioner import partition_graph

    A = poisson2d_5pt(12)
    ps = partition_system(A, partition_graph(A, 4), local_order="interior")
    xstar, b = manufactured_rhs(A, seed=23)
    res = cg_dist(ps, b, options=OPTS)
    assert res.converged
    np.testing.assert_allclose(res.x, xstar, atol=1e-8)


def test_cg_dist_27pt_block_partition_many_neighbors():
    """27-pt stencil over 2x2x2 blocks: parts touch face, edge, AND corner
    neighbours (7 each here) — the densest edge-coloring schedule the halo
    builder faces; convergence through it validates the multi-round
    ppermute pipeline."""
    from acg_tpu.sparse import poisson3d_27pt

    A = poisson3d_27pt(8)
    part = grid_partition_vector((8, 8, 8), (2, 2, 2))
    ss = build_sharded(A, part=part, dtype=np.float64)
    assert ss.halo.nrounds >= 7          # every part exchanges with all 7
    xstar, b = manufactured_rhs(A, seed=25)
    res = cg_dist(ss, b, options=OPTS)
    assert res.converged
    np.testing.assert_allclose(res.x, xstar, atol=1e-8)


def test_dist_fused_path_matches_generic(monkeypatch):
    """The distributed fused padded path (per-shard permanently-padded
    carries + in-kernel local p'Ap inside shard_map) must reproduce the
    generic distributed solve — forced through interpret mode on CPU by
    monkeypatching the probe (VERDICT r3 item 3; ref overlapped hot loop
    acg/cgcuda.c:847-894)."""
    import jax.numpy as jnp

    from acg_tpu.ops import pallas_kernels as pk
    import importlib

    cgd = importlib.import_module("acg_tpu.solvers.cg_dist")

    # shards must be >= 2048 rows for the 256-aligned lane layout the
    # resident plan needs: 32^3 / 8 = 4096 rows per shard
    A = poisson3d_7pt(32, dtype=np.float32)
    xstar, b = manufactured_rhs(A, seed=23)
    opts = SolverOptions(maxits=400, residual_rtol=1e-6)
    res_generic = cg_dist(A, b, options=opts, nparts=8, dtype=np.float32)
    assert res_generic.converged

    used = {}
    orig = pk.dia_matvec_pallas_2d_padded

    def interp(*a, **k):
        used["fused"] = True
        k["interpret"] = True
        return orig(*a, **k)

    monkeypatch.setattr(pk, "dia_matvec_pallas_2d_padded", interp)
    monkeypatch.setitem(pk._SPMV_PROBE, "fused2d", True)
    # fresh system so the (plan-bearing) jitted solver is rebuilt
    ss = build_sharded(A, nparts=8, dtype=np.float32)
    assert cgd._dist_fused_plan(ss) is not None
    res_fused = cg_dist(ss, b, options=opts)
    res_again = cg_dist(ss, b, options=opts)  # cached solver reuse
    assert used.get("fused"), "fused kernel was not selected"
    assert res_fused.converged
    assert abs(res_fused.niterations - res_generic.niterations) <= 2
    np.testing.assert_allclose(res_fused.x, res_generic.x,
                               atol=1e-4 * np.abs(xstar).max())
    # the cached jitted solver must reproduce the first solve exactly
    assert res_again.niterations == res_fused.niterations
    np.testing.assert_array_equal(res_again.x, res_fused.x)

    # pipelined variant through the same padded kernel SpMV.  The f32
    # pipelined RECURRENCE stalls near |r|/|r0| ~ 2e-4 without drift
    # correction (the residual estimate walks away from the truth and
    # the 1e-6 exit is never certified), so this stage runs the
    # production configuration — replace_every=50, exactly what
    # bench_suite times — which converges in ~83 iterations
    res_pd = cg_pipelined_dist(
        ss, b, options=dataclasses.replace(opts, replace_every=50))
    assert res_pd.converged
    np.testing.assert_allclose(res_pd.x, xstar,
                               atol=1e-3 * np.abs(xstar).max())


def test_halo_and_local_spmv_are_data_independent():
    """The overlap claim (cg_dist.py: XLA may run the halo collective
    concurrently with the local SpMV, the reference's split-phase
    schedule, acg/cgcuda.c:847-883) rests on a graph property this test
    pins: in the per-shard matvec, the ppermute chain must not depend on
    the band stack (local SpMV inputs), and the local SpMV must not
    depend on ppermute outputs.  Verified at the jaxpr level — fusion
    renaming in optimized HLO cannot hide a dependence here.  (The
    scheduler's actual async overlap is only observable on multi-chip
    hardware; on the CPU mesh XLA emits synchronous collective-permute —
    checked 2026-07-31, zero -start/-done pairs in the compiled text.)"""
    import jax
    import jax.numpy as jnp

    from acg_tpu.ops.spmv import ell_matvec
    from acg_tpu.parallel.mesh import PARTS_AXIS

    A = poisson3d_7pt(8)
    ss = build_sharded(A, nparts=4)
    halo_fn = ss.shard_halo_fn()
    local_mv = ss.local_matvec_fn()
    lops = tuple(np.asarray(a)[0] for a in ss.local_op_arrays())
    tables = [np.asarray(a)[0] for a in
              (ss.send_idx, ss.recv_idx, ss.partner, ss.pack_idx,
               ss.ghost_src_part, ss.ghost_src_pos)]
    x0 = np.zeros(ss.nown_max, dtype=ss.vec_dtype)

    def matvec(x, bands):
        # bands ride as a traced ARGUMENT: a closure constant would be
        # folded into per-diagonal constvars and lose its identity
        ghosts = halo_fn(x, *tables)
        return local_mv(x, (bands, *lops[1:])) + ell_matvec(
            np.asarray(ss.ivals)[0], np.asarray(ss.icols)[0], ghosts)

    spec = jax.sharding.PartitionSpec()
    traced = jax.make_jaxpr(
        lambda xv, bv: jax.shard_map(
            matvec, mesh=ss.mesh, in_specs=(spec, spec),
            out_specs=spec, check_vma=False)(xv, bv)
    )(x0, lops[0])
    # walk into the shard_map inner jaxpr
    inner = None
    for eqn in traced.jaxpr.eqns:
        if "jaxpr" in eqn.params:
            inner = eqn.params["jaxpr"]
            break
    assert inner is not None
    jaxpr = inner.jaxpr if hasattr(inner, "jaxpr") else inner

    # producers map: var -> eqn
    prod = {}
    for eqn in jaxpr.eqns:
        for ov in eqn.outvars:
            prod[ov] = eqn

    def _vars(vs):
        return {v for v in vs if hasattr(v, "count")}   # skip Literals

    def ancestors(eqn, acc):
        for v in _vars(eqn.invars):
            if v in prod and v not in acc:
                acc.add(v)
                ancestors(prod[v], acc)
        return acc

    ppermutes = [e for e in jaxpr.eqns if e.primitive.name == "ppermute"]
    assert ppermutes, "halo schedule must contain ppermute"
    # the band stack consts enter as jaxpr constvars/invars; identify the
    # band array by shape among the jaxpr inputs
    band_shape = lops[0].shape
    band_vars = {v for v in (*jaxpr.invars, *jaxpr.constvars)
                 if getattr(v.aval, "shape", None) == band_shape}
    assert band_vars, "band stack not found among jaxpr inputs"
    for pp in ppermutes:
        anc = ancestors(pp, set())
        # the collective's transitive inputs never touch the band stack
        assert not (anc & band_vars) and not (_vars(pp.invars) & band_vars)
    # and the local SpMV (any consumer of the band stack) never consumes
    # a ppermute output
    pp_out = {v for pp in ppermutes for v in pp.outvars}
    for eqn in jaxpr.eqns:
        if _vars(eqn.invars) & band_vars:
            anc = ancestors(eqn, set())
            assert not (anc & pp_out) and not (_vars(eqn.invars) & pp_out)


def test_dist_host_cg_oracle_iterates():
    """A host-side DISTRIBUTED CG — per-part matvec through the halo
    oracle (PartitionedSystem.matvec) with globally-summed dots — must
    track cg_dist iterate-for-iterate: the host twin of the reference's
    acgsolver_solvempi (acg/cg.c:408), which doubles as the distributed
    oracle there."""
    from acg_tpu.partition.graph import partition_system
    from acg_tpu.partition.partitioner import partition_graph

    A = poisson3d_7pt(8)
    xstar, b = manufactured_rhs(A, seed=21)
    part = partition_graph(A, 4)
    ps = partition_system(A, part, local_order="band")

    # host distributed CG, beta-first rotation like loops.cg_while
    x = np.zeros(A.nrows)
    r = b - ps.matvec(x)
    rr = float(r @ r)
    rr0 = rr
    thresh2 = 1e-20 * rr0
    beta = 0.0
    p = np.zeros_like(b)
    iters_host = 0
    for k in range(1000):
        p = r + beta * p
        t = ps.matvec(p)
        alpha = rr / float(p @ t)
        x = x + alpha * p
        r = r - alpha * t
        rr_new = float(r @ r)
        iters_host = k + 1
        if rr_new < thresh2:
            break
        beta = rr_new / rr
        rr = rr_new

    res = cg_dist(A, b, part=part,
                  options=SolverOptions(maxits=1000, residual_rtol=1e-10))
    assert res.converged
    assert abs(res.niterations - iters_host) <= 2, (res.niterations,
                                                    iters_host)
    np.testing.assert_allclose(res.x, x, atol=1e-8)
    np.testing.assert_allclose(res.x, xstar, atol=1e-8)


def test_dist_sgell_local_fast_path():
    """Scattered local blocks that neither DIA nor per-part RCM->DIA can
    recover route to the per-shard segmented-gather ELL tier
    (interpret-forced on CPU), and the solve matches the generic ELL
    distributed solve — the distributed extension of the single-chip
    sgell route (the reference's merge-CSR local SpMV role,
    acg/cg-kernels-cuda.cu:340-441)."""
    from acg_tpu.sparse.csr import CsrMatrix

    # unstructured-but-local matrix (random window) so RCM-DIA fails on
    # each part but the sgell pack stays dense
    rng = np.random.default_rng(33)
    n, W = 2500, 7
    rows = np.repeat(np.arange(n), W)
    cols = np.clip(rows + rng.integers(-300, 301, size=n * W), 0, n - 1)
    uniq = np.unique(rows * np.int64(n) + cols)
    rows, cols = (uniq // n).astype(np.int64), (uniq % n).astype(np.int64)
    # symmetrize + diagonal dominance for SPD
    lo, hi = np.minimum(rows, cols), np.maximum(rows, cols)
    key = np.unique(lo * np.int64(n) + hi)
    lo, hi = key // n, key % n
    off = lo != hi
    v = rng.standard_normal(off.sum()) * 0.1
    r_all = np.concatenate([lo[off], hi[off], np.arange(n)])
    c_all = np.concatenate([hi[off], lo[off], np.arange(n)])
    deg = np.zeros(n)
    np.add.at(deg, lo[off], np.abs(v))
    np.add.at(deg, hi[off], np.abs(v))
    v_all = np.concatenate([v, v, deg + 1.0])
    from acg_tpu.sparse import coo_to_csr

    A = coo_to_csr(r_all, c_all, v_all, n, n)
    xstar, b = manufactured_rhs(A, seed=34)
    opts = SolverOptions(maxits=300, residual_rtol=1e-4)

    ss = build_sharded(A, nparts=4, dtype=np.float32, sgell_interpret=True)
    assert ss.local_fmt == "sgell", ss.local_fmt
    assert ss.sg_S > 0 and ss.nown_max % 1024 == 0
    res = cg_dist(ss, b, options=opts)
    assert res.converged
    res_ell = cg_dist(A, b, options=opts, nparts=4, dtype=np.float32,
                      fmt="ell")
    assert abs(res.niterations - res_ell.niterations) <= 3
    np.testing.assert_allclose(res.x, xstar,
                               atol=5e-3 * np.abs(xstar).max())
    # dtype-gate regression: dtype=None solves at float64 (ShardedSystem
    # default) regardless of A's value dtype — the f32-only sgell tier
    # must refuse, not hand Mosaic an f64 gather
    ss64 = build_sharded(A, nparts=4, sgell_interpret=True)
    assert ss64.local_fmt == "ell"


def test_dist_pipelined_iter_kernel_matches_generic(monkeypatch):
    """Distributed pipelined CG through the per-shard single-kernel
    iteration (pipe2d + interface correction: z' = z_k + I,
    w' = w_k - alpha*I, delta = delta_k - alpha*<I, r'>) must reproduce
    the generic distributed pipelined solve — interpret-forced on CPU."""
    import importlib


    from acg_tpu.ops import pallas_kernels as pk

    cgd = importlib.import_module("acg_tpu.solvers.cg_dist")

    A = poisson3d_7pt(32, dtype=np.float32)   # 4096-row shards (resident)
    xstar, b = manufactured_rhs(A, seed=41)
    # rtol 1e-5: the f32 pipelined recurrence drift floor sits near 1e-6
    # at this size (the generic path itself stalls there)
    opts = SolverOptions(maxits=400, residual_rtol=1e-5)
    res_generic = cg_pipelined_dist(A, b, options=opts, nparts=8,
                                    dtype=np.float32)
    assert res_generic.converged

    used = {}
    orig_pad = pk.dia_matvec_pallas_2d_padded
    orig_iter = pk.cg_pipelined_iter_pallas

    def interp_pad(*a, **k):
        k["interpret"] = True
        return orig_pad(*a, **k)

    def interp_iter(*a, **k):
        used["pipe2d"] = True
        k["interpret"] = True
        return orig_iter(*a, **k)

    monkeypatch.setattr(pk, "dia_matvec_pallas_2d_padded", interp_pad)
    monkeypatch.setattr(pk, "cg_pipelined_iter_pallas", interp_iter)
    monkeypatch.setitem(pk._SPMV_PROBE, "fused2d", True)
    monkeypatch.setitem(pk._SPMV_PROBE, "pipe2d", True)
    ss = build_sharded(A, nparts=8, dtype=np.float32)  # fresh solver cache
    assert cgd._dist_fused_plan(ss) is not None
    res_kernel = cg_pipelined_dist(ss, b, options=opts)
    assert used.get("pipe2d"), "per-shard pipe2d kernel was not selected"
    assert res_kernel.converged
    assert abs(res_kernel.niterations - res_generic.niterations) <= 2
    np.testing.assert_allclose(res_kernel.x, xstar,
                               atol=1e-3 * np.abs(xstar).max())
    np.testing.assert_allclose(res_kernel.x, res_generic.x,
                               atol=2e-4 * np.abs(res_generic.x).max())


def test_dist_pipelined_ell_local_fmt():
    """Distributed pipelined CG with a NON-DIA local tier (forced ell):
    the pipe2d gate must not touch DIA-only fields (lbands is None for
    ell/sgell shards — fuzz seed 239 crashed every such solve)."""
    A = poisson2d_5pt(12)
    xstar, b = manufactured_rhs(A, seed=5)
    res = cg_pipelined_dist(A, b, options=SolverOptions(
        maxits=500, residual_rtol=1e-8), nparts=3, fmt="ell")
    assert res.converged
    assert res.operator_format == "ell"
    np.testing.assert_allclose(res.x, xstar,
                               atol=1e-5 * np.abs(xstar).max())


def test_dist_segment_iters_bit_identical():
    """Distributed segment_iters (VERDICT r5 weak #6): a segmented
    distributed solve re-dispatches the SAME shard_map'd loop body from
    the exact carry — bit-identical to the unsegmented solve, including
    the residual trajectory, for 1-D and batched right-hand sides."""
    A = poisson3d_7pt(12, dtype=np.float32)
    xstar, b = manufactured_rhs(A, seed=5)
    ss = build_sharded(A, nparts=8, dtype=np.float32)
    o1 = SolverOptions(maxits=200, residual_rtol=1e-5)
    o2 = SolverOptions(maxits=200, residual_rtol=1e-5, segment_iters=7)
    res1 = cg_dist(ss, b, options=o1)
    res2 = cg_dist(ss, b, options=o2)
    assert res2.niterations == res1.niterations
    np.testing.assert_array_equal(res2.x, res1.x)
    np.testing.assert_array_equal(res2.residual_history,
                                  res1.residual_history)
    # batched: per-system carries (incl. the ksys element) survive the
    # segment boundary
    B = np.stack([b, 2 * b, -b])
    r1 = cg_dist(ss, B, options=o1)
    r2 = cg_dist(ss, B, options=SolverOptions(maxits=200,
                                              residual_rtol=1e-5,
                                              segment_iters=9))
    np.testing.assert_array_equal(r2.iterations_per_system,
                                  r1.iterations_per_system)
    np.testing.assert_array_equal(r2.x, r1.x)


def test_dist_segment_iters_pipelined_bit_identical():
    """Distributed pipelined segment_iters (ISSUE 7 satellite: wired
    through _shard_solver like classic got in PR 5): the segmented solve
    re-dispatches the SAME shard_map'd pipelined body from the exact
    carry (whose last element is the device-computed continue bit) —
    bit-identical to the monolithic solve, 1-D and batched."""
    A = poisson3d_7pt(12, dtype=np.float32)
    xstar, b = manufactured_rhs(A, seed=6)
    ss = build_sharded(A, nparts=8, dtype=np.float32)
    o1 = SolverOptions(maxits=200, residual_rtol=1e-5)
    o2 = SolverOptions(maxits=200, residual_rtol=1e-5, segment_iters=7)
    res1 = cg_pipelined_dist(ss, b, options=o1)
    res2 = cg_pipelined_dist(ss, b, options=o2)
    assert res2.niterations == res1.niterations
    np.testing.assert_array_equal(res2.x, res1.x)
    np.testing.assert_array_equal(res2.residual_history,
                                  res1.residual_history)
    # batched: the per-system done/ksys carry elements survive the
    # segment boundary
    B = np.stack([b, 2 * b, -b])
    r1 = cg_pipelined_dist(ss, B, options=o1)
    r2 = cg_pipelined_dist(ss, B, options=SolverOptions(
        maxits=200, residual_rtol=1e-5, segment_iters=9))
    np.testing.assert_array_equal(r2.iterations_per_system,
                                  r1.iterations_per_system)
    np.testing.assert_array_equal(r2.x, r1.x)
