"""Multi-RHS (batched) CG: equivalence, masking, kernels, export, CLI.

The batched contract (ISSUE 2): ``cg(A, stack([b1, b2]))`` solves the
systems INDEPENDENTLY inside one device loop — per-system iteration
counts and residual trajectories must match B separate solves, a system
that converges first must FREEZE (its history stops advancing, its
iteration count pins) while stragglers run on, and ``nrhs=1`` through
the 1-D path is bit-for-bit today's solver.
"""

import json

import numpy as np
import pytest

from acg_tpu.config import SolverOptions
from acg_tpu.errors import AcgError, Status
from acg_tpu.solvers.cg import cg, cg_pipelined
from acg_tpu.sparse import poisson2d_5pt

OPTS = SolverOptions(maxits=400, residual_rtol=1e-8)


def _rhs_pair(A, seed=0):
    rng = np.random.default_rng(seed)
    return np.ones(A.nrows), rng.standard_normal(A.nrows)


def _hist_close(got, want, rtol, floor_rel):
    """Trajectories agree entrywise down to the attainable-accuracy
    floor.  The batched reduction (sum over the system axis) and the 1-D
    ``jnp.vdot`` differ in summation order; classic CG carries that as
    ~1e-15 relative noise, the pipelined RECURRENCE amplifies it smoothly
    along the solve (measured: 1e-15 head -> ~1e-6 by iteration 40, f64),
    and below ``floor_rel``·|r0|² both trajectories are pure rounding
    noise — same decay curve, same exit, different last bits."""
    w0 = float(want[0]) if want[0] > 0 else 1.0
    floor = floor_rel * w0
    big = want > floor
    np.testing.assert_allclose(got[big], want[big], rtol=rtol)
    assert np.all(got[~big] <= np.maximum(1e3 * want[~big], 10 * floor))


def _assert_matches_sequential(solver, A, bs, opts=OPTS, x_rtol=1e-6,
                               hist_rtol=1e-5, floor_rel=1e-14,
                               its_slack=0, **kw):
    """Batched solve == the B independent solves: per-system iteration
    counts within ``its_slack``, matching trajectories and solutions."""
    seq = [solver(A, b, options=opts, **kw) for b in bs]
    res = solver(A, np.stack(bs), options=opts, **kw)
    assert res.nrhs == len(bs)
    its = [int(k) for k in res.iterations_per_system]
    assert all(abs(k - r.niterations) <= its_slack
               for k, r in zip(its, seq)), (its, [r.niterations for r in seq])
    assert res.niterations == max(its)
    assert bool(res.converged) and all(res.converged_per_system)
    for i, r in enumerate(seq):
        np.testing.assert_allclose(res.x[i], r.x, rtol=x_rtol,
                                   atol=x_rtol * np.abs(r.x).max())
        hi = res.residual_history[i]
        k = min(its[i], r.niterations)
        _hist_close(hi[: k + 1], r.residual_history[: k + 1],
                    hist_rtol, floor_rel)
        # the active-mask freeze: history stops advancing at this
        # system's own exit (NaN fill past it)
        assert np.all(np.isnan(hi[its[i] + 1:]))
    return res, seq


def test_batched_matches_sequential_classic():
    A = poisson2d_5pt(12)
    _assert_matches_sequential(cg, A, _rhs_pair(A))


def test_batched_matches_sequential_pipelined():
    A = poisson2d_5pt(12)
    # the pipelined recurrence amplifies reduction-order noise along the
    # solve (see _hist_close) — same exit, looser trajectory tail
    _assert_matches_sequential(cg_pipelined, A, _rhs_pair(A),
                               hist_rtol=1e-3, floor_rel=1e-12)


def test_batched_matches_sequential_b4():
    A = poisson2d_5pt(10)
    rng = np.random.default_rng(3)
    bs = [rng.standard_normal(A.nrows) for _ in range(4)]
    _assert_matches_sequential(cg, A, bs)


def test_batched_matches_sequential_ell():
    A = poisson2d_5pt(10)
    _assert_matches_sequential(cg, A, _rhs_pair(A), fmt="ell")


def test_batched_matches_sequential_f32_bf16_bands():
    """f32 vectors with the mat_dtype='auto' bf16-narrowed band storage
    (Poisson bands are bf16-exact) AND full-width f32 storage."""
    A = poisson2d_5pt(12)
    b1, b2 = _rhs_pair(A)
    opts = SolverOptions(maxits=400, residual_rtol=1e-5)
    # f32 reductions over the batch axis and over one vector are summed
    # in different orders, and the XLA of jax 0.9 fuses them differently
    # enough that a system can cross rtol 1e-5 one iteration apart: the
    # claim at f32 is the same exit within one iteration
    for mat_dtype in ("auto", None):
        _assert_matches_sequential(cg, A, (b1, b2), opts=opts,
                                   x_rtol=2e-3, hist_rtol=1e-2,
                                   floor_rel=1e-7, its_slack=1,
                                   dtype=np.float32, mat_dtype=mat_dtype)
        _assert_matches_sequential(cg_pipelined, A, (b1, b2), opts=opts,
                                   x_rtol=2e-3, hist_rtol=5e-2,
                                   floor_rel=1e-6, its_slack=1,
                                   dtype=np.float32, mat_dtype=mat_dtype)


def test_batched_sgell_interpret():
    from acg_tpu.ops.sgell import build_device_sgell

    A = poisson2d_5pt(16)
    dev = build_device_sgell(A, dtype=np.float32, interpret=True,
                             min_fill=0.0)
    assert dev is not None
    opts = SolverOptions(maxits=400, residual_rtol=1e-5)
    b1, b2 = _rhs_pair(A)
    res, _ = _assert_matches_sequential(cg, dev, (b1, b2), opts=opts,
                                        x_rtol=2e-3, hist_rtol=1e-2,
                                        floor_rel=1e-7)
    assert res.kernel == "pallas-sgell-interpret"


def test_batched_mask_zero_rhs_converges_at_zero():
    """A zero RHS is converged at k=0; its carries freeze for the whole
    solve while the other system runs — per-system iterations must read
    [k1, 0] and the zero system's history must be the single |r0|²=0
    sample."""
    A = poisson2d_5pt(12)
    b1 = np.ones(A.nrows)
    res = cg(A, np.stack([b1, np.zeros(A.nrows)]), options=OPTS)
    r1 = cg(A, b1, options=OPTS)
    assert list(res.iterations_per_system) == [r1.niterations, 0]
    assert res.residual_history[1, 0] == 0.0
    assert np.all(np.isnan(res.residual_history[1, 1:]))
    np.testing.assert_array_equal(res.x[1], np.zeros(A.nrows))
    np.testing.assert_allclose(res.x[0], r1.x, rtol=1e-9)


def test_batched_mask_different_convergence_counts():
    """Systems engineered to converge at different iteration counts: the
    early one's trajectory/iterate must be identical to its own
    independent solve (no leakage from the straggler's extra
    iterations)."""
    A = poisson2d_5pt(12)
    # a smooth RHS (in the low modes) converges much faster than noise
    xs = np.arange(A.nrows, dtype=np.float64)
    b_easy = A.matvec(np.ones(A.nrows))
    b_hard = np.sin(xs * 977.0)
    r_easy = cg(A, b_easy, options=OPTS)
    r_hard = cg(A, b_hard, options=OPTS)
    assert r_easy.niterations != r_hard.niterations
    _assert_matches_sequential(cg, A, (b_easy, b_hard))


def test_batched_b1_matches_1d_path():
    """(1, n) batched solve reproduces the 1-D solve (identical iteration
    count; trajectories equal to reduction-order noise)."""
    A = poisson2d_5pt(12)
    b = np.ones(A.nrows)
    r = cg(A, b, options=OPTS)
    rb = cg(A, b[None, :], options=OPTS)
    assert rb.nrhs == 1
    assert list(rb.iterations_per_system) == [r.niterations]
    np.testing.assert_allclose(rb.residual_history[0], r.residual_history,
                               rtol=1e-12)
    np.testing.assert_allclose(rb.x[0], r.x, rtol=1e-12)
    # a one-system batch still exports a valid (flat-history) document
    from acg_tpu.obs.export import (build_stats_document,
                                    validate_stats_document)

    doc = build_stats_document(solver="acg", options=OPTS, res=rb,
                               stats=rb.stats)
    assert validate_stats_document(doc) == []


def test_batched_not_converged_raises_with_per_system_result():
    A = poisson2d_5pt(16)
    b1, b2 = _rhs_pair(A)
    with pytest.raises(AcgError) as ei:
        cg(A, np.stack([b1, b2]),
           options=SolverOptions(maxits=3, residual_rtol=1e-12))
    assert ei.value.status == Status.ERR_NOT_CONVERGED
    res = ei.value.result
    assert res.nrhs == 2
    assert list(res.iterations_per_system) == [3, 3]


def test_batched_relative_residual_pairs_one_system():
    """The scalar rnrm2/r0nrm2 summary must come from ONE system (the
    worst by relative residual) — max(rnrm2) over one system paired with
    max(r0nrm2) over another would understate a stalled unit-scale
    system hiding behind a converged huge-|r0| one."""
    A = poisson2d_5pt(12)
    rng = np.random.default_rng(7)
    b_small = rng.standard_normal(A.nrows)
    b_huge = 1e6 * np.ones(A.nrows)
    with pytest.raises(AcgError) as ei:
        cg(A, np.stack([b_huge, b_small]),
           options=SolverOptions(maxits=4, residual_rtol=1e-12))
    res = ei.value.result
    rel = np.asarray(res.rnrm2_per_system) \
        / np.asarray(res.r0nrm2_per_system)
    assert res.relative_residual == pytest.approx(rel.max(), rel=1e-12)
    # bnrm2 pairs with the SAME worst system (x0=0 => |b| = |r0|)
    assert res.bnrm2 == pytest.approx(
        res.r0nrm2_per_system[int(np.argmax(rel))], rel=1e-12)


def test_batched_x0_shape_contract():
    """1-D x0 broadcasts across the batch; a mismatched 2-D x0 raises a
    clean AcgError instead of an opaque while_loop carry TypeError."""
    from acg_tpu.solvers.cg_dist import cg_dist

    A = poisson2d_5pt(10)
    b1, b2 = _rhs_pair(A)
    bb = np.stack([b1, b2])
    x0 = 0.5 * b1
    res = cg(A, bb, x0=x0, options=OPTS)
    r0 = cg(A, b1, x0=x0, options=OPTS)
    assert res.iterations_per_system[0] == r0.niterations
    np.testing.assert_allclose(res.x[0], r0.x, rtol=1e-6)
    for solver, kw in ((cg, {}), (cg_dist, {"nparts": 4})):
        with pytest.raises(AcgError) as ei:
            solver(A, bb, x0=np.zeros((3, A.nrows)), options=OPTS, **kw)
        assert ei.value.status == Status.ERR_INVALID_VALUE
    # distributed 1-D broadcast too
    rd = cg_dist(A, bb, x0=x0, options=OPTS, nparts=4)
    assert rd.iterations_per_system[0] == r0.niterations


def test_cli_nrhs_manufactured_error_not_inflated(tmp_path, capsys):
    """--manufactured-solution --nrhs K must report a per-system error,
    not a sqrt(K)-inflated all-systems norm."""
    from acg_tpu.cli import main as cli_main
    from acg_tpu.io import write_mtx
    from acg_tpu.io.mtxfile import MtxFile

    A = poisson2d_5pt(8)
    r, c, v = A.to_coo()
    keep = r >= c
    m = MtxFile(symmetry="symmetric", nrows=A.nrows, ncols=A.ncols,
                nnz=int(keep.sum()), rowidx=r[keep], colidx=c[keep],
                vals=v[keep])
    mtx = tmp_path / "A.mtx"
    write_mtx(mtx, m)
    errs = []
    for flags in ([], ["--nrhs", "4"]):
        rc = cli_main([str(mtx), "--manufactured-solution",
                       "--max-iterations", "500", "--residual-rtol",
                       "1e-10", "-q", "--warmup", "0"] + flags)
        assert rc == 0
        out = capsys.readouterr().out
        errs.append(float(out.split("manufactured solution error: ")[1]
                          .split()[0]))
    assert errs[1] == pytest.approx(errs[0], rel=1e-6)


def test_batched_fixed_iteration_protocol():
    """No stopping criteria: every system runs exactly maxits (the
    benchmark protocol), histories fully live."""
    A = poisson2d_5pt(10)
    b1, b2 = _rhs_pair(A)
    res = cg(A, np.stack([b1, b2]),
             options=SolverOptions(maxits=20, residual_rtol=0.0))
    assert list(res.iterations_per_system) == [20, 20]
    assert res.residual_history.shape == (2, 21)
    assert np.all(np.isfinite(res.residual_history))


# ---------------------------------------------------------------------------
# distributed (CPU mesh)


def test_batched_dist_matches_sequential():
    from acg_tpu.solvers.cg_dist import cg_dist, cg_pipelined_dist

    A = poisson2d_5pt(12)
    b1, b2 = _rhs_pair(A)
    for solver in (cg_dist, cg_pipelined_dist):
        seq = [solver(A, b, options=OPTS, nparts=4) for b in (b1, b2)]
        res = solver(A, np.stack([b1, b2]), options=OPTS, nparts=4)
        assert res.nrhs == 2
        assert list(res.iterations_per_system) \
            == [r.niterations for r in seq]
        for i, r in enumerate(seq):
            np.testing.assert_allclose(res.x[i], r.x, rtol=1e-6,
                                       atol=1e-10)
            # same trajectory down to the attainable-accuracy floor
            # (below it both are rounding noise: 3.9e-6 apart at 1.6e-20
            # under jax 0.9)
            _hist_close(res.residual_history[i][: r.niterations + 1],
                        r.residual_history, 1e-6, 1e-14)


def test_batched_dist_allgather_halo():
    from acg_tpu.config import HaloMethod
    from acg_tpu.solvers.cg_dist import cg_dist

    A = poisson2d_5pt(12)
    b1, b2 = _rhs_pair(A)
    rp = cg_dist(A, np.stack([b1, b2]), options=OPTS, nparts=4)
    ra = cg_dist(A, np.stack([b1, b2]), options=OPTS, nparts=4,
                 method=HaloMethod.ALLGATHER)
    assert list(rp.iterations_per_system) \
        == list(ra.iterations_per_system)
    np.testing.assert_allclose(rp.x, ra.x, rtol=1e-9, atol=1e-12)


def test_batched_dist_collective_count_independent_of_B():
    """The halo exchange moves (B, nghost) packs through the SAME
    collectives: the per-iteration ppermute count in the compiled batched
    SOLVER program must equal the 1-D program's (amortization, not
    replication), while the payload bytes scale by exactly B.  Checked
    against the CommAudit of the compiled step (acg_tpu/obs/hlo.py) —
    the invariant as data, not a string grep."""
    from acg_tpu.obs.hlo import audit_compiled
    from acg_tpu.solvers.cg_dist import build_sharded, compile_step

    A = poisson2d_5pt(12)
    ss = build_sharded(A, nparts=4)

    def audit(nrhs):
        b = np.ones(A.nrows) if nrhs == 1 \
            else np.ones((nrhs, A.nrows))
        return audit_compiled(compile_step(ss, b, options=OPTS))

    a1, a4 = audit(1), audit(4)
    assert a4.ppermute.count == a1.ppermute.count > 0
    assert a4.allreduce.count == a1.allreduce.count > 0
    # (B, S) message blocks: per-iteration halo payload is exactly B×
    assert a1.ppermute.bytes > 0
    assert a4.ppermute.bytes == 4 * a1.ppermute.bytes


# ---------------------------------------------------------------------------
# batched Pallas kernel (interpret mode) + plan gates


def test_batched_pallas_kernel_interpret_matches():
    from acg_tpu.ops.pallas_kernels import _probe_batched_group

    assert _probe_batched_group(interpret=True)


def test_batched_pallas_plan_budget():
    from acg_tpu.ops.pallas_kernels import pallas_2d_batched_plan

    offs = (-128, -1, 0, 1, 128)
    assert pallas_2d_batched_plan(4, 128 * 128, offs,
                                  np.float32, np.float32) is not None
    # a batch too large for VMEM must fall back (plan None)
    assert pallas_2d_batched_plan(512, 512 * 128, offs,
                                  np.float32, np.float32) is None
    # f64 outside kernel bounds
    assert pallas_2d_batched_plan(2, 128 * 128, offs,
                                  np.float64, np.float64) is None


def test_batched_fused_loop_interpret_matches_sequential(monkeypatch):
    """The classic batched solve THROUGH the batched fused kernel
    (interpret mode, probe monkeypatched on) reproduces the sequential
    solves — the same forcing discipline as the 1-D fused-path test."""
    from acg_tpu.ops import pallas_kernels as pk
    from acg_tpu.sparse import poisson3d_7pt

    orig = pk.dia_matvec_pallas_2d_padded_batched
    used = {}

    def interp(*a, **k):
        used["batched"] = True
        k["interpret"] = True
        return orig(*a, **k)

    monkeypatch.setattr(pk, "dia_matvec_pallas_2d_padded_batched", interp)
    monkeypatch.setitem(pk._SPMV_PROBE, "batched2d", True)
    A = poisson3d_7pt(8, dtype=np.float32)
    opts = SolverOptions(maxits=200, residual_rtol=1e-5)
    b1, b2 = _rhs_pair(A)
    res, _ = _assert_matches_sequential(cg, A, (b1, b2), opts=opts,
                                        x_rtol=2e-3, hist_rtol=1e-2,
                                        floor_rel=1e-7, dtype=np.float32)
    assert used.get("batched"), "batched fused kernel was not selected"
    assert res.kernel == "pallas-resident-batched"


# ---------------------------------------------------------------------------
# export schema /2 + CLI --nrhs


def test_batched_stats_export_per_system():
    from acg_tpu.obs.export import (build_stats_document,
                                    validate_stats_document)

    A = poisson2d_5pt(12)
    b1, b2 = _rhs_pair(A)
    res = cg(A, np.stack([b1, b2]), options=OPTS)
    doc = build_stats_document(solver="acg", options=OPTS, res=res,
                               stats=res.stats, nunknowns=A.nrows)
    assert validate_stats_document(doc) == []
    from acg_tpu.obs.export import SCHEMA
    assert doc["schema"] == SCHEMA          # current version (/3)
    r = doc["result"]
    assert r["nrhs"] == 2
    assert r["iterations_per_system"] \
        == [int(v) for v in res.iterations_per_system]
    # each trajectory trimmed to ITS OWN iteration count
    for i in range(2):
        assert len(r["residual_history"][i]) \
            == r["iterations_per_system"][i] + 1
    doc2 = json.loads(json.dumps(doc))
    assert validate_stats_document(doc2) == []


def test_cli_nrhs_1_identical_to_default(tmp_path):
    """Acceptance: --nrhs 1 is numerically identical to today's solver
    output — same iteration count, same residual_history, bit for bit."""
    from acg_tpu.cli import main as cli_main
    from acg_tpu.io import write_mtx
    from acg_tpu.io.mtxfile import MtxFile

    A = poisson2d_5pt(8)
    r, c, v = A.to_coo()
    keep = r >= c
    m = MtxFile(symmetry="symmetric", nrows=A.nrows, ncols=A.ncols,
                nnz=int(keep.sum()), rowidx=r[keep], colidx=c[keep],
                vals=v[keep])
    mtx = tmp_path / "A.mtx"
    write_mtx(mtx, m)
    docs = []
    for flags in ([], ["--nrhs", "1"]):
        out = tmp_path / f"s{len(docs)}.json"
        rc = cli_main([str(mtx), "--max-iterations", "400",
                       "--residual-rtol", "1e-10", "-q", "--warmup", "0",
                       "--output-stats-json", str(out)] + flags)
        assert rc == 0
        docs.append(json.loads(out.read_text()))
    assert docs[0]["result"]["niterations"] \
        == docs[1]["result"]["niterations"]
    assert docs[0]["result"]["residual_history"] \
        == docs[1]["result"]["residual_history"]
    assert docs[1]["result"]["nrhs"] == 1


def test_cli_nrhs_batched_runs_and_exports(tmp_path):
    from acg_tpu.cli import main as cli_main
    from acg_tpu.io import write_mtx
    from acg_tpu.io.mtxfile import MtxFile
    from acg_tpu.obs.export import load_stats_document

    A = poisson2d_5pt(8)
    r, c, v = A.to_coo()
    keep = r >= c
    m = MtxFile(symmetry="symmetric", nrows=A.nrows, ncols=A.ncols,
                nnz=int(keep.sum()), rowidx=r[keep], colidx=c[keep],
                vals=v[keep])
    mtx = tmp_path / "A.mtx"
    write_mtx(mtx, m)
    out = tmp_path / "stats.json"
    rc = cli_main([str(mtx), "--nrhs", "3", "--max-iterations", "400",
                   "--residual-rtol", "1e-10", "-q", "--warmup", "0",
                   "--output-stats-json", str(out)])
    assert rc == 0
    doc = load_stats_document(str(out))      # validates on load
    r = doc["result"]
    assert r["nrhs"] == 3
    # replicated RHS: identical systems, identical per-system counts
    assert len(set(r["iterations_per_system"])) == 1
    assert all(r["converged_per_system"])


def test_cli_nrhs_rejects_host_solver(tmp_path):
    from acg_tpu.cli import main as cli_main
    from acg_tpu.io import write_mtx
    from acg_tpu.io.mtxfile import MtxFile

    A = poisson2d_5pt(8)
    r, c, v = A.to_coo()
    keep = r >= c
    m = MtxFile(symmetry="symmetric", nrows=A.nrows, ncols=A.ncols,
                nnz=int(keep.sum()), rowidx=r[keep], colidx=c[keep],
                vals=v[keep])
    mtx = tmp_path / "A.mtx"
    write_mtx(mtx, m)
    rc = cli_main([str(mtx), "--nrhs", "2", "--solver", "host", "-q"])
    assert rc != 0


# ---------------------------------------------------------------------------
# bench_batched smoke (tier-1: the suite wiring must keep executing)


def test_bench_batched_dry_run_smoke(capsys):
    from acg_tpu.obs.export import validate_bench_record
    from scripts.bench_batched import main as bench_main

    assert bench_main(["--dry-run", "--batches", "1,2"]) == 0
    lines = [ln for ln in capsys.readouterr().out.splitlines()
             if ln.strip().startswith("{")]
    assert len(lines) == 2
    for ln, want_b in zip(lines, (1, 2)):
        rec = json.loads(ln)
        assert validate_bench_record(rec) == []
        assert rec["nrhs"] == want_b
        assert rec["unit"] == "it/s*rhs"
        assert rec["dry_run"] is True
