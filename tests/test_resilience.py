"""Resilience layer (ISSUE 4): fault injection, detection, self-healing.

The deterministic subset of the injection matrix — every device fault
kind × {classic, pipelined} × {single-chip, CPU-mesh distributed}, plus
the host faults (killed segment, corrupt checkpoint) — driven through
``solve_resilient()`` with the certified TRUE residual asserted, plus
the detection layer, breakdown classification, checkpoint hardening,
the acg-tpu-stats/4 ``resilience`` block, and the zero-overhead proof
(guard adds no collectives; resilience off compiles the pre-PR
program).  The randomized extension is ``scripts/fuzz_solvers.py
--faults``.
"""

import dataclasses
import json
import os

import numpy as np
import pytest

from acg_tpu.config import SolverOptions
from acg_tpu.errors import AcgError, Status
from acg_tpu.robust.faults import (FaultSpec, SITE_SPMV,
                                   inject_reduction, inject_vector)
from acg_tpu.robust.supervisor import solve_resilient
from acg_tpu.solvers.cg import cg, cg_pipelined
from acg_tpu.solvers.cg_dist import cg_dist
from acg_tpu.solvers.cg_host import cg_host
from acg_tpu.sparse import poisson2d_5pt
from acg_tpu.sparse.csr import coo_to_csr
from acg_tpu.utils.checkpoint import load_checkpoint, save_checkpoint

OPTS = SolverOptions(maxits=500, residual_rtol=1e-10)
GUARDED = dataclasses.replace(OPTS, guard_nonfinite=True)


@pytest.fixture(scope="module")
def problem():
    A = poisson2d_5pt(8)
    return A, np.ones(A.nrows)


def _true_rel(A, b, x):
    import scipy.sparse as sp

    S = sp.csr_matrix((A.vals, A.colidx, A.rowptr),
                      shape=(A.nrows, A.ncols))
    x = np.asarray(x, np.float64)
    return np.linalg.norm(S @ x - b) / np.linalg.norm(b)


# ---------------------------------------------------------------------------
# FaultSpec parsing (the CLI surface)


def test_fault_spec_parse_kinds_and_modes():
    f = FaultSpec.parse("spmv-nan@7")
    assert (f.kind, f.mode, f.iteration) == ("spmv", "nan", 7)
    assert FaultSpec.parse("halo@12").kind == "halo"
    assert FaultSpec.parse("halo-pack@3").kind == "halo"
    assert FaultSpec.parse("reduction-scale@5").mode == "scale"
    assert FaultSpec.parse("carry-inf@2").mode == "inf"
    k = FaultSpec.parse("killed-segment@1")
    assert k.kind == "segment-kill" and not k.is_device
    assert FaultSpec.parse("corrupt-checkpoint@0").kind == \
        "checkpoint-corrupt"
    assert str(FaultSpec.parse("spmv-inf@4")) == "spmv-inf@4"


@pytest.mark.parametrize("bad", ["spmv", "nope@3", "spmv@x", "halo@-1"])
def test_fault_spec_parse_rejects(bad):
    with pytest.raises(AcgError) as ei:
        FaultSpec.parse(bad)
    assert ei.value.status == Status.ERR_INVALID_VALUE


def test_device_plan_for_host_fault_rejected():
    with pytest.raises(AcgError):
        FaultSpec.parse("segment-kill@1").device_plan(np.float64)


# ---------------------------------------------------------------------------
# injection primitives: data-only selection, single-element corruption


def test_inject_vector_strikes_only_its_iteration():
    import jax.numpy as jnp

    plan = FaultSpec("spmv", iteration=3, index=2).device_plan(np.float64)
    v = jnp.arange(8.0)
    hit = inject_vector(plan, SITE_SPMV, jnp.asarray(3), v)
    miss = inject_vector(plan, SITE_SPMV, jnp.asarray(4), v)
    wrong_site = inject_vector(plan, 1, jnp.asarray(3), v)
    # the struck element is index offset from the MIDPOINT — kept clear
    # of the zero pad slots of the internal layouts (faults.py)
    assert np.isnan(np.asarray(hit)[(8 // 2 + 2) % 8])
    assert np.isfinite(np.asarray(hit)).sum() == 7
    np.testing.assert_array_equal(np.asarray(miss), np.arange(8.0))
    np.testing.assert_array_equal(np.asarray(wrong_site), np.arange(8.0))
    # None plan is the identity and traces nothing
    assert inject_vector(None, SITE_SPMV, 0, v) is v


def test_inject_scale_delivers_on_zero_element():
    """A multiplicative fault on an exactly-zero element would deliver
    nothing (and a trial would pass vacuously); scale mode injects the
    factor absolutely there — the exponent-bit-flip of 0.0 is a power
    of two, not zero."""
    import jax.numpy as jnp

    plan = FaultSpec("spmv", iteration=0, mode="scale",
                     scale=1e8).device_plan(np.float64)
    v = jnp.zeros(8)
    out = np.asarray(inject_vector(plan, SITE_SPMV, jnp.asarray(0), v))
    assert out[4] == 1e8 and np.count_nonzero(out) == 1


def test_inject_scale_mode_multiplies_one_element():
    import jax.numpy as jnp

    plan = FaultSpec("reduction", iteration=1, mode="scale",
                     scale=1e6).device_plan(np.float64)
    s = jnp.asarray(2.0)
    assert float(inject_reduction(plan, jnp.asarray(1), s)) == 2e6
    assert float(inject_reduction(plan, jnp.asarray(2), s)) == 2.0


# ---------------------------------------------------------------------------
# detection: the guard raises ERR_FAULT_DETECTED with a partial result


@pytest.mark.parametrize("solver", [cg, cg_pipelined])
def test_guard_detects_injected_nan(problem, solver):
    A, b = problem
    with pytest.raises(AcgError) as ei:
        solver(A, b, options=GUARDED, dtype=np.float64,
               fault=FaultSpec.parse("spmv-nan@5"))
    e = ei.value
    assert e.status == Status.ERR_FAULT_DETECTED
    res = e.result
    assert res.status == Status.ERR_FAULT_DETECTED
    assert not res.converged
    assert "on-device guard" in res.fpexcept
    # detection is prompt: within a couple of iterations of the strike
    assert 5 <= res.niterations <= 8


def test_guard_detects_on_mesh(problem):
    A, b = problem
    with pytest.raises(AcgError) as ei:
        cg_dist(A, b, options=GUARDED, nparts=4, dtype=np.float64,
                fault=FaultSpec.parse("reduction-inf@4"))
    assert ei.value.status == Status.ERR_FAULT_DETECTED


def test_without_guard_nan_spins_to_not_converged(problem):
    """The pre-existing behavior the guard exists to fix: an unguarded
    NaN solve burns the whole budget and exits ERR_NOT_CONVERGED —
    never the fault classification."""
    A, b = problem
    opts = dataclasses.replace(OPTS, maxits=40)
    with pytest.raises(AcgError) as ei:
        cg(A, b, options=opts, dtype=np.float64,
           fault=FaultSpec.parse("carry-nan@3"))
    assert ei.value.status == Status.ERR_NOT_CONVERGED
    assert ei.value.result.niterations == 40


def test_detection_rides_check_every(problem):
    """The guard is evaluated at the existing check_every points: with
    check_every=7 a fault at iteration 8 cannot be flagged before
    iteration 14."""
    A, b = problem
    opts = dataclasses.replace(GUARDED, check_every=7)
    with pytest.raises(AcgError) as ei:
        cg(A, b, options=opts, dtype=np.float64,
           fault=FaultSpec.parse("carry-nan@8"))
    assert ei.value.status == Status.ERR_FAULT_DETECTED
    assert ei.value.result.niterations == 14


# ---------------------------------------------------------------------------
# the injection matrix: every device fault kind x solver x mesh width
# recovers through solve_resilient() with a certified true residual
# (acceptance criterion; the full randomized matrix is the --faults fuzz)


@pytest.mark.parametrize("kind", ["spmv", "halo", "reduction", "carry"])
@pytest.mark.parametrize("solver,nparts", [
    ("cg", 1), ("cg-pipelined", 1), ("cg", 4), ("cg-pipelined", 4)])
def test_injection_matrix_recovers(problem, kind, solver, nparts):
    A, b = problem
    res, rep = solve_resilient(A, b, options=OPTS, solver=solver,
                               nparts=nparts, dtype=np.float64,
                               faults=[f"{kind}@5"])
    assert res.converged and res.status == Status.SUCCESS
    assert np.all(np.isfinite(res.x))
    assert _true_rel(A, b, res.x) < 1e-9
    # the report names the ladder step that fixed it
    assert rep.fixed_by == "restart"
    assert rep.restarts == 1
    assert rep.converged
    assert rep.certified_relative_residual < 1e-9
    assert any(s.action == "fault-detected" for s in rep.steps)
    # history is stitched across attempts: budget+1 samples
    assert len(res.residual_history) == res.niterations + 1


def test_segment_kill_recovers_from_checkpoint(problem, tmp_path):
    A, b = problem
    ckpt = str(tmp_path / "c.npz")
    res, rep = solve_resilient(A, b, options=OPTS, solver="cg",
                               dtype=np.float64,
                               faults=["segment-kill@1"],
                               checkpoint_path=ckpt, checkpoint_every=4)
    assert res.converged
    assert _true_rel(A, b, res.x) < 1e-9
    actions = [s.action for s in rep.steps]
    assert "segment-kill" in actions
    assert "checkpoint-restore" in actions
    assert rep.checkpoints_written > 0
    assert os.path.exists(ckpt)


def test_corrupt_checkpoint_recovers(problem, tmp_path):
    A, b = problem
    ckpt = str(tmp_path / "c.npz")
    res, rep = solve_resilient(A, b, options=OPTS, solver="cg",
                               dtype=np.float64,
                               faults=["checkpoint-corrupt@0"],
                               checkpoint_path=ckpt, checkpoint_every=4)
    assert res.converged
    actions = [s.action for s in rep.steps]
    assert "checkpoint-corrupt" in actions
    assert "checkpoint-restore-failed" in actions


@pytest.mark.parametrize("kind", ["segment-kill@1", "checkpoint-corrupt@0"])
@pytest.mark.parametrize("solver,nparts", [
    ("cg", 1), ("cg-pipelined", 1), ("cg", 4), ("cg-pipelined", 4)])
def test_host_fault_matrix_recovers(problem, tmp_path, kind, solver,
                                    nparts):
    """The host-fault half of the acceptance injection matrix: killed
    segments and corrupt checkpoints recover on every solver x mesh
    width, certified true residual."""
    A, b = problem
    ckpt = str(tmp_path / "c.npz")
    res, rep = solve_resilient(A, b, options=OPTS, solver=solver,
                               nparts=nparts, dtype=np.float64,
                               faults=[kind], checkpoint_path=ckpt,
                               checkpoint_every=5)
    assert res.converged
    assert _true_rel(A, b, res.x) < 1e-9
    assert rep.certified_relative_residual < 1e-9
    assert kind.split("@")[0] in [s.action for s in rep.steps]


def test_divergence_from_finite_corruption_recovers(problem):
    """A scaled (finite) reduction corruption poisons classic CG's
    beta/alpha recurrence and the solve DIVERGES with every value
    finite — invisible to the non-finiteness guard.  The supervisor's
    per-segment host certification catches the growth, refuses the
    diverged iterate, and the restart recovers."""
    A, b = problem
    res, rep = solve_resilient(A, b, options=OPTS, solver="cg",
                               dtype=np.float64,
                               faults=["reduction-scale@4"])
    assert res.converged
    assert rep.certified_relative_residual < 1e-9
    actions = [s.action for s in rep.steps]
    assert "divergence-detected" in actions or \
        "certify-failed" in actions or "attempt-exhausted" in actions
    assert rep.restarts >= 1 and rep.fixed_by is not None


def test_resilient_gives_up_with_report(problem):
    """An unfixable failure (indefinite matrix) walks the ladder to the
    host oracle and fails with BOTH the partial result and the report
    attached."""
    n = 32
    d = np.ones(n)
    d[n // 2] = -1.0
    A = coo_to_csr(np.arange(n), np.arange(n), d, n, n)
    b = np.ones(n)
    with pytest.raises(AcgError) as ei:
        solve_resilient(A, b, options=OPTS, solver="cg",
                        dtype=np.float64, max_restarts=3)
    e = ei.value
    assert e.result is not None
    rep = e.recovery
    assert not rep.converged
    assert rep.restarts == 3
    assert rep.final_status == "ERR_NOT_CONVERGED_INDEFINITE_MATRIX"
    # the ladder actually escalated (rungs appear on the steps)
    rungs = {s.rung for s in rep.steps if s.rung}
    assert "restart" in rungs


def test_resilient_plain_solve_no_recovery(problem):
    """A clean supervised solve: no restarts, fixed_by None, certified."""
    A, b = problem
    res, rep = solve_resilient(A, b, options=OPTS, solver="cg",
                               dtype=np.float64)
    assert res.converged and rep.restarts == 0 and rep.fixed_by is None
    assert rep.certified_relative_residual < 1e-9
    assert len(res.residual_history) == res.niterations + 1


# ---------------------------------------------------------------------------
# breakdown classification (satellite): indefinite matrices are a
# first-class status, not a silent maxits exhaustion


def _indefinite(n=24):
    d = np.ones(n)
    d[3] = -2.0
    return coo_to_csr(np.arange(n), np.arange(n), d, n, n), np.ones(n)


def test_indefinite_status_classic_single_chip():
    A, b = _indefinite()
    with pytest.raises(AcgError) as ei:
        cg(A, b, options=OPTS, dtype=np.float64)
    assert ei.value.status == Status.ERR_NOT_CONVERGED_INDEFINITE_MATRIX
    assert ei.value.result.status == \
        Status.ERR_NOT_CONVERGED_INDEFINITE_MATRIX


def test_indefinite_status_classic_distributed():
    A, b = _indefinite(32)
    with pytest.raises(AcgError) as ei:
        cg_dist(A, b, options=OPTS, nparts=4, dtype=np.float64)
    assert ei.value.result.status == \
        Status.ERR_NOT_CONVERGED_INDEFINITE_MATRIX


def test_indefinite_status_host_carries_partial_result():
    """cg_host's breakdown now attaches the partial result (satellite:
    the CLI must export stats for breakdown solves too)."""
    A, b = _indefinite()
    with pytest.raises(AcgError) as ei:
        cg_host(A, b, options=OPTS)
    res = ei.value.result
    assert res is not None
    assert res.status == Status.ERR_NOT_CONVERGED_INDEFINITE_MATRIX
    assert res.residual_history is not None


def test_pipelined_denominator_restart_keeps_success(problem):
    """SPD floor noise trips the pipelined denominator restart, never a
    breakdown: the solve stays status SUCCESS (the loop restarts its
    directions instead of dying — loops.py breakdown-handling note)."""
    A, b = problem
    res = cg_pipelined(A, b, options=dataclasses.replace(
        OPTS, residual_rtol=1e-13, maxits=2000), dtype=np.float64)
    assert res.converged and res.status == Status.SUCCESS


def test_not_converged_status(problem):
    A, b = problem
    with pytest.raises(AcgError) as ei:
        cg(A, b, options=dataclasses.replace(OPTS, maxits=2),
           dtype=np.float64)
    assert ei.value.result.status == Status.ERR_NOT_CONVERGED


def test_success_status(problem):
    A, b = problem
    res = cg(A, b, options=OPTS, dtype=np.float64)
    assert res.status == Status.SUCCESS


# ---------------------------------------------------------------------------
# checkpoint hardening (satellite)


def test_checkpoint_truncated_is_invalid_format(tmp_path):
    p = str(tmp_path / "c.npz")
    save_checkpoint(p, np.ones(16), niterations=3, rnrm2=0.5)
    size = os.path.getsize(p)
    with open(p, "r+b") as f:
        f.truncate(size // 3)
    with pytest.raises(AcgError) as ei:
        load_checkpoint(p)
    assert ei.value.status == Status.ERR_INVALID_FORMAT


def test_checkpoint_garbage_is_invalid_format(tmp_path):
    p = str(tmp_path / "c.npz")
    with open(p, "wb") as f:
        f.write(b"not a zip archive at all")
    with pytest.raises(AcgError) as ei:
        load_checkpoint(p)
    assert ei.value.status == Status.ERR_INVALID_FORMAT


def test_checkpoint_shape_validated_against_problem(tmp_path):
    p = str(tmp_path / "c.npz")
    save_checkpoint(p, np.ones(16))
    x, _, _, _ = load_checkpoint(p, expect_shape=(16,))
    assert x.shape == (16,)
    with pytest.raises(AcgError) as ei:
        load_checkpoint(p, expect_shape=(64,))
    assert ei.value.status == Status.ERR_INVALID_FORMAT
    assert "wrong matrix" in str(ei.value)


def test_checkpoint_dtype_validated(tmp_path):
    p = str(tmp_path / "c.npz")
    save_checkpoint(p, np.arange(8))          # integer payload
    with pytest.raises(AcgError) as ei:
        load_checkpoint(p)
    assert ei.value.status == Status.ERR_INVALID_FORMAT
    # a float checkpoint resumes any float problem (caller casts)
    save_checkpoint(p, np.ones(8, np.float32))
    load_checkpoint(p, expect_dtype=np.float64)


def test_checkpoint_nonfinite_payload_rejected(tmp_path):
    """A NaN-poisoned iterate (what a fault can leave behind) is never
    a valid resume point: resuming from it would NaN every threshold
    and spin an unguarded solve to maxits."""
    p = str(tmp_path / "c.npz")
    x = np.ones(16)
    x[5] = np.nan
    save_checkpoint(p, x)
    with pytest.raises(AcgError) as ei:
        load_checkpoint(p)
    assert ei.value.status == Status.ERR_INVALID_FORMAT
    assert "non-finite" in str(ei.value)


def test_resilient_exact_x0_certifies_at_entry(problem):
    """An (effectively) exact initial guess makes rtol-relative-to-r0
    uncertifiable (the target collapses below f64 precision); the
    supervisor certifies at entry instead of burning every attempt."""
    import scipy.sparse as sp

    A, b = problem
    S = sp.csr_matrix((A.vals, A.colidx, A.rowptr),
                      shape=(A.nrows, A.ncols))
    xex = sp.linalg.spsolve(S.tocsc(), b)
    res, rep = solve_resilient(A, b, x0=xex, options=OPTS, solver="cg",
                               dtype=np.float64)
    assert res.converged and res.niterations == 0
    assert rep.steps[0].action == "certified"


def test_checkpoint_missing_solution_array(tmp_path):
    p = str(tmp_path / "c.npz")
    np.savez(p, y=np.ones(4))
    with pytest.raises(AcgError) as ei:
        load_checkpoint(p)
    assert ei.value.status == Status.ERR_INVALID_FORMAT


# ---------------------------------------------------------------------------
# schema /4: the resilience block


def _doc(resilience=None, status="SUCCESS"):
    from acg_tpu.obs.export import build_stats_document
    from acg_tpu.solvers.base import SolveResult, SolveStats

    res = SolveResult(x=None, converged=True, niterations=2, bnrm2=1.0,
                      r0nrm2=1.0, rnrm2=0.1,
                      residual_history=[1.0, 0.5, 0.01])
    return build_stats_document(solver="acg", options=SolverOptions(),
                                res=res, stats=SolveStats(),
                                nunknowns=4, capabilities={},
                                resilience=resilience)


def test_stats_v4_null_resilience_validates():
    from acg_tpu.obs.export import SCHEMA, validate_stats_document

    doc = _doc(None)
    assert doc["schema"] == SCHEMA == "acg-tpu-stats/13"
    assert doc["resilience"] is None
    assert doc["result"]["status"] == "SUCCESS"
    assert validate_stats_document(doc) == []


def test_stats_v4_report_validates(problem):
    from acg_tpu.obs.export import validate_stats_document

    A, b = problem
    _, rep = solve_resilient(A, b, options=OPTS, solver="cg",
                             dtype=np.float64, faults=["spmv@3"])
    doc = _doc(rep.as_dict())
    assert validate_stats_document(doc) == []
    assert doc["resilience"]["fixed_by"] == "restart"


def test_stats_v4_requires_resilience_key():
    from acg_tpu.obs.export import validate_stats_document

    doc = _doc(None)
    del doc["resilience"]
    assert any("resilience" in p for p in validate_stats_document(doc))
    doc = _doc({"steps": "nope"})
    assert any("resilience.steps" in p
               for p in validate_stats_document(doc))


def test_stats_v3_documents_still_validate():
    """Back-compat: a pre-bump /3 document (no resilience block, no
    result.status) must keep linting."""
    from acg_tpu.obs.export import validate_stats_document

    doc = _doc(None)
    doc["schema"] = "acg-tpu-stats/3"
    del doc["resilience"]
    del doc["result"]["status"]
    assert validate_stats_document(doc) == []


# ---------------------------------------------------------------------------
# zero-overhead proof: resilience machinery adds no collectives, and
# resilience-off compiles a program whose CommAudit is unchanged (the
# absolute per-iteration counts are pinned by tests/test_hlo_audit.py;
# here we pin guard-on == guard-off equality so the guard can never
# grow a collective)


@pytest.mark.parametrize("pipelined", [False, True])
def test_guard_adds_no_collectives_distributed(problem, pipelined):
    from acg_tpu.obs.hlo import audit_compiled
    from acg_tpu.solvers.cg_dist import compile_step

    A, b = problem
    audits = {}
    for guard in (False, True):
        opts = dataclasses.replace(OPTS, maxits=5,
                                   guard_nonfinite=guard)
        audits[guard] = audit_compiled(compile_step(
            A, b, options=opts, pipelined=pipelined, nparts=4,
            dtype=np.float64))
    for cls in ("ppermute", "allreduce", "allgather"):
        off, on = [getattr(audits[g], cls) for g in (False, True)]
        assert (off.count, off.bytes) == (on.count, on.bytes), cls


def test_fault_plan_adds_no_collectives(problem):
    """Injection is data-only ``where`` selection: the faulted program
    moves the same collective traffic as the plain one."""
    from acg_tpu.obs.hlo import audit_compiled
    from acg_tpu.solvers.cg_dist import compile_step

    A, b = problem
    opts = dataclasses.replace(OPTS, maxits=5, guard_nonfinite=True)
    plain = audit_compiled(compile_step(A, b, options=opts, nparts=4,
                                        dtype=np.float64))
    # the faulted program: route through the executed path (lowered via
    # the solver cache) by auditing a lowered step with a fault plan
    from acg_tpu.solvers.cg_dist import _shard_solver, build_sharded
    ss = build_sharded(A, nparts=4, dtype=np.float64)
    fn = _shard_solver(ss, "cg", 5, False, guard=True, has_fault=True)
    import jax.numpy as jnp
    fplan = FaultSpec.parse("spmv@2").device_plan(np.float64)
    lowered = fn.lower(
        ss.local_op_arrays(), ss.iface_op_arrays(), ss.send_idx,
        ss.recv_idx, ss.partner, ss.pack_idx, ss.ghost_src_part,
        ss.ghost_src_pos, ss.zeros_sharded(), ss.zeros_sharded(),
        (jnp.asarray(0.0), jnp.asarray(1e-20)), jnp.asarray(0.0),
        fplan)
    faulted = audit_compiled(lowered.compile())
    for cls in ("ppermute", "allreduce", "allgather"):
        a, c = getattr(plain, cls), getattr(faulted, cls)
        assert (a.count, a.bytes) == (c.count, c.bytes), cls


# ---------------------------------------------------------------------------
# CLI round-trips (satellites: failed solves export stats; --resilient
# wiring; --inject-fault wiring)


@pytest.fixture
def matrix_file(tmp_path):
    from acg_tpu.io import write_mtx
    from acg_tpu.io.mtxfile import MtxFile

    A = poisson2d_5pt(8)
    r, c, v = A.to_coo()
    keep = r >= c
    m = MtxFile(symmetry="symmetric", nrows=A.nrows, ncols=A.ncols,
                nnz=int(keep.sum()), rowidx=r[keep], colidx=c[keep],
                vals=v[keep])
    p = tmp_path / "A.mtx"
    write_mtx(p, m)
    return str(p)


def test_cli_fault_detection_exports_stats(matrix_file, tmp_path, capsys):
    from acg_tpu.cli import main as cli_main

    sj = tmp_path / "stats.json"
    rc = cli_main([matrix_file, "--max-iterations", "500",
                   "--residual-rtol", "1e-10",
                   "--inject-fault", "spmv-nan@5",
                   "--output-stats-json", str(sj), "-q"])
    assert rc == 1
    doc = json.load(open(sj))
    assert doc["result"]["status"] == "ERR_FAULT_DETECTED"
    assert doc["result"]["converged"] is False
    assert doc["resilience"] is None
    assert "on-device guard" in capsys.readouterr().err


def test_cli_resilient_recovers(matrix_file, tmp_path, capsys):
    from acg_tpu.cli import main as cli_main
    from acg_tpu.obs.export import load_stats_document

    sj = tmp_path / "stats.json"
    rc = cli_main([matrix_file, "--max-iterations", "500",
                   "--residual-rtol", "1e-10", "--resilient",
                   "--inject-fault", "reduction-nan@4",
                   "--output-stats-json", str(sj), "-q"])
    assert rc == 0
    doc = load_stats_document(str(sj))     # validates on read
    assert doc["result"]["status"] == "SUCCESS"
    resil = doc["resilience"]
    assert resil["fixed_by"] == "restart"
    assert resil["restarts"] == 1
    assert resil["faults"] == ["reduction@4"]


def test_cli_resilient_host_faults(matrix_file, tmp_path, capsys):
    from acg_tpu.cli import main as cli_main

    sj = tmp_path / "stats.json"
    ck = tmp_path / "c.npz"
    rc = cli_main([matrix_file, "--max-iterations", "500",
                   "--residual-rtol", "1e-10", "--resilient",
                   "--checkpoint-every", "6",
                   "--write-checkpoint", str(ck),
                   "--inject-fault", "segment-kill@1",
                   "--output-stats-json", str(sj), "-q"])
    assert rc == 0
    doc = json.load(open(sj))
    assert "segment-kill" in [s["action"]
                              for s in doc["resilience"]["steps"]]


def test_cli_host_fault_requires_resilient(matrix_file, capsys):
    from acg_tpu.cli import main as cli_main

    rc = cli_main([matrix_file, "--inject-fault", "segment-kill@1", "-q"])
    assert rc == 1
    assert "--resilient" in capsys.readouterr().err


def test_cli_breakdown_exports_stats(tmp_path, capsys):
    """Satellite: a breakdown (host solver, indefinite matrix) still
    exports the stats document and the partial result, exit nonzero."""
    from acg_tpu.cli import main as cli_main
    from acg_tpu.io import write_mtx
    from acg_tpu.io.mtxfile import MtxFile

    n = 16
    d = np.ones(n)
    d[5] = -1.0
    m = MtxFile(symmetry="general", nrows=n, ncols=n, nnz=n,
                rowidx=np.arange(n), colidx=np.arange(n), vals=d)
    mf = tmp_path / "ind.mtx"
    write_mtx(mf, m)
    sj = tmp_path / "stats.json"
    rc = cli_main([str(mf), "--solver", "host", "--max-iterations", "50",
                   "--residual-rtol", "1e-10",
                   "--output-stats-json", str(sj), "-q"])
    assert rc == 1
    doc = json.load(open(sj))
    assert doc["result"]["status"] == \
        "ERR_NOT_CONVERGED_INDEFINITE_MATRIX"
    assert "not positive definite" in capsys.readouterr().err


def test_cli_resume_validates_checkpoint(matrix_file, tmp_path, capsys):
    from acg_tpu.cli import main as cli_main

    ck = tmp_path / "c.npz"
    save_checkpoint(str(ck), np.ones(7))   # wrong length for n=64
    rc = cli_main([matrix_file, "--resume", str(ck), "-q"])
    assert rc == 1
    assert "wrong matrix" in capsys.readouterr().err
