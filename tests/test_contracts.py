"""Solver contracts (acg_tpu/analysis/): static verification of every
compiled program against its declared per-iteration model.

Three layers under test: the contract checker itself (seeded HLO
mutations must fire exactly their rule), the registry matrix (every
shipped configuration's compiled program verifies green), and the
surfacing (schema /7 ``contract`` field, the ``acg-tpu-contracts/1``
report, the ``declared_contract`` solver hooks)."""

import dataclasses
import json

import numpy as np
import pytest

from acg_tpu.analysis.contracts import (RULES, SolverContract, Violation,
                                        contract_block, format_verdict,
                                        verify_hlo_text,
                                        verify_nrhs_scaling)
from acg_tpu.analysis.registry import (SSTEP, contract_for,
                                       registry_cases, run_registry,
                                       solver_options)
from acg_tpu.config import SolverOptions
from acg_tpu.obs.export import (validate_contracts_document,
                                validate_stats_document)
from acg_tpu.obs.hlo import while_body_profile
from acg_tpu.sparse import poisson2d_5pt

OPTS = SolverOptions(maxits=5, residual_rtol=1e-9)


# ---------------------------------------------------------------------------
# seeded violations on synthetic HLO (the checker fires the right rule)

# one while loop whose body holds 1 ppermute + 1 psum — the shape of a
# pipelined distributed iteration
_BASE = """\
HloModule synth

%body.1 (p: (f32[8], f32[8])) -> (f32[8], f32[8]) {
  %p = (f32[8]{0}, f32[8]{0}) parameter(0)
  %x = f32[8]{0} get-tuple-element((f32[8]{0}, f32[8]{0}) %p), index=0
  %cp = f32[8]{0} collective-permute(f32[8]{0} %x), source_target_pairs={{0,1},{1,0}}
  %ar = f32[8]{0} all-reduce(f32[8]{0} %cp), to_apply=%add.2
  ROOT %t = (f32[8]{0}, f32[8]{0}) tuple(f32[8]{0} %cp, f32[8]{0} %ar)
}

%cond.3 (q: (f32[8], f32[8])) -> pred[] {
  %q = (f32[8]{0}, f32[8]{0}) parameter(0)
  ROOT %lt = pred[] constant(false)
}

ENTRY %main.9 (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %init = (f32[8]{0}, f32[8]{0}) tuple(f32[8]{0} %a, f32[8]{0} %a)
  %w = (f32[8]{0}, f32[8]{0}) while((f32[8]{0}, f32[8]{0}) %init), condition=%cond.3, body=%body.1
  ROOT %out = f32[8]{0} get-tuple-element((f32[8]{0}, f32[8]{0}) %w), index=0
}
"""

_CONTRACT = SolverContract(name="synth", solver="cg-pipelined", nparts=2,
                           dtype="float32", psums=1, ppermutes=1,
                           psum_bytes=32, allow_hot_gather=False)


def _inject(line: str) -> str:
    """Insert an instruction line into the while body."""
    return _BASE.replace(
        "  ROOT %t =",
        f"  {line}\n  ROOT %t =")


def _rules(violations) -> set:
    return {v.rule for v in violations}


def test_base_module_satisfies_its_contract():
    assert verify_hlo_text(_BASE, _CONTRACT) == []


def test_extra_psum_fires_C1():
    txt = _inject("%ar2 = f32[8]{0} all-reduce(f32[8]{0} %x), "
                  "to_apply=%add.2")
    assert _rules(verify_hlo_text(txt, _CONTRACT)) == {"C1"}


def test_extra_ppermute_fires_C2():
    txt = _inject("%cp2 = f32[8]{0} collective-permute(f32[8]{0} %x), "
                  "source_target_pairs={{0,1}}")
    assert _rules(verify_hlo_text(txt, _CONTRACT)) == {"C2"}


def test_unexpected_allgather_fires_C3():
    txt = _inject("%ag = f32[16]{0} all-gather(f32[8]{0} %x), "
                  "dimensions={0}")
    assert _rules(verify_hlo_text(txt, _CONTRACT)) == {"C3"}


def test_injected_while_body_gather_fires_C4():
    txt = _inject("%g = f32[8]{0} gather(f32[8]{0} %x, s32[8,1]{1,0} %x), "
                  "offset_dims={}")
    v = verify_hlo_text(txt, _CONTRACT)
    assert _rules(v) == {"C4"}
    assert "gather" in str(v[0])
    # the same program under a tier that declares its gathers passes
    ok = dataclasses.replace(_CONTRACT, allow_hot_gather=True)
    assert verify_hlo_text(txt, ok) == []


def test_injected_scatter_fires_C5():
    txt = _inject("%sc = f32[8]{0} scatter(f32[8]{0} %x, s32[1]{0} %x, "
                  "f32[1]{0} %x), to_apply=%add.2")
    assert _rules(verify_hlo_text(txt, _CONTRACT)) == {"C5"}


def test_host_callback_fires_C6_and_monitor_allowance_passes():
    txt = _inject('%cb = () custom-call(f32[8]{0} %x), '
                  'custom_call_target="xla_python_cpu_callback"')
    assert _rules(verify_hlo_text(txt, _CONTRACT)) == {"C6"}
    monitored = dataclasses.replace(_CONTRACT, allow_host_transfer=True)
    assert verify_hlo_text(txt, monitored) == []


def test_outfeed_fires_C6():
    txt = _inject("%of = token[] outfeed(f32[8]{0} %x, token[] %x)")
    assert _rules(verify_hlo_text(txt, _CONTRACT)) == {"C6"}


def test_device_custom_call_is_not_a_host_transfer():
    # LAPACK/Pallas kernels are custom-calls too — only callback targets
    # (and infeed/outfeed/send/recv) witness a host round-trip
    txt = _inject('%eig = f32[8]{0} custom-call(f32[8]{0} %x), '
                  'custom_call_target="lapack_ssyevd_ffi"')
    assert verify_hlo_text(txt, _CONTRACT) == []


def test_forged_f64_op_fires_C7():
    txt = _inject("%d = f64[8]{0} convert(f32[8]{0} %x)")
    v = verify_hlo_text(txt, _CONTRACT)
    assert _rules(v) == {"C7"}
    f64_ok = dataclasses.replace(_CONTRACT, forbid_f64=False)
    assert verify_hlo_text(txt, f64_ok) == []


def test_psum_payload_mismatch_fires_C10():
    tight = dataclasses.replace(_CONTRACT, psum_bytes=8)
    assert _rules(verify_hlo_text(_BASE, tight)) == {"C10"}


def test_single_chip_collective_fires_C12():
    single = SolverContract(name="s", solver="cg", nparts=1,
                            dtype="float32", no_collectives_anywhere=True,
                            allow_hot_gather=True)
    v = verify_hlo_text(_BASE, single)
    assert "C12" in _rules(v)


def test_nrhs_scaling_laws_C8_C9():
    # same counts, bytes x4: the law holds
    quad = _BASE.replace("f32[8]", "f32[4,8]").replace("f32[16]",
                                                       "f32[4,16]")
    assert verify_nrhs_scaling(_BASE, quad, 4) == []
    # count changed (an extra psum in the B=4 program only) -> C8
    extra = quad.replace(
        "  ROOT %t =",
        "  %ar2 = f32[4,8]{1,0} all-reduce(f32[4,8]{1,0} %x), "
        "to_apply=%add.2\n  ROOT %t =")
    assert _rules(verify_nrhs_scaling(_BASE, extra, 4)) == {"C8"}
    # count equal, bytes NOT xB -> C9
    assert _rules(verify_nrhs_scaling(_BASE, _BASE, 4)) == {"C9"}


def test_branch_hidden_callback_detected():
    """A host callback behind a conditional inside the while body (the
    monitor lowering) is still found — branch_computations are followed
    for host-transfer detection."""
    txt = _BASE.replace(
        "  ROOT %t =",
        "  %c = () conditional(s32[] %x, () %x, () %x), "
        "branch_computations={%br.7, %br.8}\n  ROOT %t =") + """
%br.7 () -> () {
  %cb = () custom-call(), custom_call_target="xla_ffi_python_cpu_callback"
  ROOT %r = () tuple()
}

%br.8 () -> () {
  ROOT %r = () tuple()
}
"""
    prof = while_body_profile(txt)
    assert any("callback" in h for h in prof.host_transfers)
    assert _rules(verify_hlo_text(txt, _CONTRACT)) == {"C6"}


def test_violation_formatting_names_the_rule():
    v = Violation("C1", "expected 1, got 2")
    assert "C1" in str(v) and RULES["C1"] in str(v)
    assert v.as_dict() == {"rule": "C1", "detail": "expected 1, got 2"}


def test_format_verdict_pass_and_fail():
    assert format_verdict(_CONTRACT, []).endswith("PASS")
    s = format_verdict(_CONTRACT, [Violation("C4", "x"),
                                   Violation("C7", "y")])
    assert "FAIL" in s and "C4" in s and "+1 more" in s


def test_contract_block_shapes():
    assert contract_block(None, None) is None
    blk = contract_block(_CONTRACT, [])
    assert blk["verdict"] == "PASS" and blk["violations"] == []
    assert blk["declared"]["psums_per_iter"] == "1"
    blk = contract_block(_CONTRACT, [Violation("C1", "d")])
    assert blk["verdict"] == "FAIL"
    assert blk["violations"] == [{"rule": "C1", "detail": "d"}]


# ---------------------------------------------------------------------------
# real compiled programs vs. declared contracts


def test_real_dist_program_fires_on_sabotaged_contract():
    """Wiring proof on a REAL compiled step: a contract that understates
    the psum count must fail the classic distributed program."""
    from acg_tpu.solvers.cg_dist import build_sharded, compile_step

    A = poisson2d_5pt(12)
    ss = build_sharded(A, nparts=4)
    txt = compile_step(ss, np.ones(A.nrows), options=OPTS).as_text()
    good = contract_for("cg", OPTS, ss=ss, nrhs=1)
    assert verify_hlo_text(txt, good) == []
    bad = dataclasses.replace(good, psums=1, psum_bytes=None)
    assert _rules(verify_hlo_text(txt, bad)) == {"C1"}


def test_declared_contract_hooks():
    """The solver-side hooks next to lowered_step: what compile_step
    lowers verifies against what declared_contract declares."""
    from acg_tpu.solvers.cg import compile_step, declared_contract
    from acg_tpu.solvers.cg_dist import \
        compile_step as dist_compile_step
    from acg_tpu.solvers.cg_dist import \
        declared_contract as dist_declared

    A = poisson2d_5pt(12)
    b = np.ones(A.nrows)
    c1 = declared_contract(A, b, options=OPTS)
    assert c1.nparts == 1 and c1.no_collectives_anywhere
    assert verify_hlo_text(compile_step(A, b, options=OPTS).as_text(),
                           c1) == []
    cd = dist_declared(A, b, options=OPTS, pipelined=True, nparts=4)
    assert cd.psums == 1 and cd.ppermutes > 0
    assert str(cd.psums_per_iter()) == "1"
    txt = dist_compile_step(A, b, options=OPTS, pipelined=True,
                            nparts=4).as_text()
    assert verify_hlo_text(txt, cd) == []


def test_sstep_contract_carries_the_rational_counts():
    opts = solver_options("cg-sstep")
    from acg_tpu.solvers.cg_dist import build_sharded

    A = poisson2d_5pt(12)
    ss = build_sharded(A, nparts=4)
    c = contract_for("cg-sstep", opts, ss=ss, nrhs=1)
    assert c.iters_per_body == SSTEP and c.psums == 1
    assert str(c.psums_per_iter()) == f"1/{SSTEP}"
    m = 2 * SSTEP + 1
    assert c.psum_bytes == m * m * 8      # f64 Gram


# ---------------------------------------------------------------------------
# the registry sweep


def test_registry_fast_matrix_green():
    """The tier-1 face: every single-chip configuration's compiled
    program satisfies its declared contract; unsupported configurations
    SKIP with a reason instead of failing the sweep."""
    rep = run_registry(fast=True, check_recompile=False)
    fails = [c for c in rep["cases"] if c["verdict"] == "FAIL"]
    assert fails == [], fails
    assert rep["ok"] and rep["failed"] == 0
    for c in rep["cases"]:
        if c["verdict"] == "SKIP":
            assert c["skip_reason"]
    # the pairs checked the B-scaling law for every compiled pair
    assert all(p["verdict"] == "PASS" for p in rep["pairs"])
    assert validate_contracts_document(rep) == []


def test_registry_dist_spot_checks():
    """Representative 4-part cases inside tier-1 (the FULL matrix sweep
    is the slow test below + scripts/check_contracts.py): classic,
    pipelined and s-step distributed programs verify green, and the
    B-scaling law holds for the classic pair."""
    from acg_tpu.analysis.registry import _compile_case, ContractCase

    A = poisson2d_5pt(12)
    cache: dict = {}
    texts = {}
    for case in (ContractCase("cg", 4, "float32", 1),
                 ContractCase("cg", 4, "float32", 4),
                 ContractCase("cg-pipelined", 4, "float32", 1),
                 ContractCase("cg-sstep", 4, "float32", 1)):
        txt, contract = _compile_case(case, A, cache)
        assert verify_hlo_text(txt, contract) == [], case.name
        texts[case.name] = txt
    assert verify_nrhs_scaling(texts["cg-p4-float32-b1"],
                               texts["cg-p4-float32-b4"], 4) == []


@pytest.mark.slow
def test_registry_full_matrix_green():
    rep = run_registry(fast=False)
    fails = ([c for c in rep["cases"] if c["verdict"] == "FAIL"]
             + [p for p in rep["pairs"] if p["verdict"] == "FAIL"])
    assert fails == [], fails
    assert validate_contracts_document(rep) == []


def test_no_recompile_check_single_chip():
    from acg_tpu.analysis.registry import check_no_recompile

    assert check_no_recompile(poisson2d_5pt(12), nparts=1) == []


def test_registry_matrix_covers_the_acceptance_axes():
    cases = registry_cases(fast=False)
    assert {c.solver for c in cases} == {"cg", "cg-pipelined",
                                         "cg-sstep",
                                         "cg-pipelined-deep",
                                         "cg-recycled"}
    assert {c.nparts for c in cases} == {1, 4}
    assert {c.dtype for c in cases} == {"float32", "bfloat16"}
    assert {c.nrhs for c in cases} == {1, 4}
    # 32 stored-tier cases + the 8-case compressed-wire sub-matrix
    # ({cg-pipelined, cg-pipelined-deep} x {bf16, int16-delta} x
    # {B=1, 4} at 4 parts — ISSUE 17) + the 8-case deflated-recycling
    # sub-matrix (cg-recycled x {1, 4} x {f32, bf16} x {B=1, 4} —
    # ISSUE 20) + the 16-case matrix-free stencil sub-matrix
    # ({cg, cg-pipelined} x {1, 4} x {f32, bf16} x {B=1, 4} —
    # ISSUE 12)
    # and, apart from those, the multigrid-preconditioned classic loop
    # on the stored and matrix-free tiers (single chip, f32, B=1)
    plain = [c for c in cases if c.precond == "none"]
    assert len([c for c in plain if c.fmt != "stencil"]) == 48
    rec = [c for c in cases if c.solver == "cg-recycled"]
    assert len(rec) == 8
    assert {c.nparts for c in rec} == {1, 4}
    assert {c.fmt for c in rec} == {"dia"}
    wire = [c for c in cases if c.wire not in (None, "f32")]
    assert len(wire) == 8
    assert {c.solver for c in wire} == {"cg-pipelined",
                                        "cg-pipelined-deep"}
    assert {c.wire for c in wire} == {"bf16", "int16-delta"}
    assert {c.nparts for c in wire} == {4}
    mg = [c for c in cases if c.precond == "mg"]
    assert [(c.solver, c.nparts, c.dtype, c.nrhs, c.fmt) for c in mg] == [
        ("cg", 1, "float32", 1, "dia"), ("cg", 1, "float32", 1, "stencil")]
    st = [c for c in plain if c.fmt == "stencil"]
    assert len(st) == 16
    assert {c.solver for c in st} == {"cg", "cg-pipelined"}
    assert {c.nparts for c in st} == {1, 4}
    fast = registry_cases(fast=True)
    assert {c.nparts for c in fast} == {1} and len(fast) == 23
    assert [c for c in fast if c.precond == "mg"] == mg
    assert len([c for c in fast if c.fmt == "stencil"
                and c.precond == "none"]) == 1


# ---------------------------------------------------------------------------
# schemas: the contracts report and the stats /7 contract field


def test_contracts_report_validator_rejects_malformed():
    rep = run_registry(fast=True, check_recompile=False)
    assert validate_contracts_document(rep) == []
    bad = json.loads(json.dumps(rep))
    bad["failed"] = 99
    assert any("failed" in m for m in validate_contracts_document(bad))
    bad = json.loads(json.dumps(rep))
    bad["cases"][0]["verdict"] = "MAYBE"
    assert validate_contracts_document(bad)
    bad = json.loads(json.dumps(rep))
    bad["cases"][0]["verdict"] = "FAIL"   # FAIL without violations
    bad["failed"] += 1
    bad["ok"] = False
    assert any("no violations" in m
               for m in validate_contracts_document(bad))
    assert validate_contracts_document({"schema": "nope"})


def test_check_contracts_script_exit_codes(tmp_path):
    """The script face: --fast runs green and writes a conforming
    report; a seeded registry failure exits nonzero."""
    from scripts.check_contracts import main as contracts_main

    out = tmp_path / "CONTRACTS_t.json"
    rc = contracts_main(["--fast", "--no-recompile-check", "-q",
                         "--output", str(out)])
    assert rc == 0
    from scripts.check_stats_schema import validate_file

    assert validate_file(str(out)) == []

    # seeded violation -> exit 1: patch the registry sweep to report one
    # FAILed case (main() imports run_registry at call time, so the
    # module attribute is the seam)
    from acg_tpu.analysis import registry as reg

    real = reg.run_registry

    def sabotaged(**kw):
        rep = real(fast=True, check_recompile=False)
        rep["cases"][0]["verdict"] = "FAIL"
        rep["cases"][0]["violations"] = [
            {"rule": "C1", "detail": "seeded"}]
        rep["failed"] += 1
        rep["ok"] = False
        return rep

    reg.run_registry = sabotaged
    try:
        rc = contracts_main(["--fast", "-q"])
    finally:
        reg.run_registry = real
    assert rc == 1


def test_stats_schema_v7_contract_field():
    """/7 requires the nullable contract key; /6 documents without it
    still validate (back-compat), and a FAIL block must carry its
    violations."""
    from acg_tpu.obs.export import SCHEMA, SCHEMA_V6, build_stats_document
    from acg_tpu.solvers.base import SolveResult, SolveStats

    res = SolveResult(x=np.zeros(4), converged=True, niterations=0,
                      bnrm2=1.0, r0nrm2=1.0, rnrm2=0.0)
    doc = build_stats_document(solver="acg", options=OPTS, res=res,
                               stats=SolveStats(), nunknowns=4,
                               contract=contract_block(_CONTRACT, []))
    assert doc["schema"] == SCHEMA
    assert validate_stats_document(doc) == []
    # null contract (no --explain) validates
    doc2 = build_stats_document(solver="acg", options=OPTS, res=res,
                                stats=SolveStats(), nunknowns=4)
    assert doc2["contract"] is None
    assert validate_stats_document(doc2) == []
    # /6 document without the key keeps validating
    doc6 = json.loads(json.dumps(doc2))
    doc6["schema"] = SCHEMA_V6
    del doc6["contract"]
    assert validate_stats_document(doc6) == []
    # /7 without the key is rejected
    doc7 = json.loads(json.dumps(doc2))
    del doc7["contract"]
    assert any("contract" in m for m in validate_stats_document(doc7))
    # FAIL with empty violations is rejected
    doc8 = json.loads(json.dumps(doc))
    doc8["contract"]["verdict"] = "FAIL"
    doc8["contract"]["violations"] = []
    assert any("FAIL" in m for m in validate_stats_document(doc8))
