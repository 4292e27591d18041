"""Perf-regression gate + artifact lint (scripts/check_perf_regression.py,
scripts/lint_artifacts.py) and the acg-tpu-stats/3 schema extension."""

import json
import os

import pytest

from scripts.check_perf_regression import (find_regressions,
                                           load_trajectory)
from scripts.check_perf_regression import main as gate_main
from scripts.lint_artifacts import main as lint_main

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _wrapper(tmp_path, n, value, metric="cg_iters_per_sec_x",
             unit="iterations/sec", rc=0):
    doc = {"n": n, "cmd": "python bench.py", "rc": rc, "tail": "",
           "parsed": None if value is None else
           {"metric": metric, "value": value, "unit": unit}}
    p = tmp_path / f"BENCH_r{n:02d}.json"
    p.write_text(json.dumps(doc))
    return str(p)


# ---------------------------------------------------------------------------
# gate core


def test_gate_fails_on_synthetic_regression(tmp_path):
    _wrapper(tmp_path, 1, 1000.0)
    _wrapper(tmp_path, 2, 800.0)      # 20% drop > 10% tolerance
    assert gate_main(["--dir", str(tmp_path)]) == 1


def test_gate_passes_within_tolerance(tmp_path):
    _wrapper(tmp_path, 1, 1000.0)
    _wrapper(tmp_path, 2, 950.0)      # 5% < 10% tolerance
    assert gate_main(["--dir", str(tmp_path)]) == 0


def test_gate_passes_on_improvement(tmp_path):
    _wrapper(tmp_path, 1, 1000.0)
    _wrapper(tmp_path, 2, 1500.0)
    assert gate_main(["--dir", str(tmp_path)]) == 0


def test_gate_compares_against_best_prior_not_last(tmp_path):
    # best prior is round 1 (1000); the newest must be priced against it
    # even though round 2 was already slow
    _wrapper(tmp_path, 1, 1000.0)
    _wrapper(tmp_path, 2, 500.0)
    _wrapper(tmp_path, 3, 850.0)      # +70% vs round 2, -15% vs best
    assert gate_main(["--dir", str(tmp_path)]) == 1


def test_gate_dry_run_never_fails_on_regressions(tmp_path):
    _wrapper(tmp_path, 1, 1000.0)
    _wrapper(tmp_path, 2, 100.0)
    assert gate_main(["--dry-run", "--dir", str(tmp_path)]) == 0


def test_gate_skips_failed_rounds(tmp_path):
    _wrapper(tmp_path, 1, 1000.0)
    _wrapper(tmp_path, 2, None, rc=3)   # no chip reached: parsed null
    _wrapper(tmp_path, 3, 990.0)
    assert gate_main(["--dir", str(tmp_path)]) == 0


def test_gate_single_record_passes_vacuously(tmp_path):
    _wrapper(tmp_path, 1, 1000.0)
    assert gate_main(["--dir", str(tmp_path)]) == 0


def test_gate_malformed_artifact_exits_2_even_dry(tmp_path):
    _wrapper(tmp_path, 1, 1000.0)
    (tmp_path / "BENCH_r02.json").write_text("{not json")
    assert gate_main(["--dir", str(tmp_path)]) == 2
    assert gate_main(["--dry-run", "--dir", str(tmp_path)]) == 2


def test_gate_max_slowdown_configurable(tmp_path):
    _wrapper(tmp_path, 1, 1000.0)
    _wrapper(tmp_path, 2, 800.0)
    assert gate_main(["--dir", str(tmp_path),
                      "--max-slowdown", "0.25"]) == 0


def test_gate_lower_is_better_units(tmp_path):
    _wrapper(tmp_path, 1, 10.0, metric="solve_latency", unit="s")
    _wrapper(tmp_path, 2, 20.0, metric="solve_latency", unit="s")
    assert gate_main(["--dir", str(tmp_path)]) == 1


def test_gate_on_real_trajectory():
    """Acceptance: the repo's actual BENCH_*.json trajectory passes the
    gate (one parsed record per metric so far — vacuous or improving)."""
    assert gate_main(["--dir", REPO]) == 0


def test_load_trajectory_orders_by_round(tmp_path):
    _wrapper(tmp_path, 2, 900.0)
    _wrapper(tmp_path, 1, 1000.0)
    recs, problems = load_trajectory(
        sorted(str(p) for p in tmp_path.glob("BENCH_*.json")))
    assert not problems
    assert [r["n"] for r in recs] == [1, 2]
    cmp = find_regressions(recs, 0.05)
    assert len(cmp) == 1 and cmp[0]["regressed"]


# ---------------------------------------------------------------------------
# lint_artifacts: one command for schema lint + dry gate


def test_lint_artifacts_on_real_repo():
    assert lint_main(["--dir", REPO, "-q"]) == 0


def test_lint_artifacts_fails_on_bad_artifact(tmp_path):
    _wrapper(tmp_path, 1, 1000.0)
    bad = tmp_path / "BENCH_r99.json"
    bad.write_text(json.dumps({"n": 99, "cmd": "x", "rc": 0,
                               "tail": "", "parsed": None}))
    # rc==0 with parsed null violates the wrapper schema
    assert lint_main(["--dir", str(tmp_path), "-q"]) == 1


def test_lint_artifacts_validates_extra_stats_documents(tmp_path):
    bad = tmp_path / "stats.json"
    bad.write_text(json.dumps({"schema": "acg-tpu-stats/3"}))
    assert lint_main(["--dir", str(tmp_path), "-q", str(bad)]) == 1


# ---------------------------------------------------------------------------
# acg-tpu-stats/3: introspection block validation


def _doc_v3(introspection):
    from acg_tpu.config import SolverOptions
    from acg_tpu.obs.export import build_stats_document
    from acg_tpu.solvers.base import SolveResult, SolveStats

    res = SolveResult(x=None, converged=True, niterations=2, bnrm2=1.0,
                      r0nrm2=1.0, rnrm2=0.1,
                      residual_history=[1.0, 0.5, 0.01])
    return build_stats_document(solver="acg", options=SolverOptions(),
                                res=res, stats=SolveStats(),
                                nunknowns=4, capabilities={},
                                introspection=introspection)


def test_stats_v3_null_introspection_validates():
    from acg_tpu.obs.export import SCHEMA, validate_stats_document

    doc = _doc_v3(None)
    assert doc["schema"] == SCHEMA == "acg-tpu-stats/13"
    assert doc["introspection"] == {"comm_audit": None, "roofline": None,
                                    "halo_wire": None}
    assert validate_stats_document(doc) == []


def test_stats_v3_full_introspection_validates():
    from acg_tpu.obs.export import validate_stats_document
    from acg_tpu.obs.hlo import audit_hlo_text
    from acg_tpu.obs.roofline import RooflineModel

    audit = audit_hlo_text("")
    model = RooflineModel(operator_format="dia", solver="cg", nrhs=1,
                          nrows=64, nparts=1, operator_bytes=640,
                          vector_bytes=6656, hbm_gbps=819.0)
    roof = dict(model.as_dict(), measured_iters_per_sec=100.0,
                roofline_frac=0.5)
    doc = _doc_v3({"comm_audit": audit.as_dict(), "roofline": roof})
    assert validate_stats_document(doc) == []


def test_stats_v3_missing_introspection_fails():
    from acg_tpu.obs.export import validate_stats_document

    doc = _doc_v3(None)
    del doc["introspection"]
    assert any("introspection" in p for p in
               validate_stats_document(doc))


def test_stats_v3_mangled_roofline_fails():
    from acg_tpu.obs.export import validate_stats_document

    doc = _doc_v3({"comm_audit": None,
                   "roofline": {"bytes_per_iter": "lots"}})
    assert any("roofline" in p for p in validate_stats_document(doc))


def test_stats_v2_documents_still_validate():
    """Back-compat: a /2 document without introspection keeps linting."""
    from acg_tpu.obs.export import SCHEMA_V2, validate_stats_document

    doc = _doc_v3(None)
    doc["schema"] = SCHEMA_V2
    del doc["introspection"]
    assert validate_stats_document(doc) == []


# ---------------------------------------------------------------------------
# suite wiring smoke (same tier as bench_batched --dry-run)


def test_check_perf_regression_dry_run_smoke(capsys):
    """The wiring bench_suite.py invokes after every sweep."""
    assert gate_main(["--dry-run", "--dir", REPO]) == 0
    out = capsys.readouterr().out
    assert "perf gate" in out


# ── preprocessing benchmark (scripts/bench_partition.py) wiring ────────


def test_bench_partition_dry_run_smoke(tmp_path):
    """Tier-1 wiring smoke (same tier as bench_batched --dry-run): the
    dry pass runs end-to-end, its artifact validates through the shared
    schema linter, and the perf gate consumes it."""
    from scripts.bench_partition import main as bench_main
    from scripts.check_stats_schema import validate_file

    out = tmp_path / "PARTBENCH_r00.json"
    assert bench_main(["--dry-run", "--out", str(out), "--round", "0"]) == 0
    assert validate_file(str(out)) == []
    import json

    doc = json.loads(out.read_text())
    assert doc["schema"] == "acg-tpu-partbench/1"
    metrics = {r["metric"] for r in doc["records"]}
    assert any(m.startswith("partition-24") for m in metrics)
    assert any(m.startswith("halo-") for m in metrics)
    assert any(m.startswith("shard-") for m in metrics)
    assert all(r["dry_run"] for r in doc["records"])
    # the gate consumes the wrapper (single round: vacuous pass)
    assert gate_main(["--dry-run", "--dir", str(tmp_path),
                      "--glob", "PARTBENCH_*.json"]) == 0


def test_partbench_trajectory_gates_regressions(tmp_path):
    """A partition-wall regression in the newest PARTBENCH round fails
    the gate like any solver metric (latency direction: 's' and 'edges'
    regress UPWARD, 'ratio' too)."""
    import json

    def wrap(n, t_part, cut):
        return {"schema": "acg-tpu-partbench/1", "n": n, "cmd": "x",
                "config": {}, "records": [
                    {"metric": "partition-96-p8", "value": t_part,
                     "unit": "s"},
                    {"metric": "partition-cut-96-p8", "value": cut,
                     "unit": "edges"}]}

    (tmp_path / "PARTBENCH_r01.json").write_text(
        json.dumps(wrap(1, 100.0, 50000)))
    (tmp_path / "PARTBENCH_r02.json").write_text(
        json.dumps(wrap(2, 55.0, 50100)))
    assert gate_main(["--dir", str(tmp_path),
                      "--glob", "PARTBENCH_*.json"]) == 0
    # newest round 3 regresses the wall 3x beyond the best prior
    (tmp_path / "PARTBENCH_r03.json").write_text(
        json.dumps(wrap(3, 170.0, 50050)))
    assert gate_main(["--dir", str(tmp_path),
                      "--glob", "PARTBENCH_*.json"]) == 1
    # dry mode reports but passes
    assert gate_main(["--dry-run", "--dir", str(tmp_path),
                      "--glob", "PARTBENCH_*.json"]) == 0


def test_partbench_schema_rejects_malformed(tmp_path):
    import json

    from scripts.check_stats_schema import validate_file

    bad = {"schema": "acg-tpu-partbench/1", "n": "six",
           "records": [{"metric": 7, "unit": "s"}]}
    p = tmp_path / "PARTBENCH_bad.json"
    p.write_text(json.dumps(bad))
    problems = validate_file(str(p))
    assert problems and any("n missing" in m for m in problems)
