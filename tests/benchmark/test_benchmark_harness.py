"""The harness: the spec's shape, files found by name, the loops' timing,
the metric arithmetic, and whole runs of every cell at a small size on
the CPU (the harness's look for a chip is the only step left out)."""

import json
import math
import os
import re
import shutil
import subprocess
import sys
import time

import jax
import pytest

from benchmark import counts, harness, peaks, traffic
from benchmark.trace_reduce import TraceSummary

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_spec_has_the_contract_shape(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= spec["run_seconds"] <= 51
    cells = {w["name"]: w for w in spec["workloads"]}
    configs = {c["name"]: c for c in spec["configs"]}
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    assert len(cells) == len(spec["workloads"])
    assert {c["config"] for c in cells.values()} == set(configs)
    assert sum(w["chips"] == 4 for w in cells.values()) <= max(
        1, len(cells) // 2)
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert any(c["file"].startswith(p + "/") for p in spec["paths"])
        assert os.path.exists(os.path.join(REPO, c["file"]))
    for w in cells.values():
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert NAME.match(w["traffic"])
    metrics = spec["end_to_end"] + spec["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m["workloads"] if "workloads" in m else []) <= set(cells)
        assert os.path.exists(os.path.join(REPO, "benchmark", "metrics",
                                           m["name"] + ".py"))
    for m in spec["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert e2e["setup_s"]["bound"] <= 0.25 and "workloads" not in e2e[
        "setup_s"]
    for m in spec["per_layer"]:
        assert m["moves"] in e2e and "bound" not in m
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for name in cells:
        moved = [m for m in spec["end_to_end"]
                 if name in m.get("workloads", [name])]
        assert len(moved) >= 2
        assert any(name in m.get("workloads", [name])
                   for m in spec["per_layer"])


@pytest.mark.parametrize("workload", ["p7-256-cg", "p7-128-cg-4chip",
                                      "p7-128-serve", "p7-256-pipelined"])
def test_everything_a_cell_needs_is_found_by_name(tiny_root, workload):
    cell = harness.find_cell(workload, root=tiny_root)
    assert cell.config["grid"] and cell.traffic["loop"] in ("closed", "open")
    assert callable(cell.operator.reference_apply)
    for kind in ("end_to_end", "per_layer"):
        for m in cell.metrics(kind):
            assert callable(cell.reader(m["name"]).read)


def test_unknown_workload_is_a_setup_error():
    with pytest.raises(harness.SetupError, match="no workload"):
        harness.find_cell("no-such-cell")


def test_open_loop_times_each_request_from_its_due_time():
    """A stall in one request shows in the requests due behind it."""
    stall = 0.3

    def call(k, until):
        if k == 0:
            time.sleep(stall)
        return traffic.Outcome(k, 0.0, 0.0, 0.0, ok=True)

    offsets = [0.0, 0.05, 0.1]
    out, (t0, t1) = traffic.open_loop(call, offsets, 0.2, workers=1)
    assert t1 - t0 == pytest.approx(0.2)
    for o, off in zip(out, offsets):
        assert o.due == pytest.approx(t0 + off)
        assert o.end - o.due >= stall - off - 1e-3


def test_arrivals_are_poisson_gaps_at_the_rate():
    off = traffic.arrival_offsets(8.0, 32.0)
    assert len(off) == 256 and off[0] == 0.0
    gaps = [b - a for a, b in zip(off, off[1:])]
    assert min(gaps) > 0
    assert 28.0 < off[-1] < 32.0
    assert list(off) == list(traffic.arrival_offsets(8.0, 32.0))


def _run(cell, outcomes, window, trace=None, kind="TPU v5 lite"):
    return harness.Run(cell, 1.0, 0.5, outcomes, window, trace, kind)


def test_solve_s_is_window_over_solves():
    cell = harness.find_cell("p7-256-cg")
    outs = [traffic.Outcome(i, 0.0, 0.0, 10.0 + i, ok=True, niterations=500)
            for i in range(4)]
    read = cell.reader("solve_s").read
    assert read(_run(cell, outs, (10.0, 13.0))) == pytest.approx(3.0 / 4)
    outs[1].ok = False                     # not a certified solve
    assert read(_run(cell, outs, (10.0, 13.0))) == pytest.approx(3.0 / 3)


def test_served_per_s_counts_answers_inside_the_window(tiny_root):
    cell = harness.find_cell("p7-128-serve", root=tiny_root)
    outs = [traffic.Outcome(i, 0.0, 0.0, t, ok=True)
            for i, t in enumerate([1.0, 2.0, 3.9, 4.5])]
    assert cell.reader("served_per_s").read(
        _run(cell, outs, (0.0, 4.0))) == pytest.approx(3 / 4.0)


def test_latency_tail_counts_a_failed_request_as_missing(tiny_root):
    cell = harness.find_cell("p7-128-serve", root=tiny_root)
    outs = [traffic.Outcome(i, 0.0, 0.0, 0.1, ok=True) for i in range(99)]
    read = cell.reader("latency_p95_s").read
    assert read(_run(cell, outs, (0.0, 1.0))) == pytest.approx(0.1)
    outs += [traffic.Outcome(99, 0.0, 0.0, 0.1, ok=False)] * 10
    assert read(_run(cell, outs, (0.0, 1.0))) is None


def test_roofline_bytes_come_from_the_configuration_grid():
    cell = harness.find_cell("p7-256-cg")
    n = 256 ** 3
    assert counts.cg_iteration_min_bytes(cell.config) == 16 * n
    summary = TraceSummary(window_s=2.0, busy_s=[1.0], collective_s=[0.0],
                           op_s={}, gap_s={})
    outs = [traffic.Outcome(0, 0.0, 0.0, 1.0, ok=True, niterations=200)]
    share = cell.reader("cg_iter_roofline").read(
        _run(cell, outs, (0.0, 2.0), summary))
    floor_s = 16 * n / peaks.PEAKS["TPU v5 lite"]["hbm_bytes_per_s"]
    assert share == pytest.approx(100 * floor_s * 200 / 1.0)
    # the reader never consults the program's operator
    cell.operator = None
    assert cell.reader("cg_iter_roofline").read(
        _run(cell, outs, (0.0, 2.0), summary)) == pytest.approx(share)


def test_an_unknown_device_kind_has_no_peaks():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("TPU v99")


@pytest.mark.parametrize("workload", ["p7-256-cg", "p7-128-cg-4chip",
                                      "p7-128-serve", "p7-256-pipelined"])
def test_a_cell_runs_correct_on_the_cpu(tiny_root, workload):
    cell = harness.find_cell(workload, root=tiny_root)
    line = harness.run_cell(cell, 2**31 + 5, 0.5, False, time.perf_counter(),
                            jax.devices(), log=None)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    names = {m["name"] for m in cell.metrics("end_to_end")}
    assert set(line["metrics"]) == names and "setup_s" in names
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert list(line)[-1] == "checks"
    chk = line["checks"]["true_relres_max"]
    assert chk["value"] <= chk["limit"] == 2e-5
    assert line["device"]["count"] == len(jax.devices())


def test_a_new_mix_and_metric_are_files_and_entries(tiny_root, tmp_path):
    """A later change adds a traffic mix, a per-layer metric and a cell
    with new files and new entries alone."""
    root = str(tmp_path / "root")
    shutil.copytree(tiny_root, root)
    with open(os.path.join(root, "benchmark", "traffic",
                           "solve-loop-sstep.json"), "w") as f:
        json.dump({"loop": "closed", "entry": "cg_sstep", "rhs_pool": 2,
                   "options": {"sstep": 4}}, f)
    with open(os.path.join(root, "benchmark", "metrics",
                           "solves_in_window.py"), "w") as f:
        f.write("def read(run):\n    return len(run.outcomes)\n")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["workloads"].append({"name": "tiny-sstep", "config": "tiny",
                              "traffic": "solve-loop-sstep", "chips": 1,
                              "why": "s-step CG"})
    spec["per_layer"].append({"name": "solves_in_window", "unit": "solves",
                              "better": "higher",
                              "source": "program_counter",
                              "layer": "solver loop", "moves": "solve_s",
                              "workloads": ["tiny-sstep"]})
    for m in spec["end_to_end"]:
        if m["name"] == "solve_s":
            m["workloads"].append("tiny-sstep")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    cell = harness.find_cell("tiny-sstep", root=root)
    run = harness.run_cell(cell, 3, 0.3, False, time.perf_counter(),
                           jax.devices(), log=None)
    assert run["correct"] is True
    assert set(run["metrics"]) == {"setup_s", "solve_s"}
    assert "solves_in_window" in [m["name"]
                                  for m in cell.metrics("per_layer")]
    assert cell.reader("solves_in_window").read(harness.Run(
        cell, 0, 0, [1, 2], (0, 1), None, "")) == 2


def _command(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "p7-256-cg",
         "--seed", str(2**31 + 1), "--seconds", "1", "--trace", "0",
         *extra], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_the_command_refuses_a_machine_without_a_tpu():
    p = _command(REPO)
    assert p.returncode == 2, p.stderr[-2000:]
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_the_command_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        for p in json.load(f)["paths"]:
            shutil.copytree(os.path.join(REPO, p), tmp_path / p,
                            ignore=shutil.ignore_patterns("__pycache__"))
    p = _command(str(tmp_path))
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_non_finite_checks_print_as_text():
    from benchmark.run import finite_or_text

    assert finite_or_text(math.nan) == "nan"
    assert finite_or_text(2e-5) == 2e-5 and finite_or_text(0) == 0
