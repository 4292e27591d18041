"""The HPCG configuration's benchmark files: its operator's own float64
reference against the program's matrices, the symmetries, the work
count and its roofline reader, the precision control, and a whole run
of the ``hpcg27-256-mg`` cell at 16³ on the CPU.

``benchmark_roots.make_root`` builds its tiny configuration from the
last entry of ``configs``, which is ``hpcg27-256`` since that entry was
appended; so the 7-point cells are also run here on a tiny copy of
their own configuration, which keeps the 7-point operator and its
reference under ``harness.run_cell``."""

import json
import os
import shutil
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from acg_tpu.sparse.poisson import poisson3d_27pt, poisson3d_27pt_dia
from benchmark import harness, peaks, reference, traffic
from benchmark.counts_mg import mg_pcg_iteration_min_bytes
from benchmark.operators import hpcg27
from benchmark.trace_reduce import TraceSummary
from benchmark_roots import make_root

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "hpcg27-256-mg"
POISSON_CELLS = ["p7-256-cg", "p7-128-cg-4chip", "p7-128-serve",
                 "p7-256-pipelined"]


def _config() -> dict:
    with open(os.path.join(REPO, "benchmark", "configs",
                           "hpcg27-256.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def root16(tmp_path_factory):
    """A copy of the benchmark whose HPCG cell runs a 16³ grid: a new
    configuration file and entry beside the files that are there."""
    root = str(tmp_path_factory.mktemp("hpcg27"))
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    cfg = dict(_config(), grid=[16, 16, 16])
    with open(os.path.join(root, "benchmark", "configs", "hpcg27-16.json"),
              "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"].append({"name": "hpcg27-16", "source": "test",
                            "file": "benchmark/configs/hpcg27-16.json",
                            "reduced": ["grid"], "why": "CPU test size"})
    for w in spec["workloads"]:
        if w["name"] == CELL:
            w["config"] = "hpcg27-16"
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return root


@pytest.fixture(scope="module")
def poisson_root(tmp_path_factory):
    """``make_root``'s copy of the benchmark, its tiny configuration a
    12³ copy of ``poisson7-128``."""
    root = make_root(tmp_path_factory.mktemp("poisson7"))
    with open(os.path.join(REPO, "benchmark", "configs",
                           "poisson7-128.json")) as f:
        cfg = dict(json.load(f), grid=[12, 12, 12])
    with open(os.path.join(root, "benchmark", "configs", "tiny.json"),
              "w") as f:
        json.dump(cfg, f)
    return root


@pytest.mark.parametrize("workload", POISSON_CELLS)
def test_the_poisson_cells_run_correct_on_their_own_operator(
        poisson_root, workload):
    cell = harness.find_cell(workload, root=poisson_root)
    assert cell.config["operator"] == "poisson7"
    assert os.path.basename(cell.operator.__file__) == "poisson7.py"
    line = harness.run_cell(cell, 2**31 + 5, 0.5, False, time.perf_counter(),
                            jax.devices(), log=None)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    names = {m["name"] for m in cell.metrics("end_to_end")}
    assert set(line["metrics"]) == names and "setup_s" in names
    chk = line["checks"]["true_relres_max"]
    assert chk["value"] <= chk["limit"] == 2e-5


def test_the_cell_runs_correct_on_the_cpu(root16):
    cell = harness.find_cell(CELL, root=root16)
    assert cell.traffic["options"] == {"precond": "mg", "mg_levels": 4}
    line = harness.run_cell(cell, 2**31 + 11, 0.5, False,
                            time.perf_counter(), jax.devices(), log=None)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert set(line["metrics"]) == {"setup_s", "solve_s"}
    chk = line["checks"]["true_relres_max"]
    assert chk["value"] <= chk["limit"] == 2e-5
    assert [m["name"] for m in cell.metrics("per_layer")
            if m["name"] == "mg_pcg_iter_roofline"]


@pytest.mark.parametrize("grid", [(8, 8, 8), (12, 16, 20)])
def test_reference_matches_the_program_matrices(grid):
    apply = hpcg27.reference_apply({"grid": list(grid)})
    x = np.random.default_rng(1).standard_normal(int(np.prod(grid)))
    want = apply(x)
    np.testing.assert_allclose(poisson3d_27pt(*grid).matvec(x), want,
                               rtol=0, atol=1e-12)
    np.testing.assert_allclose(poisson3d_27pt_dia(*grid).matvec(x), want,
                               rtol=0, atol=1e-12)
    np.testing.assert_allclose(
        np.asarray(hpcg27.reference_apply_jnp({"grid": list(grid)})(
            jnp.asarray(x))), want, rtol=0, atol=1e-12)


@pytest.mark.parametrize("grid", [[6, 6, 6], [4, 6, 6]])
def test_transforms_commute_with_the_operator(grid):
    cfg = {"grid": grid}
    apply = hpcg27.reference_apply(cfg)
    v = np.random.default_rng(3).standard_normal(int(np.prod(grid)))
    seen = set()
    for code in range(hpcg27.transforms(cfg)):
        moved = hpcg27.transform(v, cfg, code)
        np.testing.assert_allclose(
            apply(moved), hpcg27.transform(apply(v), cfg, code), atol=1e-12)
        seen.add(moved.tobytes())
    assert len(seen) == hpcg27.transforms(cfg)


@pytest.mark.parametrize("side, levels, want", [(16, 4, 381_120),
                                                (256, 4, 1_561_067_520),
                                                (16, 3, 378_368)])
def test_mg_pcg_bytes_are_pinned(side, levels, want):
    """4 s + s per unknown for CG and z, 16 s per unknown of each level
    above the coarsest, 6 s on the coarsest: about 93 B per unknown in
    float32 with HPCG's 4 levels."""
    cfg = {"grid": [side] * 3, "dtype": "float32"}
    assert mg_pcg_iteration_min_bytes(cfg, levels) == want


def test_mg_pcg_iter_roofline_reader():
    cell = harness.find_cell(CELL)
    summary = TraceSummary(window_s=2.0, busy_s=[1.5], collective_s=[0.0],
                           op_s={}, gap_s={})
    outs = [traffic.Outcome(i, 0.0, 0.0, 1.0, ok=True, niterations=90)
            for i in range(3)]
    run = harness.Run(cell, 1.0, 0.5, outs, (0.0, 2.0), summary,
                      "TPU v5 lite")
    read = cell.reader("mg_pcg_iter_roofline").read
    floor_s = 1_561_067_520 / peaks.PEAKS["TPU v5 lite"]["hbm_bytes_per_s"]
    assert read(run) == pytest.approx(100 * floor_s * 270 / 1.5)
    outs[0].ok = False                     # not a certified solve
    assert read(run) == pytest.approx(100 * floor_s * 180 / 1.5)
    # the level count is the traffic's, not a constant of the reader
    cell.traffic = dict(cell.traffic,
                        options={"precond": "mg", "mg_levels": 3})
    floor3_s = 1_549_795_328 / peaks.PEAKS["TPU v5 lite"]["hbm_bytes_per_s"]
    assert read(run) == pytest.approx(100 * floor3_s * 180 / 1.5)
    run.trace = None
    assert read(run) is None


def test_control_one_precision_below_fails_the_limit():
    """The reference CG on the 27-point operator in bfloat16 fails the
    configuration's limit on the 16³ right-hand sides a run draws; in
    float32 it meets it."""
    cfg = dict(_config(), grid=[16, 16, 16])
    apply_np = hpcg27.reference_apply(cfg)
    apply_jnp = hpcg27.reference_apply_jnp(cfg)
    limit = cfg["certified_relres"]
    for b in traffic.rhs_set(hpcg27, cfg, 11, 3):
        x, _ = reference.cg_reference(apply_jnp, b, 1e-5, 5000, jnp.bfloat16)
        assert reference.true_relres(apply_np, b, x) > 10 * limit
        x, _ = reference.cg_reference(apply_jnp, b, 1e-5, 5000, jnp.float32)
        assert reference.true_relres(apply_np, b, x) < limit
