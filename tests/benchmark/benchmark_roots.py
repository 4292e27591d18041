"""Benchmark roots for the benchmark's own tests: a copy of the
benchmark whose cells run a small grid on the CPU.

The copy is built the way a later change adds a configuration: a new
configuration file and new ``BENCHMARK.json`` entries beside the files
that are there, none of them edited.
"""

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TINY_GRID = 12
# cells whose traffic and metric files the benchmark has but whose
# entries a later change adds; the test roots add them as that change
# would, with entries alone
LATER_CELLS = [
    {"name": "p7-128-serve", "traffic": "serve-overload", "chips": 1,
     "why": "open-loop arrivals above capacity, batches of 4"},
    {"name": "p7-256-pipelined", "traffic": "solve-loop-pipelined",
     "chips": 1, "why": "pipelined CG"},
]
LATER_METRICS = {
    "end_to_end": [{"name": "served_per_s", "unit": "req/s",
                    "better": "higher", "bound": 0.05,
                    "source": "host_clock", "workloads": ["p7-128-serve"]}],
    "per_layer": [
        {"name": name, "unit": unit, "better": "lower", "source": source,
         "layer": layer, "moves": "served_per_s",
         "workloads": ["p7-128-serve"]}
        for name, unit, source, layer in [
            ("device_idle_share.serve", "%", "device_trace", "device"),
            ("latency_p95_s", "s", "host_clock", "serve"),
            ("queue_wait_mean_s", "s", "program_span", "serve"),
            ("batch_occupancy", "%", "program_counter", "serve")]],
}


def add_later_cells(spec: dict, config: str) -> None:
    """Add the cells and metrics of LATER_CELLS / LATER_METRICS."""
    names = {w["name"] for w in spec["workloads"]}
    spec["workloads"] += [dict(c, config=config) for c in LATER_CELLS
                          if c["name"] not in names]
    have = {m["name"] for m in spec["end_to_end"] + spec["per_layer"]}
    for kind, metrics in LATER_METRICS.items():
        spec[kind] += [m for m in metrics if m["name"] not in have]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if m["name"] in ("solve_s", "device_idle_share.solve",
                         "iterations_per_solve", "prep_s"):
            m["workloads"] = sorted(set(m["workloads"])
                                    | {"p7-256-pipelined"})
        if m["name"] == "prep_s":
            m["workloads"] = sorted(set(m["workloads"]) | {"p7-128-serve"})


def make_root(path, grid: int = TINY_GRID, rate_per_s: float = 40.0) -> str:
    """A benchmark root under ``path`` whose cells use a ``grid``³
    configuration named ``tiny``; the served mix offers
    ``rate_per_s`` requests per second."""
    root = str(path)
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        spec = json.load(f)
    src = spec["configs"][-1]["file"]
    with open(os.path.join(REPO, src)) as f:
        cfg = json.load(f)
    cfg["grid"] = [grid] * 3
    with open(os.path.join(root, "benchmark", "configs", "tiny.json"),
              "w") as f:
        json.dump(cfg, f)
    spec["configs"].append({"name": "tiny", "source": "test",
                            "file": "benchmark/configs/tiny.json",
                            "reduced": ["grid"], "why": "CPU test size"})
    for w in spec["workloads"]:
        w["config"] = "tiny"
    add_later_cells(spec, "tiny")
    for w in spec["workloads"]:
        mix_path = os.path.join(root, "benchmark", "traffic",
                                w["traffic"] + ".json")
        with open(mix_path) as f:
            mix = json.load(f)
        if mix["loop"] == "open":
            mix["rate_per_s"] = rate_per_s
            with open(mix_path, "w") as f:
                json.dump(mix, f)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return root

