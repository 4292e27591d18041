"""Run one cell of ``BENCHMARK.json`` once and build its result line.

Everything a cell needs is found by name: the cell's configuration file
(``configs[].file``), its traffic mix ``benchmark/traffic/<traffic>.json``,
the operator the configuration names ``benchmark/operators/<operator>.py``
and one reader per metric ``benchmark/metrics/<metric>.py``, whose
``read(run)`` returns a number or None (nothing to read; the metric is
then left out of the line).  Adding a configuration, a mix or a metric
is adding files and entries.

A run: build the program's entry (timed as ``prep_s``), make the
right-hand sides from the seed, warm up every shape the window uses,
then measure for ``seconds`` (traced with ``trace``), read the device
peak, free the program's state, and only then check every answer of the
window against the float64 reference.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import tempfile

import numpy as np

from benchmark import sut, traffic as gen
from benchmark.reference import true_relres
from benchmark.trace_reduce import find_xplane, load_planes, reduce_planes

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# JAX monitoring events that mean a program was compiled or loaded from
# the persistent cache: none may fall inside the measured window
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")


class SetupError(RuntimeError):
    """The cell cannot run here (unknown name, too few chips)."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str):
    """Import a benchmark file by path (metric names hold dots)."""
    name = "benchmark_file_" + "".join(
        c if c.isalnum() else "_" for c in os.path.relpath(path, ROOT))
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One workload of the spec with everything found for it."""

    root: str
    spec: dict
    cell: dict
    config: dict
    traffic: dict
    operator: object

    @property
    def name(self) -> str:
        return self.cell["name"]

    def metrics(self, kind: str) -> list:
        """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
        return [m for m in self.spec[kind]
                if self.name in m.get("workloads", [self.name])]

    def reader(self, metric: str):
        return load_module(os.path.join(self.root, "benchmark", "metrics",
                                        metric + ".py"))


def find_cell(workload: str, root: str = ROOT,
              spec: dict | None = None) -> Cell:
    spec = spec if spec is not None else load_json(
        os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SetupError(f"no workload {workload!r} in BENCHMARK.json: "
                         f"{sorted(cells)}")
    cell = cells[workload]
    entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    config = load_json(os.path.join(root, entry["file"]))
    mix = load_json(os.path.join(root, "benchmark", "traffic",
                                 cell["traffic"] + ".json"))
    operator = load_module(os.path.join(root, "benchmark", "operators",
                                        config["operator"] + ".py"))
    return Cell(root, spec, cell, config, mix, operator)


@dataclasses.dataclass
class Run:
    """What a metric reader sees of one run."""

    cell: Cell
    setup_s: float
    prep_s: float
    outcomes: list              # traffic.Outcome, window order
    window: tuple               # (open, close) on traffic.clock
    trace: object | None        # trace_reduce.TraceSummary
    device_kind: str

    @property
    def config(self) -> dict:
        return self.cell.config

    @property
    def traffic(self) -> dict:
        return self.cell.traffic


class CompileCounter:
    """Counts compilations and cache loads while entered."""

    def __init__(self):
        self.count = 0

    def _listen(self, event, _duration, **_kw):
        if event in COMPILE_EVENTS:
            self.count += 1

    def __enter__(self):
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc):
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self._listen)


def drive(cell: Cell, entry, seed: int, seconds: float, log=None):
    """Make the inputs from the seed and warm up every shape the window
    uses; return ``window``, whose call runs the measured window and
    returns ``(outcomes, (open, close), rhs)``."""
    mix = cell.traffic
    t0 = gen.clock()
    if mix["loop"] == "closed":
        rhs = gen.rhs_set(cell.operator, cell.config, seed, mix["rhs_pool"])
        t1 = gen.clock()
        entry.solve(0, rhs[0])
        _note(log, f"set-up inputs_s {t1 - t0} warm_s {gen.clock() - t1}")

        def window():
            out, win = gen.closed_loop(lambda i: entry.solve(i, rhs[i]),
                                       len(rhs), seconds)
            return out, win, rhs
        return window
    if mix["loop"] == "open":
        offsets = gen.arrival_offsets(mix["rate_per_s"], seconds)
        rhs = gen.rhs_set(cell.operator, cell.config, seed, mix["rhs_pool"])
        t1 = gen.clock()
        entry.warm(rhs)
        _note(log, f"set-up inputs_s {t1 - t0} warm_s {gen.clock() - t1}")

        def window():
            out, win = gen.open_loop(
                lambda k, until: entry.request(k % len(rhs), rhs[k % len(rhs)],
                                               until),
                offsets, seconds)
            return out, win, rhs
        return window
    raise SetupError(f"unknown loop {mix['loop']!r} (closed, open)")


def _note(log, text: str) -> None:
    if log is not None:
        print(text, file=log, flush=True)


def describe_window(outcomes, window) -> str:
    """One line on what the window held, for standard error."""
    its = [o.niterations for o in outcomes if o.ok]
    took = [o.end - o.start for o in outcomes]
    text = (f"window_s {window[1] - window[0]} attempted {len(outcomes)} "
            f"iterations {min(its, default=0)}..{max(its, default=0)} "
            f"call_s {min(took, default=0)} {gen.percentile(took, 50)} "
            f"{max(took, default=0)}")
    lat = [o.end - o.due for o in outcomes]
    lag = [o.start - o.due for o in outcomes]
    if outcomes and max(lag) > 0:
        text += (f" latency_max_s {max(lat)} "
                 f"generator_lag_p95_s {gen.percentile(lag, 95)}")
    return text


def peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def check(cell: Cell, outcomes, rhs) -> tuple:
    """``(correct, failed, checks)``: every answer of the window against
    the float64 reference; ``checks`` maps each number compared to
    ``{"value", "limit"}``."""
    apply = cell.operator.reference_apply(cell.config)
    failed = sum(1 for o in outcomes if not o.ok or o.x is None)
    rels = [true_relres(apply, rhs[o.index], o.x) for o in outcomes
            if o.ok and o.x is not None]
    worst = float(np.max(rels)) if rels else 0.0     # a NaN stays NaN
    limit = float(cell.config["certified_relres"])
    checks = {"true_relres_max": {"value": worst, "limit": limit},
              "failed": {"value": failed, "limit": 0}}
    correct = bool(outcomes) and failed == 0 and worst <= limit
    return correct, failed, checks


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, devices, log=sys.stderr) -> dict:
    """One run of ``cell``; returns the result line as a dict."""
    import jax
    from jax.profiler import TraceAnnotation

    chips = int(cell.cell["chips"])
    if len(devices) < chips:
        raise SetupError(f"{cell.name} needs {chips} chips, JAX sees "
                         f"{len(devices)}")
    used = devices[:chips]
    t0 = gen.clock()
    with TraceAnnotation("bench.prep"):
        entry = sut.build(cell.config, cell.traffic, cell.operator)
        entry.ready()
    prep_s = gen.clock() - t0
    _note(log, f"set-up prep_s {prep_s}")
    window = drive(cell, entry, seed, seconds, log)
    setup_s = gen.clock() - t_start
    logdir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        if trace:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0     # annotations only
            jax.profiler.start_trace(logdir, profiler_options=options)
        try:
            cpu0 = os.times()
            with CompileCounter() as compiles, \
                    TraceAnnotation("bench.window"):
                outcomes, win, rhs = window()
            cpu1 = os.times()
        finally:
            if trace:
                jax.profiler.stop_trace()
        memory_peak = peak_bytes(used)
        summary = (reduce_planes(load_planes(find_xplane(logdir)), chips)
                   if trace else None)
    finally:
        if logdir is not None:
            shutil.rmtree(logdir, ignore_errors=True)
    entry.close()
    del entry
    _note(log, describe_window(outcomes, win) + " host_cpu_s "
          f"{cpu1.user + cpu1.system - cpu0.user - cpu0.system}")
    if summary is not None:
        for name, sec in summary.breakdown(5)["device_ops"]:
            _note(log, f"op {sec} {summary.op_text[name][:400]}")
    for o in [o for o in outcomes if not o.ok][:3]:
        _note(log, f"failed {o.index}: {o.error}")
    _note(log, f"compiles_in_window {compiles.count}")
    run = Run(cell, setup_s, prep_s, outcomes, win, summary,
              used[0].device_kind)
    correct, failed, checks = check(cell, outcomes, rhs)
    metrics = {}
    for m in cell.metrics("per_layer" if trace else "end_to_end"):
        value = cell.reader(m["name"]).read(run)
        if value is not None and np.isfinite(value):
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = {"platform": used[0].platform, "kind": used[0].device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    line = {"correct": correct, "attempted": len(outcomes),
            "failed": failed, "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.mean_busy_s
        device["window_s"] = summary.window_s
        line["breakdown"] = summary.breakdown()
    line["checks"] = checks
    return line
