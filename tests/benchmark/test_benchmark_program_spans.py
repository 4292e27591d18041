"""The readers of the program's own host spans: the window filter, self
times per thread, and each metric on a synthetic run; a program without
the span ring reads as nothing."""

import time

import jax
import pytest

from benchmark import harness, program_spans
from acg_tpu.obs import trace as program_trace

# two threads: a solve call on thread 1 (with one device wait), and a
# served batch on thread 2 whose dispatch holds a call; a response on
# thread 3; one call before the window opens
SPANS = [
    ("acg.call.device_wait", 0.1, 0.5, 1), ("acg.call", 0.0, 0.9, 1),
    ("acg.call.prepare", 10.0, 10.1, 1),
    ("acg.call.device_wait", 10.2, 10.8, 1),
    ("acg.call.finish", 10.8, 10.95, 1),
    ("acg.call", 10.0, 11.0, 1),
    ("acg.serve.stack", 12.0, 12.05, 2),
    ("acg.call.device_wait", 12.1, 12.5, 2),
    ("acg.call", 12.08, 12.6, 2),
    ("acg.serve.dispatch", 12.05, 12.62, 2),
    ("acg.serve.demux", 12.62, 12.7, 2),
    ("acg.serve.batch", 12.0, 12.7, 2),
    ("acg.serve.respond", 12.7, 12.73, 3),
    ("solve", 12.06, 12.61, 2),                 # not the program layer
]


@pytest.fixture
def spans(monkeypatch):
    monkeypatch.setattr(program_trace, "program_spans", lambda: SPANS)


def _run(cell, window):
    return harness.Run(cell, 1.0, 0.5, [], window, None, "TPU v5 lite")


def test_window_spans_start_inside_the_window(spans):
    cell = harness.find_cell("p7-256-cg")
    got = program_spans.window_spans(_run(cell, (5.0, 20.0)))
    assert {s[1] for s in got} == {s[1] for s in SPANS
                                   if s[1] >= 5.0 and s[0] != "solve"}
    assert program_spans.window_spans(_run(cell, (30.0, 40.0))) is None


def test_self_seconds_per_thread():
    nested = [("acg.call", 0.0, 1.0, 1), ("acg.call.dispatch", 0.1, 0.4, 1),
              ("acg.call.device_wait", 0.4, 0.9, 1),
              ("acg.call", 0.2, 0.3, 2)]            # another thread
    selfs = {(s[0], s[3]): x for s, x in program_spans.self_seconds(nested)}
    assert selfs[("acg.call", 1)] == pytest.approx(0.2)
    assert selfs[("acg.call.dispatch", 1)] == pytest.approx(0.3)
    assert selfs[("acg.call", 2)] == pytest.approx(0.1)


def test_call_host_ms_per_solve(spans):
    cell = harness.find_cell("p7-256-cg")
    read = cell.reader("call_host_ms_per_solve").read
    # window [5, 12.65): the call on thread 1 (1.0 s less a 0.6 s wait)
    # and the served batch's call on thread 2 (0.52 s less 0.4 s)
    assert read(_run(cell, (5.0, 12.65))) == pytest.approx(
        1e3 * (0.4 + 0.12) / 2)
    assert read(_run(cell, (30.0, 40.0))) is None


def test_serve_host_ms_per_batch(spans, tiny_root):
    cell = harness.find_cell("p7-128-serve", root=tiny_root)
    read = cell.reader("serve_host_ms_per_batch").read
    # the batch's own stages and response, less the device wait: stack
    # 0.05 + dispatch 0.57 - call 0.52 + call 0.52 - wait 0.4 + demux
    # 0.08 + respond 0.03; the batch span itself holds no self time
    assert read(_run(cell, (11.5, 20.0))) == pytest.approx(
        1e3 * (0.05 + 0.05 + 0.12 + 0.08 + 0.03))
    assert read(_run(cell, (5.0, 11.5))) is None     # no batch there


def test_a_program_without_the_ring_reads_nothing(monkeypatch):
    monkeypatch.delattr(program_trace, "program_spans")
    cell = harness.find_cell("p7-256-cg")
    assert cell.reader("call_host_ms_per_solve").read(
        _run(cell, (0.0, 1.0))) is None


def test_a_solve_of_the_program_is_read(tiny_root):
    """The reader on the spans a real CPU solve leaves behind."""
    cell = harness.find_cell("p7-256-cg", root=tiny_root)
    line = harness.run_cell(cell, 2**31 + 7, 0.3, False, time.perf_counter(),
                            jax.devices(), log=None)
    assert line["correct"] is True
    window = (time.perf_counter() - 600.0, time.perf_counter())
    host_ms = cell.reader("call_host_ms_per_solve").read(
        _run(cell, window))
    assert host_ms is not None and host_ms > 0
