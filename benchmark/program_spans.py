"""The program's own host spans over a run's window, for the readers of
``program_span`` metrics.

The program records every span it closes (``acg.call`` and its stages
around each solver entry, ``acg.serve.*`` around each served batch and
response) in a bounded ring that ``acg_tpu.obs.trace.program_spans``
returns as ``(name, start, end, thread)`` on ``time.perf_counter``, the
clock of the run's window.  A program without that ring has nothing to
read: :func:`window_spans` then returns None.
"""

from __future__ import annotations


def window_spans(run):
    """The program's ``acg.*`` spans that started inside the window;
    None where the program records none."""
    try:
        from acg_tpu.obs.trace import program_spans
    except ImportError:
        return None
    w0, w1 = run.window
    spans = [s for s in program_spans() if s[0].startswith("acg.")]
    return [s for s in spans if w0 <= s[1] < w1] or None


def outermost(spans, name: str) -> list:
    """The spans called ``name`` that no other such span on the same
    thread holds (a solver entry that calls another entry counts once)."""
    own = [s for s in spans if s[0] == name]
    return [s for s in own
            if not any(o is not s and o[3] == s[3] and o[1] <= s[1]
                       and s[2] <= o[2] for o in own)]


def self_seconds(spans) -> list:
    """``(span, self seconds)``: each span's duration less that of the
    spans directly nested in it on the same thread."""
    out = []
    threads = {s[3] for s in spans}
    for t in threads:
        evs = sorted((s for s in spans if s[3] == t),
                     key=lambda s: (s[1], -s[2]))
        selfs = [s[2] - s[1] for s in evs]
        stack = []
        for i, s in enumerate(evs):
            while stack and evs[stack[-1]][2] <= s[1]:
                stack.pop()
            if stack:
                selfs[stack[-1]] -= s[2] - s[1]
            stack.append(i)
        out += [(s, max(x, 0.0)) for s, x in zip(evs, selfs)]
    return out
