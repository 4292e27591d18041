"""Host-side spans, and the names the program gives its layers.

One tracing path serves the whole program.  :func:`span` is a context
manager that enters a ``jax.profiler.TraceAnnotation`` (so the host
timeline lines up with device traces captured via ``--profile`` or any
``jax.profiler`` trace; the annotation is a cheap no-op when no trace is
active, and jax import failures degrade to wall-clock-only spans — the
tracer must never take down the solve it observes), records the closed
span in the process's bounded ring of program spans
(:func:`program_spans`), and, when the caller holds one, in a
:class:`SpanTracer` — the phase timeline the CLI and a serving session
keep (read, partition, operator-build, compile, solve).

Names follow one scheme, ``acg.<layer>[.<stage>]``:

- host spans, one per call, batch or response (never per iteration):
  ``acg.call`` (attribute ``solver``) around every solver entry, with the
  children ``acg.call.prepare``, ``.dispatch``, ``.device_wait``,
  ``.fetch``, ``.copyback`` and ``.finish`` in that order, and
  ``acg.call.segment`` per re-dispatch of a segmented solve;
  ``acg.serve.batch`` (``n``, ``bucket``) with ``acg.serve.stack``,
  ``.dispatch`` and ``.demux``, and ``acg.serve.respond`` (``trace_id``)
  per response; ``acg.prep.assemble``, ``.partition`` and ``.upload``
  while an operator is prepared, and ``acg.prep.mg`` while a multigrid
  hierarchy is planned;
- device scopes (``jax.named_scope``) on every op of the solve loops:
  ``acg.spmv`` (``.local``, ``.iface`` and ``.skin`` on the distributed
  path), ``acg.blas1``, ``acg.halo.pack`` / ``.exchange`` / ``.unpack``,
  ``acg.allreduce``, ``acg.check``, and around a preconditioner
  ``acg.precond`` with the V-cycle's ``acg.mg.smooth`` / ``.residual``
  / ``.restrict`` / ``.prolong``; the innermost ``acg.*`` scope of an
  op's name path is its layer.  Pallas kernels carry ``acg_<kernel>``
  names (``pallas_call(name=...)``).

The reference driver interleaves ``acgtime_gettime`` pairs around each
pipeline stage and prints deltas (ref cuda/acg-cuda.c:1296-2261); here
the same timeline is recorded structurally so it can be exported into
the ``--output-stats-json`` document (acg_tpu/obs/export.py) instead of
living only in scrollback.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import itertools
import threading
import time

#: How many closed spans the process keeps for :func:`program_spans` —
#: a served run of a minute closes a few thousand.
PROGRAM_SPANS_KEPT = 16384
# (name, start, end, thread ident), perf_counter seconds, closing order.
# Process-wide like the profiler the spans also feed; deque appends are
# atomic, so recording takes no lock.
_program_spans: collections.deque = collections.deque(
    maxlen=PROGRAM_SPANS_KEPT)


def program_spans() -> list[tuple]:
    """The spans the process closed most recently, oldest first, as
    ``(name, start, end, thread)`` with ``time.perf_counter`` times and
    the closing thread's ident."""
    return list(_program_spans)


@dataclasses.dataclass
class Span:
    """One completed (or in-flight) phase span.

    ``start`` is seconds since the tracer's epoch; ``duration`` is NaN
    while the span is still open.  ``depth`` is the nesting level at
    entry (0 = top-level phase)."""

    name: str
    start: float
    duration: float = float("nan")
    depth: int = 0
    seq: int = dataclasses.field(default=0, repr=False)

    def as_dict(self) -> dict:
        return {"name": self.name, "start": self.start,
                "duration": self.duration, "depth": self.depth}


@contextlib.contextmanager
def span(name: str, tracer: SpanTracer | None = None, **attrs):
    """A host span: a ``TraceAnnotation`` carrying ``attrs``, one entry
    in :func:`program_spans`, and a :class:`Span` in ``tracer`` when one
    is given (yielded; None otherwise)."""
    t0 = time.perf_counter()
    sp = tracer._open(name) if tracer is not None else None
    try:
        with _trace_annotation(name, **attrs):
            yield sp
    finally:
        if sp is not None:
            tracer._close(sp)
        _program_spans.append((name, t0, time.perf_counter(),
                               threading.get_ident()))


class SpanTracer:
    """Nestable wall-clock spans with optional live logging.

    ``log``, when given, is called with one formatted line as each span
    closes (the CLI routes this through its ``-v`` logger, replacing the
    ad-hoc timestamp prints).  Set-up spans (:attr:`SETUP`) are kept for
    the tracer's life; of the rest only the last :attr:`KEEP`, so a
    long-running service holds a bounded timeline.  :meth:`total` and
    :meth:`count` run over every span ever closed.  ``spans`` lists the
    kept ones in COMPLETION order; :meth:`as_dicts` sorts them by start
    time, the order a timeline reader expects.  Nesting depth is per
    thread.
    """

    #: span names that describe building the session, not serving it
    SETUP = frozenset({"read", "partition", "operator-build", "compile"})
    #: how many of the other spans are kept, the most recent
    KEEP = 256

    def __init__(self, log=None, clock=time.perf_counter):
        self._clock = clock
        self._epoch = clock()
        self._log = log
        self._local = threading.local()
        self._lock = threading.Lock()
        self._seq = itertools.count(1)
        self._last = 0
        self._setup: list[Span] = []
        self._recent: collections.deque = collections.deque(
            maxlen=self.KEEP)
        self._totals: dict[str, list] = {}     # name -> [seconds, count]

    def _stack(self) -> list:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def _open(self, name: str) -> Span:
        st = self._stack()
        sp = Span(name=name, start=self._clock() - self._epoch,
                  depth=len(st))
        st.append(sp)
        return sp

    def _close(self, sp: Span) -> None:
        sp.duration = (self._clock() - self._epoch) - sp.start
        self._stack().pop()
        with self._lock:
            sp.seq = self._last = next(self._seq)
            (self._setup if sp.name in self.SETUP
             else self._recent).append(sp)
            tot = self._totals.setdefault(sp.name, [0.0, 0])
            if sp.duration == sp.duration:
                tot[0] += sp.duration
            tot[1] += 1
        if self._log is not None:
            self._log(f"{'  ' * sp.depth}[{sp.name}] "
                      f"{sp.duration:.3f}s")

    def span(self, name: str):
        """A span recorded here (see :func:`span`)."""
        return span(name, tracer=self)

    @property
    def spans(self) -> list[Span]:
        """The kept spans in completion order."""
        with self._lock:
            return sorted(itertools.chain(self._setup, self._recent),
                          key=lambda s: s.seq)

    def mark(self) -> int:
        """A position in the completion order: pass two of them to
        :meth:`as_dicts` for the spans closed in between."""
        return self._last

    def as_dicts(self, since: int | None = None,
                 until: int | None = None) -> list[dict]:
        """Kept spans as JSON-ready dicts, sorted by start time.  With
        ``since``/``until`` (:meth:`mark` values): the set-up spans and
        the other spans that closed after ``since`` and up to ``until``
        — one dispatch's share of a long-running session."""
        lo = 0 if since is None else since
        hi = self._last if until is None else until
        return [s.as_dict() for s in sorted(
            (s for s in self.spans
             if s.name in self.SETUP or lo < s.seq <= hi),
            key=lambda s: s.start)]

    @property
    def epoch(self) -> float:
        """The tracer's clock origin (perf_counter seconds) — what
        :func:`acg_tpu.obs.events.chrome_trace` uses to put phase spans
        and flight-recorder timelines on one timebase."""
        return self._epoch

    def as_chrome_trace(self, pid: int = 0, tid: int = 0) -> list[dict]:
        """Kept spans as Chrome trace-event dicts (``ph="X"``
        complete events, microsecond timestamps) — the payload of the
        CLI's ``--trace-json`` and one half of
        :func:`acg_tpu.obs.events.chrome_trace`.  Nested spans share
        one tid; trace viewers stack them by containment."""
        out = []
        for s in sorted(self.spans, key=lambda s: s.start):
            dur = 0.0 if s.duration != s.duration else s.duration
            out.append({"name": s.name, "ph": "X", "pid": pid,
                        "tid": tid, "ts": s.start * 1e6,
                        "dur": dur * 1e6, "cat": "phase",
                        "args": {"depth": s.depth}})
        return out

    def elapsed(self) -> float:
        """Wall time since the tracer was created."""
        return self._clock() - self._epoch

    def total(self, name: str) -> float:
        """Summed duration of every completed span with this name — the
        aggregate the serve layer's ``session.stats()`` reports (e.g.
        total compile wall across all cache misses)."""
        return float(self._totals.get(name, (0.0, 0))[0])

    def count(self, name: str) -> int:
        """How many completed spans carry this name (the serve tests'
        "a warm solve opened no compile span" witness)."""
        return int(self._totals.get(name, (0.0, 0))[1])


def _trace_annotation(name: str, **attrs):
    """``jax.profiler.TraceAnnotation`` when jax is importable, else a
    null context — span timing must survive a broken backend."""
    try:
        import jax

        return jax.profiler.TraceAnnotation(name, **attrs)
    except Exception:
        return contextlib.nullcontext()
