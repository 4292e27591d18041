"""The general traffic generator: right-hand sides, arrival schedules and
the two loops that drive the system under test.

A traffic mix is a JSON file in ``benchmark/traffic/`` that this module
reads; nothing here knows a mix by name.

- ``"loop": "closed"``: one client runs solves back to back.  The
  window opens at the first solve and closes when the first solve that
  ends ``seconds`` or more after the opening returns, so every solve in
  it is whole and the window holds nothing else.
- ``"loop": "open"``: independent clients send requests at
  ``rate_per_s``; each request is timed from its due time, so a stall
  shows in every request due behind it.  Arrivals are Poisson: the gaps
  are the quantiles of the exponential distribution in one fixed
  shuffled order.  The order sets where bursts fall, and the tail
  latency follows the bursts, so it is the same for every seed (on the
  chip, the 95th percentile of one rate read 0.06 to 0.10 s over three
  orders and within 1% on one order run twice); the seed picks each
  request's vector.

Right-hand sides come from :func:`rhs_set`.
"""

from __future__ import annotations

import concurrent.futures
import dataclasses
import math
import time

import numpy as np

clock = time.perf_counter


# the draws that every seed's right-hand sides are made from, and the
# order of the arrival gaps
BASE_SEED = 20261015
ARRIVAL_SEED = 20261016
DRAWN = 16


def rhs_set(operator, cfg: dict, seed: int, count: int) -> np.ndarray:
    """``count`` float32 right-hand sides for ``seed`` (any non-negative
    integer).  Right-hand side k is draw ``k % DRAWN`` of a fixed set of
    N(0, 1) vectors, moved by a symmetry of the operator that the seed
    picks (``operator.transform``).  A symmetry leaves CG's iteration
    count as it is, so every seed asks for the same work on other
    vectors, in the same order."""
    n = math.prod(cfg["grid"])
    drawn = min(DRAWN, count)
    base = np.random.default_rng(BASE_SEED).standard_normal(
        (drawn, n), dtype=np.float32)
    codes = np.random.default_rng(seed).integers(
        0, operator.transforms(cfg), size=count)
    out = np.empty((count, n), dtype=np.float32)
    for k in range(count):
        out[k] = operator.transform(base[k % drawn], cfg, int(codes[k]))
    return out


def arrival_offsets(rate_per_s: float, seconds: float) -> np.ndarray:
    """Due times, in seconds from the window's opening, of the
    ``round(rate * seconds)`` requests of one window."""
    count = max(1, round(rate_per_s * seconds))
    q = (np.arange(count) + 0.5) / count
    gaps = -np.log1p(-q) / rate_per_s
    np.random.default_rng(ARRIVAL_SEED).shuffle(gaps)
    return np.concatenate([[0.0], np.cumsum(gaps[:-1])])


@dataclasses.dataclass
class Outcome:
    """One solve or request of the window: its timing, whether the
    program reported success, and what it returned for the check."""

    index: int              # which right-hand side
    due: float              # due time (closed loop: call time)
    start: float            # call time
    end: float              # result on the host
    ok: bool
    x: object = None        # the solution as returned, or None
    niterations: int = 0
    error: str | None = None
    extra: dict = dataclasses.field(default_factory=dict)


def closed_loop(call, nrhs: int, seconds: float):
    """Run ``call(i % nrhs)`` for i = 0, 1, ... back to back until one
    ends ``seconds`` after the first began.
    ``call`` returns an :class:`Outcome` without times.  Returns the
    outcomes and the window's (open, close) on :func:`clock`."""
    out = []
    t0 = clock()
    i = 0
    while True:
        s = clock()
        o = call(i % nrhs)
        o.due, o.start, o.end = s, s, clock()
        out.append(o)
        i += 1
        if o.end - t0 >= seconds:
            return out, (t0, o.end)


def open_loop(call, offsets, seconds: float, workers: int = 64):
    """Send request k at ``offsets[k]`` after the window opens; each is
    ``call(k, wait_until)`` on a pool thread and returns an
    :class:`Outcome` without times.  At most ``workers`` requests are
    outstanding; a later one starts when a thread frees, late, and its
    latency still counts from its due time.  ``wait_until`` is the clock time
    after which a request that has not answered counts as failed: a
    minute past the window's close.  Returns the outcomes and the
    window's (open, close)."""
    pool = concurrent.futures.ThreadPoolExecutor(max_workers=workers)
    futures = []
    try:
        t0 = clock()
        wait_until = t0 + seconds + 60.0

        def task(k, due):
            s = clock()
            o = call(k, wait_until)
            o.due, o.start, o.end = due, s, clock()
            return o

        for k, off in enumerate(offsets):
            due = t0 + float(off)
            delay = due - clock()
            if delay > 0:
                time.sleep(delay)
            futures.append(pool.submit(task, k, due))
        out = [f.result() for f in futures]
    finally:
        pool.shutdown(wait=True)
    return out, (t0, t0 + seconds)


def percentile(values, q: float) -> float:
    """The q-th percentile (linear interpolation); infinite values, which
    stand for failed requests, sort last."""
    v = np.sort(np.asarray(values, dtype=np.float64))
    if len(v) == 0:
        return math.nan
    pos = (len(v) - 1) * q / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    if lo == hi or v[hi] == v[lo]:
        return float(v[lo])
    return float(v[lo] + (v[hi] - v[lo]) * (pos - lo))

