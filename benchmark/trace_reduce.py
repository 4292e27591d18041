"""Reduce a profiler trace to device busy time, idle share, collective
time, the ops that took most time and the idle gaps named by what the
host was doing.

The input is JAX's ``.xplane.pb`` as :class:`jax.profiler.ProfileData`
reads it: planes of lines of events, each with a name, ``start_ns`` and
``duration_ns``.  Device planes are ``/device:TPU:<id>``; their ``XLA
Ops`` line holds one event per operation run, named by its HLO text.  The benchmark wraps its
measured window in the host annotation ``bench.window`` and its calls
into the program in other ``bench.*`` annotations (RHS upload, solve
call, submit, response wait); an idle gap is named by the innermost
such annotation open at its midpoint, ``idle.no_call_open`` where none
is (a served cell waiting for requests).

Busy time is the union of the op intervals inside the window, so ops
that overlap count once; op and collective times are self times (an
op's duration less that of the ops nested in it on the same line).
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
WINDOW = "bench.window"
ANNOTATION_PREFIX = "bench."
NO_ANNOTATION = "idle.no_call_open"
# an op event's name is its HLO text, "%name = type opcode(operands), ..."
HLO_NAME = re.compile(r"^%?([^\s=]+)")
HLO_OPCODE = re.compile(r"(?:^|\s)([a-z][a-z0-9-]*)\(")
# cross-chip collectives by opcode, with their -start/-done halves
COLLECTIVES = ("all-reduce", "collective-permute", "all-gather",
               "reduce-scatter", "all-to-all")


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: list            # per chip used
    collective_s: list      # per chip used
    op_s: dict              # op name -> self seconds, mean over chips
    gap_s: dict             # annotation -> idle seconds, mean over chips
    op_text: dict = dataclasses.field(default_factory=dict)  # name -> HLO

    @property
    def mean_busy_s(self) -> float:
        return float(np.mean(self.busy_s))

    @property
    def mean_collective_s(self) -> float:
        return float(np.mean(self.collective_s))

    @property
    def idle_share(self) -> float:
        """Fraction of the window in which no op ran, mean over chips."""
        return 1.0 - self.mean_busy_s / self.window_s

    def breakdown(self, top: int = 10) -> dict:
        def largest(d):
            return [[k, float(v)] for k, v in
                    sorted(d.items(), key=lambda kv: -kv[1])[:top]]

        return {"device_ops": largest(self.op_s),
                "idle_gaps": largest(self.gap_s)}


def find_xplane(logdir: str) -> str:
    """The newest ``.xplane.pb`` under a ``jax.profiler`` log directory."""
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return max(paths, key=os.path.getmtime)


def load_planes(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path).planes


def _intervals(events) -> np.ndarray:
    """(k, 2) array of [start, end) in ns, sorted by start."""
    iv = np.array([(e.start_ns, e.start_ns + e.duration_ns) for e in events],
                  dtype=np.float64).reshape(-1, 2)
    return iv[np.argsort(iv[:, 0], kind="stable")]


def union(iv: np.ndarray) -> np.ndarray:
    """Merge sorted [start, end) intervals into disjoint ones."""
    if len(iv) == 0:
        return iv
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out)


def clip(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    if len(iv) == 0:
        return iv
    c = np.stack([np.maximum(iv[:, 0], lo), np.minimum(iv[:, 1], hi)], 1)
    return c[c[:, 1] > c[:, 0]]


def self_times(events) -> list:
    """``(name, start, self_ns)`` per event: its duration less that of
    the events directly nested in it on the same line."""
    evs = sorted(((e.start_ns, e.start_ns + e.duration_ns, e.name)
                  for e in events), key=lambda t: (t[0], -t[1]))
    selfs = [e - s for s, e, _ in evs]
    stack = []          # indices of open events
    for i, (s, e, _) in enumerate(evs):
        while stack and evs[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            selfs[stack[-1]] -= e - s
        stack.append(i)
    return [(name, s, max(ns, 0.0)) for (s, _e, name), ns in zip(evs, selfs)]


def op_name(event_name: str) -> str:
    """The HLO instruction name of an op event (``fusion.12``)."""
    m = HLO_NAME.match(event_name)
    return m.group(1) if m else event_name


def opcode(event_name: str) -> str:
    """The HLO opcode of an op event; for a bare name, its stem."""
    _, eq, rest = event_name.partition(" = ")
    m = HLO_OPCODE.search(rest) if eq else None
    return m.group(1) if m else op_name(event_name).split(".")[0]


def is_collective(event_name: str) -> bool:
    op = opcode(event_name)
    return op.removesuffix("-start").removesuffix("-done") in COLLECTIVES


def _annotations(planes):
    """Host ``bench.*`` events as (name, start, end)."""
    out = []
    for p in planes:
        if p.name != HOST_PLANE:
            continue
        for line in p.lines:
            for e in line.events:
                if e.name.startswith(ANNOTATION_PREFIX):
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns))
    return out


def name_gaps(gaps: np.ndarray, annotations) -> dict:
    """Idle ns per annotation: each gap goes to the shortest annotation
    (other than the window) open at its midpoint."""
    mids = (gaps[:, 0] + gaps[:, 1]) / 2 if len(gaps) else np.zeros(0)
    order = np.argsort(mids)
    smid = mids[order]
    owner = np.full(len(gaps), -1)
    best = np.full(len(gaps), np.inf)
    for k, (name, s, e) in enumerate(annotations):
        if name == WINDOW:
            continue
        lo, hi = np.searchsorted(smid, [s, e])
        idx = order[lo:hi]
        take = idx[(e - s) < best[idx]]
        owner[take] = k
        best[take] = e - s
    out: dict = {}
    lengths = gaps[:, 1] - gaps[:, 0] if len(gaps) else np.zeros(0)
    for k, ns in zip(owner, lengths):
        name = annotations[k][0] if k >= 0 else NO_ANNOTATION
        out[name] = out.get(name, 0.0) + ns
    return out


def reduce_planes(planes, chips: int) -> TraceSummary:
    """Reduce the planes of one trace over the first ``chips`` TPUs."""
    planes = list(planes)
    ann = _annotations(planes)
    windows = [(s, e) for n, s, e in ann if n == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW} annotation on "
                         f"{HOST_PLANE}, found {len(windows)}")
    w0, w1 = windows[0]
    devs = sorted((int(m.group(1)), p) for p in planes
                  if (m := DEVICE_PLANE.match(p.name)))
    if len(devs) < chips:
        raise ValueError(f"trace has {len(devs)} TPU planes, the cell "
                         f"uses {chips}: "
                         f"{[p.name for p in planes]}")
    busy, coll, ops, gaps, text = [], [], [], [], {}
    for _id, p in devs[:chips]:
        lines = {ln.name: ln for ln in p.lines}
        if OPS_LINE not in lines:
            raise ValueError(f"{p.name} has no {OPS_LINE!r} line: "
                             f"{sorted(lines)}")
        events = [e for e in lines[OPS_LINE].events
                  if e.start_ns < w1 and e.start_ns + e.duration_ns > w0]
        iv = clip(union(_intervals(events)), w0, w1)
        busy.append(float(np.sum(iv[:, 1] - iv[:, 0])) * 1e-9
                    if len(iv) else 0.0)
        per_op: dict = {}
        cns = 0.0
        for name, s, ns in self_times(events):
            if w0 <= s < w1:
                short = op_name(name)
                per_op[short] = per_op.get(short, 0.0) + ns
                text.setdefault(short, name)
                if is_collective(name):
                    cns += ns
        ops.append(per_op)
        coll.append(cns * 1e-9)
        edges = np.concatenate([[w0], iv.reshape(-1), [w1]])
        g = edges.reshape(-1, 2)
        gaps.append(name_gaps(g[g[:, 1] > g[:, 0]], ann))
    n = len(ops)

    def mean_of(dicts):
        keys = set().union(*dicts)
        return {k: sum(d.get(k, 0.0) for d in dicts) * 1e-9 / n
                for k in keys}

    return TraceSummary(window_s=(w1 - w0) * 1e-9, busy_s=busy,
                        collective_s=coll, op_s=mean_of(ops),
                        gap_s=mean_of(gaps), op_text=text)
