"""Layer names inside the program (acg_tpu/obs/trace.py): an ``acg.*``
scope on every op of the compiled solve loops, the ``acg.call`` span and
its stages around every solver entry, and a span timeline that stays
bounded in a long-running service."""

import re

import jax
import numpy as np
import pytest

from acg_tpu.config import SolverOptions
from acg_tpu.obs.hlo import parse_hlo
from acg_tpu.obs.trace import SpanTracer, program_spans, span
from acg_tpu.sparse import poisson3d_7pt

# loop bookkeeping: the carry tuple, its elements and literal constants
BOOKKEEPING = {"parameter", "constant", "tuple", "get-tuple-element"}
CALL_STAGES = ["acg.call.prepare", "acg.call.dispatch",
               "acg.call.device_wait", "acg.call.fetch",
               "acg.call.copyback", "acg.call.finish"]


def _op_names(comps, comp, name, seen=None):
    """The op_name strings of an instruction and of every instruction in
    the computations it calls (a fusion runs as one op: a scope inside
    it is a scope on it)."""
    seen = set() if seen is None else seen
    _, _, op_name, called = comps[comp][name][:4]
    out = {op_name} if op_name else set()
    for c in called:
        if c in comps and c not in seen:
            seen.add(c)
            for inner in comps[c]:
                if not inner.startswith("__"):
                    out |= _op_names(comps, c, inner, seen)
    return out


def _is_fill(comps, comp, name) -> bool:
    """A compiler-materialized constant: a copy the compiler inserted
    (no metadata at all), or a fusion that only broadcasts a literal."""
    opcode, _, op_name, called = comps[comp][name][:4]
    if opcode == "copy" and not op_name:
        return True
    return opcode == "fusion" and all(
        v[0] in ("parameter", "constant", "broadcast")
        for c in called for k, v in comps.get(c, {}).items()
        if not k.startswith("__"))


def _layers_of_while_bodies(txt: str):
    """(layers seen, unscoped ops) over every while body of a compiled
    program: a layer is the innermost ``acg.*`` component of an op's
    name path."""
    comps = parse_hlo(txt)
    bodies = set(re.findall(r"body=(%[\w.\-]+)", txt))
    assert bodies, "no while loop in the compiled program"
    layers, unscoped = set(), []
    for body in bodies:
        for name, v in comps[body].items():
            if name.startswith("__") or v[0] in BOOKKEEPING \
                    or _is_fill(comps, body, name):
                continue
            scoped = [n for n in _op_names(comps, body, name) if "acg." in n]
            if not scoped:
                unscoped.append(f"{name} ({v[0]})")
            for n in scoped:
                layers.add([p for p in n.split("/")
                            if p.startswith("acg.")][-1])
    return layers, unscoped


def _compiled_text(case: str) -> str:
    A = poisson3d_7pt(16, dtype=np.float32)
    o = SolverOptions(maxits=5, residual_rtol=1e-6)
    kind, solver = case.split(":")
    if kind == "dist":
        from acg_tpu.solvers.cg_dist import build_sharded, lowered_step

        ss = build_sharded(A, nparts=4, dtype=np.float32)
        return lowered_step(ss, options=o, solver=solver).compile().as_text()
    from acg_tpu.solvers.cg import lowered_step

    b = np.ones(A.nrows, np.float32)
    return lowered_step(A, b, options=o, dtype=np.float32,
                        solver=solver).compile().as_text()


@pytest.mark.parametrize("case, expect", [
    ("chip:cg", {"acg.spmv", "acg.blas1", "acg.check"}),
    ("chip:cg-pipelined", {"acg.spmv", "acg.blas1", "acg.check"}),
    ("dist:cg", {"acg.spmv.local", "acg.spmv.iface", "acg.blas1",
                 "acg.check", "acg.allreduce", "acg.halo.pack",
                 "acg.halo.exchange", "acg.halo.unpack"}),
    ("dist:cg-pipelined", {"acg.spmv.local", "acg.spmv.iface",
                           "acg.blas1", "acg.check", "acg.allreduce",
                           "acg.halo.pack", "acg.halo.exchange",
                           "acg.halo.unpack"}),
])
def test_every_loop_op_carries_a_layer_scope(case, expect):
    """16³ Poisson, the classic and pipelined loops on one device and on
    a 4-shard CPU mesh: every op of the compiled while body other than
    loop bookkeeping carries an ``acg.*`` scope, and each layer the loop
    has is named."""
    layers, unscoped = _layers_of_while_bodies(_compiled_text(case))
    assert not unscoped, f"{case}: ops with no acg.* scope: {unscoped}"
    assert expect <= layers, f"{case}: missing {expect - layers}"


def _host_events(tmp_path):
    from jax.profiler import ProfileData

    path = next(tmp_path.rglob("*.xplane.pb"))
    for p in ProfileData.from_file(str(path)).planes:
        if p.name != "/host:CPU":
            continue
        for line in p.lines:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for e in line.events if e.name.startswith("acg.call")]
            if evs:
                return evs
    raise AssertionError("no acg.call events on the host plane")


def test_solve_call_stages_nest_in_order(tmp_path):
    """A CPU solve under the profiler: one ``acg.call`` span on the host
    plane with its six stages inside it, in order."""
    from acg_tpu.solvers.cg import build_device_operator, cg

    A = poisson3d_7pt(8, dtype=np.float32)
    dev = build_device_operator(A, dtype=np.float32)
    b = np.ones(A.nrows, np.float32)
    o = SolverOptions(maxits=200, residual_rtol=1e-5)
    cg(dev, b, options=o)                       # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        res = cg(dev, b, options=o)
    finally:
        jax.profiler.stop_trace()
    assert res.converged
    evs = sorted(_host_events(tmp_path), key=lambda e: e[1])
    calls = [e for e in evs if e[0] == "acg.call"]
    assert len(calls) == 1
    _, c0, c1 = calls[0]
    stages = [e for e in evs if e[0] != "acg.call"]
    assert [e[0] for e in stages] == CALL_STAGES
    for _, s0, s1 in stages:
        assert c0 <= s0 <= s1 <= c1
    for (_, _, end), (_, start, _) in zip(stages, stages[1:]):
        assert end <= start


def test_program_spans_record_every_call_stage():
    """The process ring holds what the profiler would: each solve's
    ``acg.call`` and its stages, on the calling thread."""
    from acg_tpu.solvers.cg import build_device_operator, cg
    import threading

    A = poisson3d_7pt(8, dtype=np.float32)
    dev = build_device_operator(A, dtype=np.float32)
    b = np.ones(A.nrows, np.float32)
    before = len(program_spans())
    cg(dev, b, options=SolverOptions(maxits=200, residual_rtol=1e-5))
    new = program_spans()[before:]
    me = threading.get_ident()
    names = [n for n, _s, _e, t in new if t == me]
    assert names[-1] == "acg.call"
    assert [n for n in names if n in CALL_STAGES] == CALL_STAGES
    call = new[-1]
    for n, s, e, _t in new:
        if n in CALL_STAGES:
            assert call[1] <= s <= e <= call[2]


def test_span_helper_feeds_a_tracer_and_the_ring():
    tr = SpanTracer()
    with span("acg.serve.stack", tracer=tr) as sp:
        pass
    assert sp.name == "acg.serve.stack" and tr.count("acg.serve.stack") == 1
    assert program_spans()[-1][0] == "acg.serve.stack"
    with span("acg.serve.demux", n=4) as none:
        pass
    assert none is None and tr.count("acg.serve.demux") == 0


def test_tracer_keeps_setup_spans_and_a_bounded_ring():
    """Totals and counts run over every span; the set-up spans stay;
    of the rest only the last ``KEEP``."""
    tr = SpanTracer()
    with tr.span("operator-build"):
        pass
    with tr.span("compile"):
        pass
    n = SpanTracer.KEEP + 50
    for _ in range(n):
        with tr.span("solve"):
            pass
    assert tr.count("solve") == n and tr.total("solve") > 0
    assert tr.count("compile") == 1
    names = [s.name for s in tr.spans]
    assert names[:2] == ["operator-build", "compile"]
    assert names[2:] == ["solve"] * SpanTracer.KEEP
    m0 = tr.mark()
    with tr.span("solve"):
        pass
    one = tr.as_dicts(since=m0, until=tr.mark())
    assert [d["name"] for d in one] == ["operator-build", "compile",
                                        "solve"]
    assert [d["name"] for d in tr.as_dicts(since=0, until=0)] == [
        "operator-build", "compile"]


def test_response_phases_stay_flat_across_requests():
    """200 served requests: each response's audit carries the session's
    set-up spans and its own dispatch's spans, so its length does not
    grow with the requests served before it."""
    from acg_tpu.serve.service import SolverService
    from acg_tpu.serve.session import Session
    from acg_tpu.sparse import poisson2d_5pt

    A = poisson2d_5pt(8)
    s = Session(A, dtype=np.float64,
                options=SolverOptions(maxits=200, residual_rtol=1e-8),
                share_prepared=False, prep_cache=None)
    svc = SolverService(s, max_batch=1)
    rng = np.random.default_rng(0)
    lengths = []
    try:
        for _ in range(200):
            r = svc.solve(rng.standard_normal(A.nrows), timeout=60.0)
            assert r.ok
            lengths.append(len(r.audit["phases"]))
    finally:
        svc.close()
        s.close()
    assert len(set(lengths[1:])) == 1, sorted(set(lengths))
    assert s.tracer.count("solve") == 200
    assert s.stats()["walls"]["solve"] > 0
