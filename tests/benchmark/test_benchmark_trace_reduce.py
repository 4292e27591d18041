"""The reduction from a profiler trace to busy time, idle share,
collective time, top ops and named idle gaps: on planes synthesised in
the shape ``jax.profiler.ProfileData`` gives, and on the host side of a
trace recorded on the CPU."""

from types import SimpleNamespace as NS

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import TraceAnnotation

from benchmark import trace_reduce as tr


def ev(name, start, dur):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur))


def plane(name, **lines):
    return NS(name=name, lines=[NS(name=k.replace("_", " "), events=v)
                                for k, v in lines.items()])


HOST = plane("/host:CPU", python=[
    ev("bench.window", 1000, 10000),
    ev("bench.rhs_upload", 1000, 1000),
    ev("bench.solve_call", 2000, 9000),
])


def device(i, ops):
    return plane(f"/device:TPU:{i}", XLA_Ops=ops,
                 XLA_Modules=[ev("jit_step(1)", 0, 20000)])


def test_busy_idle_collectives_and_gaps():
    ops0 = [ev("%fusion.1 = f32[8] fusion(f32[8] %all-reduce.2)", 2500, 2000),
            ev("%while.3 = (f32[8]) while(f32[8] %a)", 5000, 4000),
            ev("%all-reduce.4 = f32[] all-reduce(f32[] %d)", 5500, 500),
            ev("%collective-permute-start.1 = (f32[8]) "
               "collective-permute-start(f32[8] %x)", 6500, 1000),
            ev("%late.9 = f32[] add(f32[] %a)", 12000, 100)]   # after window
    ops1 = [ev("%fusion.1 = f32[8] fusion(f32[8] %b)", 500, 3000)]  # clipped
    s = tr.reduce_planes([HOST, device(0, ops0), device(1, ops1),
                          device(2, [])], chips=2)
    assert s.window_s == pytest.approx(10000e-9)
    # chip 0: [2500, 4500) + [5000, 9000); chip 1: [1000, 3500)
    assert s.busy_s == pytest.approx([6000e-9, 2500e-9])
    assert s.idle_share == pytest.approx(1 - 4250 / 10000)
    # the fusion that reads an all-reduce's result is not a collective
    assert s.collective_s == pytest.approx([1500e-9, 0.0])
    # self times: the while loop less the two ops nested in it; an op
    # counts in the window it starts in (chip 1's fusion began before)
    assert s.op_s["while.3"] == pytest.approx(2500e-9 / 2)
    assert s.op_s["fusion.1"] == pytest.approx(2000e-9 / 2)
    assert "late.9" not in s.op_s
    # chip 0 gaps: [1000,2500) spans both annotations, its midpoint 1750
    # is in the upload; [4500,5000) and [9000,11000) in the solve call.
    # chip 1 gap [3500, 11000) in the solve call.
    assert s.gap_s["bench.rhs_upload"] == pytest.approx(1500e-9 / 2)
    assert s.gap_s["bench.solve_call"] == pytest.approx(
        (500 + 2000 + 7500) * 1e-9 / 2)
    b = s.breakdown(top=1)
    assert b["device_ops"] == [["while.3", pytest.approx(1250e-9)]]
    assert b["idle_gaps"][0][0] == "bench.solve_call"


def test_gap_with_no_call_open_and_the_shortest_annotation_wins():
    host = plane("/host:CPU", t1=[ev("bench.window", 0, 1200),
                                  ev("bench.response_wait", 100, 800)],
                 t2=[ev("bench.submit", 140, 100)])
    ops = [ev("%a.1 = f32[] add()", 300, 100), ev("%b.2 = f32[] add()", 950,
                                                 50)]
    s = tr.reduce_planes([host, device(0, ops)], chips=1)
    # gaps [0,300) (midpoint in both, the submit is shorter), [400,950)
    # in the wait, [1000,1200) with no call open
    assert s.gap_s["bench.submit"] == pytest.approx(300e-9)
    assert s.gap_s["bench.response_wait"] == pytest.approx(550e-9)
    assert s.gap_s[tr.NO_ANNOTATION] == pytest.approx(200e-9)


@pytest.mark.parametrize("text,name,op,coll", [
    ("%copy.4 = bf16[7,8]{1,0:T(8,128)(2,1)} fusion(bf16[1] %b), "
     "kind=kLoop", "copy.4", "fusion", False),
    ("%dia.8 = (f32[8]{0:T(8,128)}, f32[1,1]) custom-call(f32[8] %x)",
     "dia.8", "custom-call", False),
    ("%all-gather.2 = f32[32] all-gather(f32[8] %x)", "all-gather.2",
     "all-gather", True),
    ("%collective-permute-done = f32[8] collective-permute-done(%s)",
     "collective-permute-done", "collective-permute-done", True),
    ("reduce-scatter.1", "reduce-scatter.1", "reduce-scatter", True),
])
def test_op_names_and_opcodes(text, name, op, coll):
    assert tr.op_name(text) == name
    assert tr.opcode(text) == op
    assert tr.is_collective(text) is coll


def test_errors_name_what_is_missing():
    with pytest.raises(ValueError, match="bench.window"):
        tr.reduce_planes([device(0, [])], chips=1)
    with pytest.raises(ValueError, match="TPU planes"):
        tr.reduce_planes([HOST, device(0, [])], chips=4)
    with pytest.raises(ValueError, match="XLA Ops"):
        tr.reduce_planes([HOST, plane("/device:TPU:0", Steps=[])], chips=1)


def test_host_annotations_of_a_cpu_trace(tmp_path):
    """A trace recorded here carries the benchmark's annotations on the
    host plane, where the reduction looks for them."""
    f = jax.jit(lambda x: jnp.sin(x) * 2)
    x = jnp.ones((64,))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with TraceAnnotation("bench.window"):
            with TraceAnnotation("bench.solve_call"):
                f(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    planes = list(tr.load_planes(tr.find_xplane(str(tmp_path))))
    names = [a[0] for a in tr._annotations(planes)]
    assert names.count("bench.window") == 1
    assert names.count("bench.solve_call") == 1
    win = next(a for a in tr._annotations(planes) if a[0] == "bench.window")
    call = next(a for a in tr._annotations(planes)
                if a[0] == "bench.solve_call")
    assert win[1] <= call[1] <= call[2] <= win[2]
