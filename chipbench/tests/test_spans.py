"""The span reductions, on hand-made spans and gaps and on a trace recorded
on the CPU."""
import re
import threading

import pytest

from chipbench import spans as sp
from chipbench import trace_reduce as tr


def _span(name, start, end, thread=0, **stats):
    return sp.Span(name, start, end, thread, stats)


# a step on the loop's thread (1): inputs, run, pull, sample; an admit with
# its run and pull before it; a boot stage on another track's thread (2); a
# submit on a caller's thread (0), which takes no idle time
SPANS = [_span("decode.admit", 0.0, 3.0, 1, req="a", slot=0, queue_wait_us=5),
         _span("decode.admit.run", 0.5, 2.5, 1), _span("decode.admit.pull", 2.5, 3.0, 1),
         _span("decode.step", 3.0, 10.0, 1, step_num=7, rows=2, ctx_tokens=9),
         _span("decode.step.inputs", 3.0, 4.0, 1), _span("decode.step.run", 4.0, 8.0, 1),
         _span("decode.step.pull", 8.0, 9.0, 1), _span("decode.step.sample", 9.0, 10.0, 1),
         _span("boot.restore_delta", 11.0, 12.0, 2),
         _span("decode.submit", 0.0, 20.0, 0, req="a")]


def test_with_children_finds_the_spans_inside_each_on_its_thread():
    (step, kids), = sp.with_children(SPANS, "decode.step")
    assert step.stats["ctx_tokens"] == 9
    assert [k.name for k in kids] == ["decode.step.inputs", "decode.step.run",
                                      "decode.step.pull", "decode.step.sample"]
    (admit, kids), = sp.with_children(SPANS, "decode.admit")
    assert [k.name for k in kids] == ["decode.admit.run", "decode.admit.pull"]
    (submit, kids), = sp.with_children(SPANS, "decode.submit")
    assert kids == []                          # the others run on other threads


def test_step_host_and_admit_wall_read_the_spans():
    spans = [_span("decode.step", 1.000, 1.090, 1), _span("decode.step.run", 1.004, 1.085, 1),
             _span("decode.step", 2.000, 2.080, 1), _span("decode.step.run", 2.002, 2.078, 1),
             _span("decode.admit", 3.000, 3.025, 1), _span("decode.admit.run", 3.0, 3.024, 1),
             _span("decode.admit", 4.000, 9.000, 1), _span("decode.boot", 4.001, 8.9, 1),
             _span("decode.step.run", 5.0, 6.0, 2)]   # another thread's: no step holds it
    assert sp.step_host_ms(spans) == pytest.approx((9.0 + 4.0) / 2)
    assert sp.admit_wall_ms(spans) == pytest.approx(25.0)    # the admit that booted left out
    assert sp.step_host_ms([]) is None and sp.admit_wall_ms([]) is None


def test_idle_by_span_puts_each_gap_under_the_innermost_span():
    assert sp.innermost(SPANS[:3]) == [(0.0, 0.5, "decode.admit"),
                                       (0.5, 2.5, "decode.admit.run"),
                                       (2.5, 3.0, "decode.admit.pull")]
    gaps = [(0.2, 0.7), (2.6, 4.5), (8.0, 10.0), (10.5, 11.5), (13.0, 14.0)]
    got = dict(sp.idle_by_span(gaps, SPANS))
    assert got == pytest.approx({
        "decode.admit": 0.3, "decode.admit.run": 0.2, "decode.admit.pull": 0.4,
        "decode.step.inputs": 1.0, "decode.step.run": 0.5, "decode.step.pull": 1.0,
        "decode.step.sample": 1.0, "boot.restore_delta": 0.5, "outside_spans": 1.5})
    assert sum(got.values()) == pytest.approx(sum(b - a for a, b in gaps))
    assert sp.idle_by_span(gaps, []) == [["outside_spans", pytest.approx(6.4)]]


def test_the_device_clock_is_set_beside_the_spans_by_the_programs_they_wait_for():
    # each step program shows 0.5 ms before the host called it (the trace's
    # clocks disagree) and ends 1.0 ms before the host saw it end
    spans = [_span("decode.step", 0.0, 10.0, 1), _span("decode.step.run", 1.0, 8.0, 1),
             _span("decode.step.pull", 8.0, 9.0, 1), _span("decode.step.sample", 9.0, 10.0, 1),
             _span("decode.step", 10.0, 20.0, 1), _span("decode.step.run", 11.0, 18.0, 1)]
    modules = [("jit_step(3)", 0.5, 7.0), ("jit_step(3)", 10.5, 17.0),
               ("jit_step(3)", 30.0, 37.0)]               # its span lies outside the trace
    assert sp.clock_offset(spans, modules) == pytest.approx(0.5)
    # a trace that meets the bounds is left as it is; no pair, no offset
    assert sp.clock_offset(spans, [("jit_step(3)", 1.2, 7.5)]) == 0.0
    assert sp.clock_offset([], modules) == 0.0
    # bounds that cross: their middle
    assert sp.clock_offset(spans, [("jit_step(3)", 0.5, 7.0), ("jit_step(3)", 11.5, 18.5)]) \
        == pytest.approx(0.0)
    # the gap 7.0-10.5 on the device's clock is 7.5-11.0 on the spans'
    gaps = [(a + 0.5, b + 0.5) for a, b in tr.idle_gaps(modules[:2])]
    assert dict(sp.idle_by_span(gaps, spans)) == pytest.approx({
        "decode.step.run": 0.5, "decode.step.pull": 1.0, "decode.step.sample": 1.0,
        "decode.step": 1.0, "outside_spans": 0.0})


def test_the_program_s_span_names_are_the_ones_the_reduction_keeps():
    metrics = pytest.importorskip("repro.core.metrics")
    assert all(n.startswith(sp.SPAN_PREFIXES) for n in metrics.SPANS)
    assert metrics.BOOT_SPAN_PREFIX in sp.SPAN_PREFIXES
    assert set(sp.CALLER_SPANS) <= set(metrics.SPANS)
    assert set(sp.WAIT_SPANS) <= set(metrics.SPANS)


def test_spans_read_from_a_trace_recorded_on_the_cpu(tmp_path):
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp
    import numpy as np

    @jax.jit
    def step(x):
        return jnp.tanh(x @ x)

    x = jnp.ones((256, 256))
    step(x).block_until_ready()

    def loop():
        for i in range(3):
            with jax.profiler.StepTraceAnnotation("decode.step", step_num=i, rows=1,
                                                  ctx_tokens=10 + i):
                with jax.profiler.TraceAnnotation("decode.step.run"):
                    y = step(x).block_until_ready()
                with jax.profiler.TraceAnnotation("decode.step.pull"):
                    np.asarray(y)

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("decode.submit", req="r1"):
        t = threading.Thread(target=loop)
        t.start()
        t.join()
    jax.profiler.stop_trace()
    path = tr.find_xplane(tmp_path)
    spans = sp.load_spans(path)
    steps = sp.with_children(spans, "decode.step")
    assert [p.stats["ctx_tokens"] for p, _ in steps] == [10, 11, 12]
    assert all({k.name for k in kids} == {"decode.step.run", "decode.step.pull"}
               for _, kids in steps)
    submit, = [s for s in spans if s.name == "decode.submit"]
    assert submit.stats["req"] == "r1" and submit.thread != steps[0][0].thread
    # on the CPU the device's operations run on the PjRt client's threads
    kw = dict(device_plane=re.compile(r"^/host:CPU$"), ops_line="tf_XLAPjRtCpuClient")
    got = sp.reduce(path, **kw)
    assert got["steps"] == 3 and got["step_host_ms"] > 0 and got["admit_wall_ms"] is None
    idle = dict(got["idle_by_span"])
    gaps = tr.idle_gaps(tr.load(path, **kw).ops["/host:CPU"])
    assert sum(idle.values()) == pytest.approx(sum(b - a for a, b in gaps))
    assert "decode.submit" not in idle
