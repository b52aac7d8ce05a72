"""The program's own spans and its compile counter.

A small decode deployment serves a few requests from a cold boot to its
cool-down under ``jax.profiler.trace``; the trace is read back with
``ProfileData`` and its host plane checked against ``SPANS``, the spans'
nesting, the ``req`` link between a request's submit and its admit, and the
scheduler's own state at each step. No timing is asserted.
"""
import dataclasses
import time

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData

from repro.core import FunctionSpec, Gateway
from repro.core.decode import DecodeConfig
from repro.core.metrics import BOOT_SPAN_PREFIX, SPANS, compile_stats, compile_times, now

BUDGETS = [5, 9, 3, 7]


@dataclasses.dataclass
class _Span:
    line: int                      # index of the host plane's line: one thread
    name: str
    start_ns: float
    end_ns: float
    stats: dict

    def holds(self, other: "_Span") -> bool:
        return (self.line == other.line and self.start_ns <= other.start_ns
                and other.end_ns <= self.end_ns)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Spans of a traced run, the scheduler's context per step, its summary."""
    gw = Gateway(n_hosts=1, slots_per_host=1, mode="cold", hedging=False,
                 decode=DecodeConfig(slots=3, page_size=8, cool_after_s=0.15))
    spec = FunctionSpec(arch="llama3.2-3b", batch_size=1, prompt_len=8,
                        decode_steps=12)
    log_dir = tmp_path_factory.mktemp("trace")
    try:
        gw.deploy(spec)
        dep, dec = gw.deployments[spec.name], gw.decoders[spec.name]
        # the scheduler's own state as each step program is called: the keys
        # each live row attends over, keyed by the step's number
        ctx = {}
        step = dec.bundle.step

        def recording_step(*args):
            ctx[dec.steps] = sum(a.pos + 1 for a in dec._slots if a is not None)
            return step(*args)

        dec.bundle = dataclasses.replace(dec.bundle, step=recording_step)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        with jax.profiler.trace(str(log_dir), profiler_options=opts):
            futs = [gw.invoke_decode_async(
                spec.name, dep.example_tokens(seed=i)[:1], max_new=b,
                label=f"req{i}") for i, b in enumerate(BUDGETS)]
            for f in futs:
                f.result(300)
            t_end = time.monotonic() + 30
            while dec.cooldowns < 1 and time.monotonic() < t_end:
                time.sleep(0.05)
            assert dec.cooldowns >= 1, "the decode tier did not cool"
        summary = gw.decode_summary(spec.name)
    finally:
        gw.shutdown()
    path = next(log_dir.rglob("*.xplane.pb"))
    host = next(p for p in ProfileData.from_file(str(path)).planes
                if p.name == "/host:CPU")
    spans = [_Span(i, e.name, e.start_ns, e.end_ns, dict(e.stats))
             for i, line in enumerate(host.lines) for e in line.events
             if e.name.startswith(("decode.", BOOT_SPAN_PREFIX))]
    return spans, ctx, summary


def _named(spans, name):
    return [s for s in spans if s.name == name]


def test_every_span_the_run_reaches_is_on_the_host_plane_and_documented(traced):
    spans, _, _ = traced
    names = {s.name for s in spans}
    # the run boots, admits, steps, idles and cools: it reaches every span
    assert set(SPANS) <= names
    assert any(n.startswith(BOOT_SPAN_PREFIX) for n in names)
    assert {n for n in names if n.startswith("decode.")} <= set(SPANS)


def test_step_children_nest_inside_the_step_on_the_loop_thread(traced):
    spans, _, _ = traced
    steps = _named(spans, "decode.step")
    assert steps and len({s.line for s in steps}) == 1
    for child in ("decode.step.inputs", "decode.step.run",
                  "decode.step.pull", "decode.step.sample"):
        for c in _named(spans, child):
            assert any(s.holds(c) for s in steps), child
    admits = _named(spans, "decode.admit")
    for child in ("decode.admit.run", "decode.admit.pull", "decode.boot"):
        for c in _named(spans, child):
            assert any(a.holds(c) for a in admits), child
    # the boot's program track runs on the loop thread, inside its admit
    boot = _named(spans, "decode.boot")[0]
    assert any(boot.holds(s) for s in spans if s.name.startswith(BOOT_SPAN_PREFIX))


def test_a_requests_submit_and_admit_carry_the_same_req(traced):
    spans, _, summary = traced
    submits = _named(spans, "decode.submit")
    admits = _named(spans, "decode.admit")
    want = {f"req{i}" for i in range(len(BUDGETS))}
    assert {s.stats["req"] for s in submits} == want
    assert {a.stats["req"] for a in admits} == want
    # submits run on the callers' thread, admits on the loop's
    assert not {s.line for s in submits} & {a.line for a in admits}
    assert {a.stats["slot"] for a in admits} <= {0, 1, 2}
    waits = [a.stats["queue_wait_us"] for a in admits]
    assert summary["queue_delay_mean_s"] == pytest.approx(
        sum(waits) / len(waits) * 1e-6, abs=1e-6)


def test_ctx_tokens_is_the_schedulers_own_count_of_attended_keys(traced):
    spans, ctx, summary = traced
    steps = _named(spans, "decode.step")
    assert len(steps) == len(ctx) == summary["steps"]
    for s in steps:
        assert s.stats["ctx_tokens"] == ctx[s.stats["step_num"]]
        assert s.stats["_r"] == 1          # the profiler's step marker
        assert 1 <= s.stats["rows"] <= 3


def test_compile_counter_counts_a_new_shape_and_not_a_repeat():
    f = jax.jit(lambda x: jnp.tanh(x) * 3)
    x, y = jnp.ones((13, 7)), jnp.ones((7, 13))
    f(x).block_until_ready()
    before = compile_stats()
    f(x).block_until_ready()
    assert compile_stats()["compiles"] == before["compiles"]
    f(y).block_until_ready()
    after = compile_stats()
    assert after["compiles"] > before["compiles"]
    assert after["compile_s"] > before["compile_s"]


def test_compile_times_place_each_compile_on_the_programs_clock():
    f = jax.jit(lambda x: jnp.cos(x) - 1)
    f(jnp.ones((5, 11))).block_until_ready()
    t0 = now()
    n0 = len([t for t in compile_times() if t >= t0])
    f(jnp.ones((5, 11))).block_until_ready()
    assert len([t for t in compile_times() if t >= t0]) == n0 == 0
    f(jnp.ones((11, 5))).block_until_ready()
    t1 = now()
    fresh = [t for t in compile_times() if t >= t0]
    assert len(fresh) >= 1 and all(t0 <= t <= t1 for t in fresh)
