"""The trace reduction, on hand-made intervals and on a trace recorded on the CPU."""
import re

import pytest

from chipbench import trace_reduce as tr


def test_union_gaps_and_programs_on_hand_made_intervals():
    ops = [("%fusion.1 = f32[8] fusion(%p)", 0.0, 1.0),      # overlap: busy 0..2
           ("%fusion.2 = f32[8] fusion(%q)", 0.5, 2.0),
           ("%custom-call.7 = f32[8] custom-call(%r)", 3.0, 4.0),
           ("%fusion.1 = f32[8] fusion(%p)", 4.0, 4.5),
           ("%copy.3 = f32[8] copy(%s)", 10.0, 10.0)]        # zero length: not busy
    assert tr.merge(ops) == [(0.0, 2.0), (3.0, 4.5)]
    assert tr.busy_seconds(ops) == pytest.approx(3.5)
    assert tr.idle_gaps(ops) == [(2.0, 3.0)]
    assert tr.top_ops(ops)[0] == ["fusion", pytest.approx(3.0)]
    modules = [("jit_step(12)", 0.0, 2.0), ("jit_admit(3)", 3.0, 4.5),
               ("jit_step(12)", 5.0, 6.5), ("jit_step_other(1)", 7.0, 8.0)]
    assert tr.program_times(modules, "step") == [2.0, 1.5]
    assert tr.program_times(modules, "admit") == [1.5]
    host = [("PjitFunction(step)", 2.1, 2.9), ("np.asarray", 2.0, 2.2),
            ("before", 1.0, 5.0)]                             # starts outside: not named
    assert tr.label_gaps([(2.0, 3.0)], host) == [["PjitFunction(step)", 1.0]]
    assert tr.label_gaps([(5.0, 6.0)], host) == [["host_untraced", 1.0]]


def test_summarize_averages_planes_and_finds_ops_inside_a_program():
    kernel = '%closed_call.3 = bf16[2] custom-call(s32[2] %a), custom_call_target="tpu_custom_call"'
    t = tr.Trace(ops={"/device:TPU:0": [("%while.1 = (s32[]) while(%t)", 0.0, 1.0),
                                        (kernel, 0.2, 0.5), (kernel, 2.0, 2.5)],
                      "/device:TPU:1": [("%fusion.1 = f32[] fusion(%x)", 0.0, 3.0)]},
                 modules={"/device:TPU:0": [("jit_step(1)", 0.0, 1.0), ("jit_admit(2)", 1.9, 3.0)],
                          "/device:TPU:1": []},
                 host=[])
    s = tr.summarize(t, window_s=4.0)
    assert s.busy_s == pytest.approx((1.0 + 0.5 + 3.0) / 2)
    assert s.window_s == 4.0
    assert s.program_times("step") == [1.0]
    # the kernel call inside the admit is not the step's
    assert s.op_times_in("step", r"tpu_custom_call") == [pytest.approx(0.3)]
    assert s.breakdown["device_ops"][0] == ["while", 1.0]
    with pytest.raises(ValueError):
        tr.summarize(tr.Trace(ops={}, modules={}, host=[]), window_s=1.0)


def test_load_reads_a_trace_recorded_on_the_cpu(tmp_path):
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    @jax.jit
    def step(x):
        return jnp.tanh(x @ x)

    x = jnp.ones((256, 256))
    step(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    for _ in range(3):
        step(x).block_until_ready()
    jax.profiler.stop_trace()
    # on the CPU the device's operations run on the PjRt client's threads
    t = tr.load(tr.find_xplane(tmp_path), device_plane=re.compile(r"^/host:CPU$"),
                ops_line="tf_XLAPjRtCpuClient")
    ops = t.ops["/host:CPU"]
    assert any(name.startswith("dot") for name, _, _ in ops)
    span = max(e for _, _, e in t.host) - min(s for _, s, _ in t.host)
    assert 0 < tr.busy_seconds(ops) <= span
    assert any("PjitFunction(step)" in name for name, _, _ in t.host)
