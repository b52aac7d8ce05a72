"""The whole step's share of the chip's bf16 peak: the live rows' model
operations per step (``chipbench.counts.step_flops``: two per non-embedding
parameter, attention over each row's context, the head; ``Run.per_step``)
over the step program's device time."""
from chipbench import counts


def read(run):
    steps = run.trace.program_times("step") if run.trace else []
    flops = run.per_step(counts.step_flops) if steps else None
    if flops is None:
        return None
    step_s = sum(steps) / len(steps)
    return 100.0 * flops / step_s / counts.peaks(run.device_kind)["bf16_flops_per_s"]
