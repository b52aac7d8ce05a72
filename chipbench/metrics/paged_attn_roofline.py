"""Share of the HBM roofline reached by the paged decode attention kernel.

The kernel is bandwidth-bound: per step it must move each live row's K and V
(``length`` tokens x kv heads x head size x 2 B, both) plus q and the output,
in every layer (``chipbench.counts.paged_attn_bytes``, counted from the live
rows' lengths in the traced window, ``Run.per_step``). The least time is
those bytes over the chip's peak bandwidth; the share is that over the
kernel's device time per step in the trace.

The kernel is the step program's one Pallas call: an operation inside a
``jit_step`` execution whose instruction is a ``custom-call`` to
``tpu_custom_call``.
"""
from chipbench import counts

KERNEL = r"custom_call_target=\"tpu_custom_call\""


def read(run):
    tr = run.trace
    steps = tr.program_times("step") if tr else []
    kernel = tr.op_times_in("step", KERNEL) if tr else []
    per_step_bytes = run.per_step(counts.paged_attn_bytes) if tr else None
    if not steps or not kernel or per_step_bytes is None:
        return None
    per_step_s = sum(kernel) / len(steps)
    bw = counts.peaks(run.device_kind)["hbm_bytes_per_s"]
    return 100.0 * per_step_bytes / bw / per_step_s
