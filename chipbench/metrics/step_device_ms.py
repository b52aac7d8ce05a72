"""Mean device time of one execution of the step program (``jit_step``),
from the traced window."""


def read(run):
    t = run.trace.program_times("step") if run.trace else None
    return 1e3 * sum(t) / len(t) if t else None
