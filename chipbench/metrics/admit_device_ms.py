"""Mean device time of one execution of the admit program (``jit_admit``),
from the traced window."""


def read(run):
    t = run.trace.program_times("admit") if run.trace else None
    return 1e3 * sum(t) / len(t) if t else None
