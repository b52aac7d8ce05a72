"""Compiles inside the window: the times the program logged for its compiles
(``repro.core.metrics.compile_times``, on the program's clock) that fall
between the window's two ends. None where the program keeps no such log."""


def read(run):
    try:
        from repro.core.metrics import compile_times
    except ImportError:              # a program without the log: no reading
        return None
    return float(sum(run.t0 <= t < run.t0 + run.seconds for t in compile_times()))
