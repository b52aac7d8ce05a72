"""95th percentile of time per output token after the first.

For each request due in the window that got n >= 2 tokens:
``(t_done - t_ttfr) / (n - 1)``.
"""
import numpy as np


def read(run):
    vals = [(s.timeline.t_done - s.timeline.t_ttfr) / (len(s.tokens) - 1) * 1e3
            for s in run.window
            if s.error is None and s.timeline is not None and len(s.tokens) >= 2]
    return float(np.percentile(vals, 95)) if vals else None
