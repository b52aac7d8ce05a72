"""Median of admission wait: ``t_dispatch`` (the decode loop takes the
request for its admit) minus the request's due time, over the window."""
import numpy as np


def read(run):
    vals = [(s.timeline.t_dispatch - s.due) * 1e3 for s in run.window
            if s.timeline is not None]
    return float(np.median(vals)) if vals else None
