"""95th percentile of time to first token, from each request's due time.

Over every request due in the window: ``t_ttfr`` (stamped once the admit's
first token is on the host) minus the time the schedule made it due, so a
stall that delays later sends is counted. A failed request counts as missing
the tail: where that reaches the 95th percentile there is no reading.
"""
import numpy as np


def read(run):
    vals = [(s.timeline.t_ttfr - s.due) * 1e3
            if s.error is None and s.timeline is not None else np.inf
            for s in run.window]
    if not vals:
        return None
    p = float(np.percentile(vals, 95))
    return p if np.isfinite(p) else None
