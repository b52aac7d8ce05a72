"""Median wall time of the decode tier's boots in the window
(``Timeline.t_boot_wall`` of the request that triggered each)."""
import numpy as np


def read(run):
    vals = [s.timeline.t_boot_wall for s in run.window
            if s.timeline is not None and s.timeline.t_boot_wall > 0]
    return float(np.median(vals)) if vals else None
