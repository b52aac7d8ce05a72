"""Median host time of a boot's weight restore in the window: the chunk
store read (``fetch_chunks_store``) plus the delta assembly (``restore_delta``)."""
import numpy as np

STAGES = ("fetch_chunks_store", "restore_delta")


def read(run):
    vals = [sum(s.timeline.stage_s[k] for k in STAGES) for s in run.window
            if s.timeline is not None and all(k in s.timeline.stage_s for k in STAGES)]
    return float(np.median(vals)) if vals else None
