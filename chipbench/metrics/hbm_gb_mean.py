"""Time-average of the chip's ``bytes_in_use`` through the window, sampled
every 100 ms: the device memory a function owner pays for."""


def read(run):
    if not run.hbm_bytes:
        return None
    return sum(run.hbm_bytes) / len(run.hbm_bytes) / 1e9
