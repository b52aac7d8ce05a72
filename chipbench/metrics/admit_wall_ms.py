"""Mean wall time of one admission in the window: from the decode loop taking
the request (``t_dispatch``) to its first token's logits being ready on the
device (``t_ttfr``), over the requests due in the window whose admission did
not boot the executor. An admission holds every resident request: no step
runs until it ends."""


def read(run):
    vals = [(s.timeline.t_ttfr - s.timeline.t_dispatch) * 1e3 for s in run.window
            if s.timeline is not None and s.timeline.t_ttfr and not s.timeline.t_boot_wall]
    return sum(vals) / len(vals) if vals else None
