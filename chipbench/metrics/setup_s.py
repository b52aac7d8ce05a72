"""Seconds from process start to the window's start: import, deploy (and its
compiles), the warm-up boot and requests, and the lead-in traffic."""


def read(run):
    return run.setup_s
