"""Host cost of the program's spans (``repro.core.metrics.span``, ``step_span``).

    PYTHONPATH=src python benchmarks/bench_spans.py [--n 200000]

Times an empty ``with`` block under each kind of span the decode loop opens:
a bare one, one with the admit's three stats, and the step span with its
three. ``off``: no profiler trace is being recorded, the cost every decode
step pays; ``on``: inside a profiler trace (Python calls unrecorded, as the
chip benchmark traces), the cost a traced run pays. Prints one JSON line of
nanoseconds per span, with the platform it ran on.
"""
from __future__ import annotations

import argparse
import json
import platform
import tempfile
import time

import jax

from repro.core.metrics import span, step_span

KINDS = {
    "bare": lambda i: span("decode.step.pull"),
    "three_stats": lambda i: span("decode.admit", req="chipbench:123", slot=3,
                                  queue_wait_us=1234),
    "step": lambda i: step_span("decode.step", step_num=i, rows=18, ctx_tokens=12345),
}


def ns_per_span(make, n: int) -> float:
    t0 = time.perf_counter()
    for i in range(n):
        with make(i):
            pass
    return (time.perf_counter() - t0) / n * 1e9


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--n", type=int, default=200_000)
    n = ap.parse_args().n
    out = {"off": {k: ns_per_span(m, n) for k, m in KINDS.items()}}
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            # fewer spans: each recorded one is kept until the trace is written
            out["on"] = {k: ns_per_span(m, n // 10) for k, m in KINDS.items()}
        finally:
            jax.profiler.stop_trace()
    out.update(n=n, jax=jax.__version__, backend=jax.default_backend(),
               cpu=platform.processor() or platform.machine())
    print(json.dumps(out))


if __name__ == "__main__":
    main()
