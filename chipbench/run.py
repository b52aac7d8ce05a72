#!/usr/bin/env python3
"""The chip benchmark: one run of one cell of ``BENCHMARK.json``.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout, on a host whose chips the cell asks for. With
``--trace 0`` the result's metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics (a profiler window inside the run). The
last line of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` with ``--trace 1``),
with the numbers compared beside their limits last, under ``checks``; the
same numbers are the last lines of standard error.

Two further modes, for defining the cells and not run by a check:

- ``--control 1``: the int8 reference's picks take the served tokens' place
  in the comparison, on the same sample, so a sound run comes out not
  correct; its ``logit_gap`` is the upper reading of the correctness limit,
  and ``program_logit_gap`` the served tokens' own;
- ``--sweep r1,r2,...``: offer each rate of a Poisson mix in turn to one
  resident deployment for ``--seconds`` each, and print tails and throughput
  per rate (the knee sweep).

Everything runs in this one process: a chip belongs to one process at a
time. The run exits non-zero, printing no result, where JAX finds no
accelerator or fewer chips than the cell asks for, or where the checkout
holds no program to measure.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")


def _args(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    ap.add_argument("--sweep", default=None,
                    help="comma-separated offered rates (requests/s)")
    return ap.parse_args(argv)


def _fail(msg: str) -> int:
    print(f"chipbench: {msg}", file=sys.stderr)
    return 1


def main(argv=None) -> int:
    args = _args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        return _fail(f"no program under {ROOT / 'src'}: run from a checkout")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

    from chipbench import harness

    cell = harness.load_cell(args.workload)
    from repro.core.compile_cache import use_checkout_compile_cache
    cache_dir = use_checkout_compile_cache()
    import jax
    # every program, small ones too, from the persistent cache after a cell's first run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    devs = jax.devices()
    if devs[0].platform not in ("tpu", "gpu"):
        return _fail(f"JAX found no accelerator (platform {devs[0].platform!r})")
    if len(devs) < cell.chips:
        return _fail(f"{cell.name} needs {cell.chips} chips, JAX found {len(devs)}")
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    print(f"chipbench: {cell.name} seed={args.seed} seconds={args.seconds} "
          f"trace={args.trace} device={json.dumps(device)} compile_cache={cache_dir}",
          flush=True)

    rates = [float(r) for r in args.sweep.split(",")] if args.sweep else None
    out = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace), T_PROCESS,
                           control=bool(args.control), sweep_rates=rates)
    if rates is not None:
        print(json.dumps(out))
        return 0
    print(json.dumps(result_line(cell, args, out, device)))
    return 0


def result_line(cell, args, out, device) -> dict:
    """The result's JSON object; the compared numbers also go to stderr."""
    from chipbench import harness

    run, check = out["run"], out["check"]
    metrics = {}
    for entry in harness.metric_entries(cell.name, bool(args.trace)):
        value = harness.load_reader(entry["name"])(run)
        if value is not None:
            metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}
    late = [s.submitted - s.due for s in run.window]
    if late:
        print(f"chipbench: generator late by max {max(late) * 1e3:.3f} ms, "
              f"mean {sum(late) / len(late) * 1e3:.3f} ms over {len(late)} sends",
              flush=True)
    device = dict(device, memory_peak_bytes=out["peak"])
    line = {"correct": check["correct"], "attempted": len(run.window),
            "failed": check["checks"]["failed_requests"]["value"],
            "metrics": metrics, "device": device}
    if run.trace is not None:
        device.update(busy_s=run.trace.busy_s, window_s=run.trace.window_s)
        line["breakdown"] = run.trace.breakdown
    if check["program_logit_gap"] is not None:
        line["program_logit_gap"] = check["program_logit_gap"]
    line["checks"] = check["checks"]
    for name, c in check["checks"].items():
        print(f"check {name}: {c['value']} limit {c['limit']}", file=sys.stderr)
    return line


if __name__ == "__main__":
    sys.exit(main())
