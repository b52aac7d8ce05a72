"""Benchmark harness — one bench per paper table/figure + the roofline report.

Prints ``name,us_per_call,derived`` CSV (one row per measurement):
  startup/*   paper Figs 1-3 (driver taxonomy x parallelism; loader comparison)
  table1/*    paper Table I (cold/warm/dispatch medians)
  e2e/*       paper Fig 4 + idle-residency integrals (cold-only vs warm-pool)
  images/*    paper Sec II-C (artifact sizes, build times)
  kernel/*    compute-layer micro-bench (CPU reference path)
  roofline/*  Sec Roofline terms from the multi-pod dry-run artifacts
"""
import os

os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from benchmarks import (  # noqa: E402
    bench_e2e, bench_images, bench_kernels, bench_startup, bench_table1, roofline,
)
from benchmarks.common import ROWS, emit  # noqa: E402


def main() -> None:
    from repro.core.compile_cache import use_checkout_compile_cache
    use_checkout_compile_cache()
    print("name,us_per_call,derived")

    bench_kernels.run()

    from repro.core import Gateway
    gw = Gateway(n_hosts=2, slots_per_host=3, mode="cold", hedging=False)
    try:
        bench_images.run(gw)
        bench_startup.run(gw)
        bench_table1.run(gw)
    finally:
        gw.shutdown()

    def make_gateway(**kw) -> Gateway:
        kw.setdefault("mode", "cold")
        kw.setdefault("n_hosts", 2)
        return Gateway(slots_per_host=3, hedging=False, **kw)

    bench_e2e.run(make_gateway)

    # roofline rows require dry-run artifacts (launch/dryrun.py --all)
    try:
        roofline.run(emit=emit)
    except Exception as e:  # pragma: no cover
        print(f"# roofline skipped: {e}")

    out = Path(__file__).resolve().parent.parent / "artifacts"
    out.mkdir(exist_ok=True)
    (out / "bench_rows.csv").write_text("name,us_per_call,derived\n" + "\n".join(ROWS) + "\n")
    print(f"# wrote {len(ROWS)} rows to artifacts/bench_rows.csv")


if __name__ == "__main__":
    main()
