"""Paper Figs 1-3: executor startup latency per driver x parallelism.

Reproduces the measurement design of Sec III: N requests at fixed concurrency per
(runtime, parallelism) cell; boxplot stats with p1/p99 whiskers. Our runtime
taxonomy (process/fork/unikernel/paused/warm vs cold_jit_cached/cold_jit) maps to
the paper's (process/solo5-spt/IncludeOS vs gVisor/runc/Docker) — see DESIGN.md 4.2.

Also reproduces the 'interpreted language' observation (Sec III-E: Python+scipy
adds ~80 ms): pre-laid-out snapshot load vs generic checkpoint load.

New with the staged boot pipeline: a per-stage startup breakdown per driver
(``bootstage/*`` rows), mirroring the paper's container-layer decomposition —
including the overlap win (boot wall time < sum of stage times) that the
concurrent program/weights tracks buy.

New with streamed restore: a TTFR cell for the ``unikernel_stream`` driver —
time until the first response begins (AOT head output ready) vs the same
boot's honest full-restore wall (head wall + the background tail: remaining
chunk stream, tail program, fused program). Written to
``BENCH_7_startup.json`` at the repo root; ``--smoke`` gates the ratio >= 2x
(the whole point of first-use-ordered streaming is that TTFR stops scaling
with what the tail still has to move).
"""
import sys
import time
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parent.parent
if __name__ == "__main__":                       # standalone CLI bootstrap
    sys.path.insert(0, str(REPO_ROOT))
    sys.path.insert(0, str(REPO_ROOT / "src"))

import numpy as np

from benchmarks.common import bench_spec, emit, parallel_invokes

TTFR_GATE_RATIO = 2.0


def stage_breakdown(gw, label: str, drv: str) -> None:
    """Emit per-stage medians + the wall-vs-sum overlap for one (driver, label)."""
    tls = gw.recorder.timelines(label)
    if not tls:
        return
    stage_names = sorted({name for tl in tls for name in tl.stage_s})
    for name in stage_names:
        med = float(np.median([tl.stage_s.get(name, 0.0) for tl in tls]))
        emit(f"bootstage/{drv}/{name}", med * 1e6, f"n={len(tls)}")
    wall = float(np.median([tl.t_boot_wall for tl in tls]))
    ssum = float(np.median([sum(tl.stage_s.values()) for tl in tls]))
    emit(f"bootstage/{drv}/wall", wall * 1e6,
         f"stage_sum_us={ssum*1e6:.1f};overlap_saved_us={max(0.0, ssum-wall)*1e6:.1f}")


def _timeline_summary(tl) -> dict:
    return {
        "t_boot_wall_ms": tl.t_boot_wall * 1e3,
        "stage_sum_ms": sum(tl.stage_s.values()) * 1e3,
        "stage_ms": {k: v * 1e3 for k, v in tl.stage_s.items()},
        "ttfr_ms": tl.ttfr * 1e3,
    }


def streamed_ttfr_comparison(gw, out_path=None,
                             eager_label: str = "fig1:unikernel_cold:first"):
    """One streamed cold boot: TTFR vs the same boot's full-restore wall.

    TTFR (``Timeline.ttfr``) is boot-relative: first response begins minus
    boot begin. The full-restore wall is the SAME boot's ``t_boot_wall``
    after the background tail patched it — remaining chunk stream, tail
    sub-program, and the fused program (a "fully restored" streamed executor
    is eager-equivalent, so the wall is honest). Writes the comparison (plus
    the eager cell, when one was measured) to ``out_path`` and returns it.
    """
    import json

    spec = bench_spec()
    if spec.name not in gw.deployments:
        gw.deploy(spec)
    dep = gw.deployments[spec.name]

    label = "fig1:unikernel_stream_cold:first"
    gw.invoke(spec.name, driver="unikernel_stream", label=label)
    tl = gw.recorder.timelines(label)[-1]
    head_wall_s = tl.t_boot_wall
    if dep.split_ok:
        # the background completion patches the timeline in place — wait it
        # out so t_boot_wall is the full-restore wall, not just the head
        deadline = time.time() + 60
        while "deserialize_program_bg" not in tl.stage_s \
                and time.time() < deadline:
            time.sleep(0.01)
    ttfr_s = tl.ttfr
    full_wall_s = tl.t_boot_wall
    ratio = full_wall_s / ttfr_s if ttfr_s > 0 else 0.0

    emit("stream/ttfr", ttfr_s * 1e6,
         f"split={dep.split_ok};head_wall_us={head_wall_s*1e6:.1f}")
    emit("stream/full_restore_wall", full_wall_s * 1e6,
         f"ratio_vs_ttfr={ratio:.2f}x;gate>={TTFR_GATE_RATIO:.1f}x")
    stage_breakdown(gw, label, "unikernel_stream_cold")

    data = {
        "schema_version": 2,
        "bench": "startup_stream",
        # config-derived (never a timestamp): runs of the same spec compare,
        # anything else is apples-to-oranges and tools/check_bench.py skips it
        "run_id": f"startup-stream-{spec.name}",
        "seed": 0,                      # single deterministic spec, no RNG knob
        "spec": spec.name,
        "split_ok": bool(dep.split_ok),
        "first_use_order_len": len(dep.first_use_order),
        "streamed": dict(_timeline_summary(tl),
                         head_wall_ms=head_wall_s * 1e3,
                         t_first_ready_stamped=tl.t_first_ready > 0.0),
        "ratio_full_wall_over_ttfr": ratio,
        # wall-clock measurement on shared CI runners — tolerance is wide;
        # the hard floor is the gate below, not the regression delta
        "headline": {
            "ratio_full_wall_over_ttfr": {
                "value": ratio, "better": "higher", "rel_tol": 0.35},
        },
        "gate": {"threshold": TTFR_GATE_RATIO,
                 "passed": bool(ratio >= TTFR_GATE_RATIO)},
    }
    eager_tls = gw.recorder.timelines(eager_label)
    if eager_tls:
        data["eager"] = _timeline_summary(eager_tls[-1])
    if out_path is not None:
        Path(out_path).write_text(json.dumps(data, indent=2) + "\n")
        print(f"# wrote {out_path}", flush=True)
    return data


def run(gw, light_requests: int = 10, heavy_requests: int = 2) -> None:
    spec = bench_spec()
    if spec.name not in gw.deployments:
        gw.deploy(spec)
    dep = gw.deployments[spec.name]

    # the very FIRST boot anywhere: host tiers empty, so this is the true
    # cold path (global-store program fetch + full-delta weight restore) —
    # captured before any warmup can populate a tier
    label = "fig1:unikernel_cold:first"
    gw.invoke(spec.name, driver="unikernel", label=label)
    stage_breakdown(gw, label, "unikernel_cold")

    # streamed cold boot: TTFR vs the same boot's full-restore wall,
    # persisted for the report + CI gate. The eager first boot above parked
    # its artifacts in a host tier (and affinity routes repeats back to it),
    # so evict every tier first — the streamed cell must be tier-cold or the
    # ratio measures cache hits, not streaming
    for host in gw.cluster.hosts:
        for k in list(host.cache.programs.keys()):
            host.cache.programs.drop(k)
        for k in list(host.cache.snapshots.keys()):
            host.cache.snapshots.drop(k)
    streamed_ttfr_comparison(gw, out_path=REPO_ROOT / "BENCH_7_startup.json")

    # warm up donors/pools so 'fork'/'process'/'paused' measure steady state
    for drv in ("process", "fork", "paused", "warm", "unikernel"):
        gw.invoke(spec.name, driver=drv, label="warmup")

    light = ("process", "fork", "unikernel", "paused", "warm")
    for concurrency in (1, 2, 4):
        for drv in light:
            label = f"fig1:{drv}:p{concurrency}"
            parallel_invokes(
                lambda d=drv, l=label: gw.invoke(spec.name, driver=d, label=l),
                light_requests, concurrency)
            st = gw.stats(label, "startup")
            emit(f"startup/{drv}/par{concurrency}", st.p50 * 1e3,
                 f"p99_ms={st.p99:.2f};n={st.n}")

    # per-stage startup decomposition (the paper's container-layer table, ours)
    for drv in light:
        stage_breakdown(gw, f"fig1:{drv}:p1", drv)

    # speculative pre-boot: boot kicked off at dispatch, claimed when the slot
    # frees — startup as seen by the request shrinks toward the claim wait
    label = "fig1:unikernel_spec:p4"
    parallel_invokes(
        lambda: gw.invoke(spec.name, driver="unikernel", label=label,
                          speculative=True),
        light_requests, 4)
    st = gw.stats(label, "startup")
    emit("startup/unikernel_spec/par4", st.p50 * 1e3,
         f"p99_ms={st.p99:.2f};n={st.n};preboots={gw.dispatcher.preboots_launched}")

    # heavyweight paths (the Docker tier) — few samples, they cost seconds each.
    # cold_jit_cached = re-trace + XLA persistent disk cache hit (the gVisor tier);
    # cold_jit = full recompile, the driver keeps the disk cache out (the full
    # Docker stack).
    import jax

    from repro.core.compile_cache import disable_xla_disk_cache, enable_xla_disk_cache

    label = "fig1:cold_jit:p1"
    for _ in range(heavy_requests):
        gw.invoke(spec.name, driver="cold_jit", label=label)
    st = gw.stats(label, "startup")
    emit("startup/cold_jit/par1", st.p50 * 1e3, f"p99_ms={st.p99:.2f};n={st.n}")
    stage_breakdown(gw, label, "cold_jit")

    previous = enable_xla_disk_cache()
    gw.invoke(spec.name, driver="cold_jit_cached", label="cache_warmup")  # populate
    label = "fig1:cold_jit_cached:p1"
    for _ in range(heavy_requests):
        gw.invoke(spec.name, driver="cold_jit_cached", label=label)
    st = gw.stats(label, "startup")
    emit("startup/cold_jit_cached/par1", st.p50 * 1e3, f"p99_ms={st.p99:.2f};n={st.n}")
    stage_breakdown(gw, label, "cold_jit_cached")
    disable_xla_disk_cache(previous)

    # loader comparison: snapshot (pre-laid-out) vs generic checkpoint
    import time

    from repro.core.snapshot import load_generic_checkpoint

    t0 = time.perf_counter()
    for _ in range(3):
        params = dep.snapshots.load_to_device(dep.image.key)
        jax.block_until_ready(params)
    snap_s = (time.perf_counter() - t0) / 3
    t0 = time.perf_counter()
    for _ in range(3):
        params = load_generic_checkpoint(dep.generic_ckpt, dep.abstract_params)
        jax.block_until_ready(params)
    gen_s = (time.perf_counter() - t0) / 3
    emit("loader/snapshot", snap_s * 1e6, f"MB={dep.image.manifest.snapshot_bytes/1e6:.1f}")
    emit("loader/generic_ckpt", gen_s * 1e6, f"penalty_x={gen_s/max(snap_s,1e-9):.2f}")

    delta_restore_comparison(gw, dep)


def delta_restore_comparison(gw, dep, reps: int = 3) -> None:
    """Warm-chunk-tier delta restore vs a v1 full restore, same snapshot.

    The v1 baseline is what every host-tier miss used to pay: read the whole
    snapshot's bytes out of the store (``delta/full_restore_v1``, mmap off so
    the bytes actually move). Against it: the v2 warm-tier paths — pure
    chunk->array assembly with every chunk already resident
    (``delta/warm_chunk_assembly``, zero bytes fetched) and the memoized
    assembled tree a repeat boot actually takes (``delta/warm_cached``). The
    acceptance bar: warm-tier restore >= 3x faster than the v1 full restore
    for an unchanged snapshot.
    """
    import shutil
    import tempfile
    import time

    from repro.core.blobstore import delta_restore
    from repro.core.snapshot import SnapshotStore

    key = dep.image.key
    cache = gw.cluster.hosts[0].cache
    tier = cache.snapshots
    mb = dep.image.manifest.snapshot_bytes / 1e6

    work = tempfile.mkdtemp(prefix="repro_v1cmp_")
    try:
        v1_store = SnapshotStore(work)                   # no blob store: v1
        v1_store.save("cmp", gw.snapshots.load_host(key))
        t0 = time.perf_counter()
        for _ in range(reps):
            v1_store.load_host("cmp", mmap=False)
        full_s = (time.perf_counter() - t0) / reps
    finally:
        shutil.rmtree(work, ignore_errors=True)

    delta_restore(gw.snapshots, key, cache)              # ensure chunks resident
    t0 = time.perf_counter()
    for _ in range(reps):
        tier.drop_tree(key)                              # memo off: pay assembly
        _, stats = delta_restore(gw.snapshots, key, cache)
        assert stats.bytes_fetched == 0, "tier unexpectedly cold"
    assembly_s = (time.perf_counter() - t0) / reps
    t0 = time.perf_counter()
    for _ in range(reps):
        delta_restore(gw.snapshots, key, cache)          # memo on: repeat boot
    cached_s = (time.perf_counter() - t0) / reps

    emit("delta/full_restore_v1", full_s * 1e6, f"mb={mb:.1f};mmap=off")
    emit("delta/warm_chunk_assembly", assembly_s * 1e6,
         f"bytes_fetched=0;speedup_vs_v1={full_s/max(assembly_s,1e-9):.1f}x")
    emit("delta/warm_cached", cached_s * 1e6,
         f"speedup_vs_v1={full_s/max(cached_s,1e-9):.1f}x")


def main(argv=None) -> int:
    """Standalone TTFR smoke: one fresh platform, streamed-then-eager cold
    boots, BENCH_7_startup.json at the repo root. ``--smoke`` exits non-zero
    when TTFR is not >= 2x lower than the streamed boot's full-restore wall
    (the CI regression gate for first-use-ordered streaming)."""
    import argparse

    from repro.core import Gateway

    parser = argparse.ArgumentParser(description=main.__doc__)
    parser.add_argument("--smoke", action="store_true",
                        help="gate the TTFR ratio and exit non-zero on miss")
    parser.add_argument("--out", default=str(REPO_ROOT / "BENCH_7_startup.json"))
    args = parser.parse_args(argv)

    import json

    print("name,us_per_call,derived")
    gw = Gateway(n_hosts=1, slots_per_host=2, mode="cold", hedging=False)
    try:
        spec = bench_spec()
        gw.deploy(spec)      # deploy also warms the in-process AOT loader —
                             # the streamed boot below is tier-cold, LLVM-warm
        data = streamed_ttfr_comparison(gw, out_path=None)
        label = "fig1:unikernel_cold:first"      # eager cell for the report
        gw.invoke(spec.name, driver="unikernel", label=label)
        stage_breakdown(gw, label, "unikernel_cold")
        data["eager"] = _timeline_summary(gw.recorder.timelines(label)[-1])
    finally:
        gw.shutdown()
    Path(args.out).write_text(json.dumps(data, indent=2) + "\n")
    print(f"# wrote {args.out}", flush=True)
    if args.smoke:
        ratio = data["ratio_full_wall_over_ttfr"]
        if not data["gate"]["passed"]:
            print(f"# TTFR gate FAILED: full_wall/ttfr={ratio:.2f}x "
                  f"< {TTFR_GATE_RATIO:.1f}x (split_ok={data['split_ok']})")
            return 1
        print(f"# TTFR gate ok: full_wall/ttfr={ratio:.2f}x")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
