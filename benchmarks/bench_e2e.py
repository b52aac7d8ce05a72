"""Paper Fig 4 + the resource-waste argument, extended with the second and
third axes of the cold-vs-warm comparison: request coalescing and placement.

Three workloads:

* ``_workload`` — the original bursty comparison (cold-only vs warm-pool) with
  idle-HBM byte-seconds integrals between bursts;
* ``load_sweep`` — an open-loop generator (exponential inter-arrivals at a
  target rate, arrivals never wait for completions) sweeping arrival rate over
  cold, cold+coalesced, and warm gateways at the SAME rates. Reported per cell:
  sustained throughput, p50/p95/p99 end-to-end latency, and boots-per-request
  — the coalescing win is boots-per-request << 1 with >= the uncoalesced
  throughput at equal load;
* ``placement_sweep`` — a multi-host sweep of the locality-aware scheduler
  (repro.core.scheduler): affinity-weighted HRW routing vs pure least-loaded
  at the same arrival rate and the same simulated artifact-transfer cost
  model, with per-host tiers sized to hold ONE function's artifacts so
  placement alone decides whether hosts thrash their caches. Emits
  ``placement/*`` rows: program/snapshot tier hit rates, peer vs store
  fetches, and cold end-to-end latency;
* ``delta_sweep`` — the chunked-snapshot (repro.core.blobstore) bench: hosts
  warm with a base snapshot restore VERSIONS of it whose content differs by a
  controlled fraction. Under delta restore only the changed chunks move, so
  bytes fetched from the store (and shipped from a peer) must scale with the
  delta, not the snapshot size — ``delta_sweep/*`` rows feed the DELTA_TABLE
  in EXPERIMENTS.md.

``--smoke`` runs a tiny coalesced-cold sweep and exits nonzero if
boots-per-request regresses to >= 1.0 (i.e. coalescing stopped engaging);
``--smoke --hosts 4`` runs the multi-host placement smoke instead and exits
nonzero if the scheduler's program-cache hit rate drops below 0.5. CI runs
both on every push and uploads the rows (``--json``) as workflow artifacts.
"""
import argparse
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))   # `--smoke` runs standalone

from benchmarks.common import bench_spec, emit, emit_json, parallel_invokes

# simulated artifact-transfer cost model for the placement benches: a global
# store fetch is charged 150 s/GB (~7 MB/s, a congested registry link) and a
# host-to-host peer transfer 5x less — the relative gap, not the absolute
# numbers, is what the scheduler's locality should exploit
SIM_STORE_S_PER_GB = 150.0
SIM_PEER_S_PER_GB = 30.0


def _workload(gw, spec, label: str, bursts: int = 3, per_burst: int = 6,
              gap_s: float = 1.2) -> int:
    """Returns the number of failed requests (this host's XLA:CPU AOT loader is
    intermittently flaky under concurrency — a real platform retries, we also
    count what slipped through the dispatcher's retry budget)."""
    failures = 0

    def one():
        nonlocal failures
        try:
            gw.invoke(spec.name, label=label)
        except Exception:
            failures += 1

    for b in range(bursts):
        parallel_invokes(one, per_burst, 3)
        time.sleep(gap_s)                         # idle gap: warm pools sit resident
    return failures


def open_loop(gw, spec, label: str, rate_rps: float, n_requests: int,
              seed: int = 0, timeout: float = 600.0):
    """Open-loop arrivals: submit at exponential inter-arrival gaps regardless
    of completions (the paper's overload regime is only visible open-loop —
    closed-loop generators self-throttle and hide the queue blow-up)."""
    rng = np.random.default_rng(seed)
    gaps = rng.exponential(1.0 / rate_rps, n_requests)
    futs = []
    failures = 0
    t0 = time.perf_counter()
    t_next = t0
    for g in gaps:
        t_next += g
        dt = t_next - time.perf_counter()
        if dt > 0:
            time.sleep(dt)
        futs.append(gw.invoke_async(spec.name, label=label))
    for f in futs:
        try:
            f.result(timeout)
        except Exception:
            failures += 1
    wall = time.perf_counter() - t0
    return wall, failures


def open_loop_multi(gw, specs, label: str, rate_rps: float, n_requests: int,
                    seed: int = 0, timeout: float = 600.0):
    """Open-loop arrivals spread uniformly over several deployed functions —
    the placement sweep's traffic: hosts see interleaved artifact demands."""
    rng = np.random.default_rng(seed)
    gaps = rng.exponential(1.0 / rate_rps, n_requests)
    picks = rng.integers(0, len(specs), n_requests)
    futs = []
    failures = 0
    t0 = time.perf_counter()
    t_next = t0
    for g, p in zip(gaps, picks):
        t_next += g
        dt = t_next - time.perf_counter()
        if dt > 0:
            time.sleep(dt)
        futs.append(gw.invoke_async(specs[p].name, label=label))
    for f in futs:
        try:
            f.result(timeout)
        except Exception:
            failures += 1
    wall = time.perf_counter() - t0
    return wall, failures


def _load_cell(make_gateway, spec, config_name: str, gw_kwargs: dict,
               rate_rps: float, n_requests: int) -> dict:
    gw = make_gateway(**gw_kwargs)
    gw.deploy(spec)
    label = f"load:{config_name}:{rate_rps:g}"
    wall, failures = open_loop(gw, spec, label, rate_rps, n_requests)
    st = gw.stats(label)
    n_ok = st.n
    boots = gw.agent.boots
    bpr = boots / max(n_ok, 1)
    throughput = n_ok / wall
    batching = gw.batching_summary()
    gw.shutdown()
    return {
        "config": config_name, "rate": rate_rps, "throughput": throughput,
        "p50": st.p50, "p95": st.p95, "p99": st.p99,
        "boots_per_request": bpr, "failures": failures, "n_ok": n_ok,
        "mean_batch": (batching or {}).get("mean_batch_size", 1.0),
    }


def load_sweep(make_gateway, rates=(40.0, 120.0), n_requests: int = 60) -> list:
    """Cold vs cold+coalesced vs warm at the same open-loop arrival rates.

    The sweep uses a boot-dominated request shape (batch 1, short prompt) —
    the paper's regime, where the per-request cost IS the start. There the
    coalescer's amortization shows directly: one boot serves a whole bucket,
    so cold throughput scales past the boots-per-second ceiling that caps the
    uncoalesced platform.
    """
    spec = bench_spec(batch=1, prompt=16, decode=2)
    configs = [
        ("cold", dict(mode="cold")),
        ("cold+coalesce", dict(mode="cold", batching=True)),
        ("warm", dict(mode="warm")),
    ]
    cells = []
    for config_name, gw_kwargs in configs:
        for rate in rates:
            cell = _load_cell(make_gateway, spec, config_name, gw_kwargs,
                              rate, n_requests)
            cells.append(cell)
            emit(f"e2e_load/{config_name}/rps{rate:g}", cell["throughput"],
                 f"p50_ms={cell['p50']:.1f};p95_ms={cell['p95']:.1f};"
                 f"p99_ms={cell['p99']:.1f};"
                 f"boots_per_request={cell['boots_per_request']:.3f};"
                 f"mean_batch={cell['mean_batch']:.2f};"
                 f"fails={cell['failures']}")
    return cells


def placement_sweep(make_gateway, hosts: int = 4, rate_rps: float = 6.0,
                    n_requests: int = 80) -> list:
    """Multi-host cold sweep: affinity-weighted HRW routing vs least-loaded.

    Both configs share the cluster size, the arrival process, and the
    simulated transfer-cost model; only the scheduler's affinity weight
    differs. Two functions are deployed and each host's tiers are shrunk to
    hold ONE function's artifacts — so least-loaded placement (which
    interleaves functions on every host) thrashes the tiers and re-pays the
    store fetch, while the affinity scheduler partitions the fleet by HRW
    replica set and converges to RAM hits. The win to look for: program-cache
    hit rate >= 0.5 and a lower cold e2e median at the same arrival rate.
    """
    from repro.core import SchedulerConfig

    specs = [bench_spec(batch=1, prompt=16, decode=2),
             bench_spec(batch=1, prompt=24, decode=2)]
    cells = []
    for config_name, weight in (("affinity", 2.0), ("no-affinity", 0.0)):
        cfg = SchedulerConfig(affinity_weight=weight, replicas=2,
                              sim_store_s_per_gb=SIM_STORE_S_PER_GB,
                              sim_peer_s_per_gb=SIM_PEER_S_PER_GB)
        gw = make_gateway(mode="cold", n_hosts=hosts, scheduler=cfg)
        deps = [gw.deploy(s) for s in specs]
        prog = max(d.image.manifest.program_bytes for d in deps)
        snap = max(d.image.manifest.snapshot_bytes for d in deps)
        for h in gw.cluster.hosts:           # tiers fit one function, not two
            h.cache.programs.capacity_bytes = int(prog * 1.5)
            h.cache.snapshots.capacity_bytes = int(snap * 1.5)
        label = f"placement:{config_name}"
        wall, failures = open_loop_multi(gw, specs, label, rate_rps, n_requests)
        st = gw.stats(label)
        ps = gw.placement_summary()
        gw.shutdown()
        cell = {
            "config": config_name, "hosts": hosts, "rate": rate_rps,
            "hit_rate": ps["program_hit_rate"],
            "snapshot_hit_rate": ps["snapshot_hit_rate"],
            "peer_fetches": ps["peer_fetches"],
            "store_fetches": ps["store_fetches"],
            "p50": st.p50, "p95": st.p95, "n_ok": st.n,
            "failures": failures, "throughput": st.n / wall,
        }
        cells.append(cell)
        emit(f"placement/{config_name}/hosts{hosts}", cell["hit_rate"],
             f"hit_rate={cell['hit_rate']:.3f};"
             f"snapshot_hit_rate={cell['snapshot_hit_rate']:.3f};"
             f"p50_ms={cell['p50']:.1f};p95_ms={cell['p95']:.1f};"
             f"peer={cell['peer_fetches']};store={cell['store_fetches']};"
             f"throughput_rps={cell['throughput']:.1f};"
             f"rate_rps={rate_rps:g};fails={cell['failures']}")
    return cells


def delta_sweep(fracs=(0.0, 0.25, 0.5, 1.0), n_leaves: int = 128,
                leaf_bytes: int = 64 << 10) -> list:
    """Delta restore: bytes moved must scale with the CONTENT delta.

    A 2-host cluster shares one chunked snapshot store. Both hosts warm their
    chunk tiers with a base snapshot (host 0 from the global store, host 1
    from its peer). Then, for each fraction f, a new VERSION of the snapshot
    is written in which f of the leaves were mutated — under chunk-level
    dedup its manifest shares (1-f) of its chunks with the base — and each
    host delta-restores it: host 0's missing chunks come from the global
    store, host 1's from its peer (which just restored the same version).
    Both paths are charged the simulated transfer cost on the bytes that
    actually moved, so restore time falls out of the delta too. The v1
    comparison is implicit: without chunking every row would fetch
    ``total_mb`` regardless of f.
    """
    import shutil
    import tempfile

    import numpy as np

    from repro.core.blobstore import ChunkStore, delta_restore
    from repro.core.cluster import Cluster
    from repro.core.scheduler import SchedulerConfig
    from repro.core.snapshot import SnapshotStore

    rng = np.random.default_rng(0)
    base = {f"layer{i:03d}": rng.standard_normal(leaf_bytes // 8)
            for i in range(n_leaves)}
    work = tempfile.mkdtemp(prefix="repro_delta_")
    blobs = ChunkStore(Path(work) / "blobs")
    store = SnapshotStore(Path(work) / "snaps", blobs=blobs)
    store.save("base", base)

    cfg = SchedulerConfig(sim_store_s_per_gb=SIM_STORE_S_PER_GB,
                          sim_peer_s_per_gb=SIM_PEER_S_PER_GB,
                          snapshot_tier_bytes=4 << 30)
    cluster = Cluster(n_hosts=2, scheduler=cfg)
    cells = []
    try:
        host_store, host_peer = cluster.hosts[0], cluster.hosts[1]
        delta_restore(store, "base", host_store.cache)   # warm via global store
        delta_restore(store, "base", host_peer.cache)    # warm via peer
        for i, frac in enumerate(fracs):
            version = dict(base)
            mutated = sorted(base)[:int(n_leaves * frac)]
            vrng = np.random.default_rng(100 + i)
            for k in mutated:
                version[k] = base[k] + vrng.standard_normal(base[k].shape)
            name = f"v{frac:g}"
            store.save(name, version)
            for source, host in (("store", host_store), ("peer", host_peer)):
                t0 = time.perf_counter()
                _, stats = delta_restore(store, name, host.cache)
                restore_s = time.perf_counter() - t0
                cell = {
                    "source": source, "frac": frac,
                    "total_mb": stats.bytes_total / 1e6,
                    "fetched_mb": stats.bytes_fetched / 1e6,
                    "deduped_mb": stats.bytes_deduped / 1e6,
                    "fetched_frac": stats.bytes_fetched / max(stats.bytes_total, 1),
                    "restore_ms": restore_s * 1e3,
                    "bytes_from_peer": stats.bytes_from_peer,
                    "bytes_from_store": stats.bytes_from_store,
                }
                cells.append(cell)
                emit(f"delta_sweep/{source}/f{frac:g}", cell["fetched_mb"],
                     f"total_mb={cell['total_mb']:.1f};"
                     f"fetched_mb={cell['fetched_mb']:.1f};"
                     f"deduped_mb={cell['deduped_mb']:.1f};"
                     f"fetched_frac={cell['fetched_frac']:.3f};"
                     f"restore_ms={cell['restore_ms']:.1f}")
    finally:
        cluster.shutdown()
        shutil.rmtree(work, ignore_errors=True)
    return cells


def decode_sweep(n_requests: int = 16, seed: int = 0, slots: int = 8,
                 decode_steps: int = 192, slo_ms: float = 30_000.0,
                 ratio_floor: float = 1.5, out_path=None) -> dict:
    """Step-granular continuous batching vs request-granular bucket batching.

    The same mixed-budget workload runs through both decode tiers: a
    heavy-tailed budget mix (most requests want a handful of tokens, a few
    want the full budget) on identical prompts. The BUCKET cell coalesces
    requests into the fused serve program, which decodes the full
    ``decode_steps`` budget for every member — an early finisher pays for
    every remaining step. The CONTINUOUS cell joins the paged-KV step loop
    and leaves at its own budget. The headline is USEFUL tokens per second:
    tokens the requests actually asked for, divided by wall clock — the
    metric the fused program wastes on retired rows.

    Writes the ``BENCH_10_decode.json`` contract (schema v2) when
    ``out_path`` is given; the CI gate is the tokens/s ratio >= ``ratio_floor``
    with the continuous cell's e2e p95 inside ``slo_ms``.
    """
    import json

    from repro.core import FunctionSpec, Gateway
    from repro.core.batching import BatchingConfig
    from repro.core.decode import DecodeConfig

    spec = FunctionSpec(arch="llama3.2-3b", batch_size=1, prompt_len=8,
                        decode_steps=decode_steps)
    rng = np.random.default_rng(seed)
    # the serving long tail: most requests stop after a handful of tokens, a
    # few run longer — and ALL of them sit far below the deploy-time fused
    # budget, which the bucket tier must decode in full for every member.
    # That gap is exactly the waste continuous batching exists to reclaim.
    long_budget = max(2, spec.decode_steps // 8)
    budgets = [long_budget if i % 4 == 0 else int(rng.integers(1, 7))
               for i in range(n_requests)]
    useful = sum(budgets)
    cells = {}

    # continuous: one resident executor, requests join/leave per step
    gw = Gateway(n_hosts=1, slots_per_host=2, mode="cold", hedging=False,
                 decode=DecodeConfig(slots=slots, page_size=8,
                                     cool_after_s=0.25))
    dep = gw.deploy(spec)
    prompts = [dep.example_tokens(seed=1000 + i)[:1] for i in range(n_requests)]
    label = "decode:continuous"
    t0 = time.perf_counter()
    futs = [gw.invoke_decode_async(spec.name, tokens=p, max_new=b, label=label)
            for p, b in zip(prompts, budgets)]
    outs = [np.asarray(f.result(600)) for f in futs]
    wall_c = time.perf_counter() - t0
    st = gw.stats(label)
    ttfr = gw.stats(label, "ttfr")
    dsum = gw.decode_summary(spec.name)
    gw.shutdown()
    short = [i for i, (o, b) in enumerate(zip(outs, budgets))
             if o.shape != (b,)]
    if short:
        raise RuntimeError(f"continuous cell truncated requests: {short}")
    cells["continuous"] = {
        "wall_s": wall_c, "useful_tokens": useful,
        "tokens_per_s": useful / wall_c,
        "p50_ms": st.p50, "p95_ms": st.p95,
        "ttfr_p50_ms": ttfr.p50, "ttfr_p95_ms": ttfr.p95,
        "steps": dsum["steps"], "occupancy": dsum["occupancy"],
        "boots": dsum["boots"], "admit_waits": dsum["admit_waits"],
        "pages_high_water": dsum["pages_high_water"],
    }
    emit("decode/continuous/tokens_per_s", cells["continuous"]["tokens_per_s"],
         f"p50_ms={st.p50:.1f};p95_ms={st.p95:.1f};"
         f"ttfr_p50_ms={ttfr.p50:.1f};steps={dsum['steps']:.0f};"
         f"occupancy={dsum['occupancy']:.3f};wall_s={wall_c:.2f}")

    # bucket: the coalescer's fused program — full decode budget per member
    gw = Gateway(n_hosts=1, slots_per_host=2, mode="cold", hedging=False,
                 batching=BatchingConfig(min_window_s=0.02))
    gw.deploy(spec)
    label = "decode:bucket"
    t0 = time.perf_counter()
    futs = [gw.invoke_async(spec.name, tokens=p, label=label) for p in prompts]
    for f in futs:
        f.result(600)
    wall_b = time.perf_counter() - t0
    st_b = gw.stats(label)
    bsum = gw.batching_summary()
    gw.shutdown()
    cells["bucket"] = {
        "wall_s": wall_b, "useful_tokens": useful,
        "decoded_tokens": n_requests * spec.decode_steps,
        "tokens_per_s": useful / wall_b,
        "p50_ms": st_b.p50, "p95_ms": st_b.p95,
        "mean_batch": (bsum or {}).get("mean_batch_size", 1.0),
    }
    emit("decode/bucket/tokens_per_s", cells["bucket"]["tokens_per_s"],
         f"p50_ms={st_b.p50:.1f};p95_ms={st_b.p95:.1f};"
         f"decoded={cells['bucket']['decoded_tokens']};"
         f"mean_batch={cells['bucket']['mean_batch']:.2f};wall_s={wall_b:.2f}")

    ratio = cells["continuous"]["tokens_per_s"] / cells["bucket"]["tokens_per_s"]
    ok = ratio >= ratio_floor and cells["continuous"]["p95_ms"] <= slo_ms
    emit("decode/ratio", ratio,
         f"floor={ratio_floor};slo_ms={slo_ms:g};ok={ok}")
    payload = {
        "schema_version": 2,
        "bench": "decode",
        "run_id": f"decode-n{n_requests}s{slots}"
                  f"d{spec.decode_steps}-seed{seed}",
        "seed": seed,
        "config": {
            "n_requests": n_requests, "slots": slots, "page_size": 8,
            "prompt_len": spec.prompt_len, "decode_steps": spec.decode_steps,
            "budgets": budgets, "useful_tokens": useful,
            "slo_ms": slo_ms, "ratio_floor": ratio_floor,
        },
        "cells": cells,
        "gate": {"ok": ok, "ratio": ratio, "ratio_floor": ratio_floor,
                 "slo_ms": slo_ms},
        "headline": {
            "tokens_per_s_ratio": {
                "value": ratio, "better": "higher", "rel_tol": 0.25},
            "continuous_p95_ms": {
                "value": cells["continuous"]["p95_ms"], "better": "lower",
                "rel_tol": 0.5},
        },
    }
    if out_path is not None:
        Path(out_path).write_text(
            json.dumps(payload, indent=2, sort_keys=True) + "\n")
    return payload


def smoke_decode(out_path=None) -> int:
    """CI gate: continuous batching must deliver >= 1.5x the bucket tier's
    useful tokens/s on the mixed-budget workload, inside the e2e p95 SLO."""
    payload = decode_sweep(out_path=out_path)
    gate = payload["gate"]
    cont = payload["cells"]["continuous"]
    print(f"bench-smoke[decode]: ratio={gate['ratio']:.2f} "
          f"(floor {gate['ratio_floor']}) "
          f"continuous_p95_ms={cont['p95_ms']:.0f} (slo {gate['slo_ms']:g}) "
          f"occupancy={cont['occupancy']:.3f} boots={cont['boots']:.0f}")
    if not gate["ok"]:
        print("bench-smoke[decode]: FAIL — continuous batching is not "
              "beating bucket batching by the required margin inside SLO")
        return 1
    print("bench-smoke[decode]: OK")
    return 0


def run(make_gateway, samples_scale: float = 1.0) -> None:
    spec = bench_spec()

    for mode in ("cold", "warm"):
        gw = make_gateway(mode=mode)
        gw.deploy(spec)
        label = f"e2e:{mode}"
        t0 = time.perf_counter()
        failures = _workload(gw, spec, label)
        wall = time.perf_counter() - t0
        st = gw.stats(label)
        su = gw.stats(label, "startup")
        gw.shutdown()                              # flushes pools -> residency
        res = gw.residency_summary()
        emit(f"e2e/{mode}/e2e_p50", st.p50 * 1e3,
             f"p99_ms={st.p99:.1f};startup_p50_ms={su.p50:.1f};"
             f"fails={failures};retries={gw.dispatcher.retries}")
        emit(f"e2e/{mode}/idle_GBs", res["idle_GBs"] * 1e6,
             f"total_GBs={res['total_GBs']:.4f};wall_s={wall:.1f}")

    load_sweep(make_gateway)
    placement_sweep(make_gateway)
    delta_sweep()
    decode_sweep(out_path=Path(__file__).resolve().parent.parent
                 / "BENCH_10_decode.json")


def smoke_placement(hosts: int = 4, rate_rps: float = 30.0,
                    n_requests: int = 24) -> int:
    """CI gate: the affinity scheduler must keep the program-cache hit rate
    at or above 0.5 on a multi-host fleet (i.e. locality is engaging)."""
    from repro.core import Gateway, SchedulerConfig

    spec = bench_spec(batch=1, prompt=16, decode=2)
    gw = Gateway(n_hosts=hosts, slots_per_host=2, mode="cold", hedging=False,
                 scheduler=SchedulerConfig(
                     affinity_weight=2.0, replicas=2,
                     sim_store_s_per_gb=SIM_STORE_S_PER_GB,
                     sim_peer_s_per_gb=SIM_PEER_S_PER_GB))
    gw.deploy(spec)
    wall, failures = open_loop(gw, spec, "smoke-placement", rate_rps, n_requests)
    st = gw.stats("smoke-placement")
    ps = gw.placement_summary()
    gw.shutdown()
    hit = ps["program_hit_rate"]
    emit(f"placement/smoke/hosts{hosts}", hit,
         f"hit_rate={hit:.3f};"
         f"snapshot_hit_rate={ps['snapshot_hit_rate']:.3f};"
         f"p50_ms={st.p50:.1f};peer={ps['peer_fetches']};"
         f"store={ps['store_fetches']};fails={failures}")
    print(f"bench-smoke[placement]: n_ok={st.n} failures={failures} "
          f"hosts={hosts} program_hit_rate={hit:.3f} "
          f"peer={ps['peer_fetches']} store={ps['store_fetches']} "
          f"p50_ms={st.p50:.1f} wall_s={wall:.1f}")
    if st.n < n_requests:
        print(f"bench-smoke[placement]: FAIL — {n_requests - st.n} requests failed")
        return 1
    if hit < 0.5:
        print("bench-smoke[placement]: FAIL — program-cache hit rate < 0.5, "
              "affinity placement is not engaging")
        return 1
    print("bench-smoke[placement]: OK")
    return 0


def smoke(rate_rps: float = 60.0, n_requests: int = 16) -> int:
    """CI gate: coalesced cold mode must keep boots-per-request below 1.0."""
    from repro.core import Gateway

    spec = bench_spec(batch=1, prompt=16, decode=2)
    gw = Gateway(n_hosts=1, slots_per_host=2, mode="cold", hedging=False,
                 batching=True)
    gw.deploy(spec)
    wall, failures = open_loop(gw, spec, "smoke", rate_rps, n_requests)
    st = gw.stats("smoke")
    boots = gw.agent.boots
    summary = gw.batching_summary()
    gw.shutdown()
    bpr = boots / max(st.n, 1)
    emit("e2e_load/smoke/coalesce", st.n / wall,
         f"p50_ms={st.p50:.1f};boots_per_request={bpr:.3f};"
         f"mean_batch={summary['mean_batch_size']:.2f};fails={failures}")
    print(f"bench-smoke: n_ok={st.n} failures={failures} boots={boots} "
          f"boots_per_request={bpr:.3f} p50_ms={st.p50:.1f} "
          f"mean_batch={summary['mean_batch_size']:.2f} wall_s={wall:.1f}")
    if st.n < n_requests:
        print(f"bench-smoke: FAIL — {n_requests - st.n} requests failed")
        return 1
    if bpr >= 1.0:
        print("bench-smoke: FAIL — boots-per-request >= 1.0, coalescing is "
              "not engaging in coalesced cold mode")
        return 1
    print("bench-smoke: OK")
    return 0


if __name__ == "__main__":
    from repro.core.compile_cache import use_checkout_compile_cache
    use_checkout_compile_cache()
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--smoke", action="store_true",
                        help="tiny CI run; nonzero exit on regression "
                             "(boots-per-request, or hit rate with --hosts > 1)")
    parser.add_argument("--hosts", type=int, default=1,
                        help="with --smoke: >1 runs the multi-host placement "
                             "smoke (program-cache hit-rate gate) instead of "
                             "the coalescing gate")
    parser.add_argument("--json", type=str, default=None,
                        help="also write the emitted rows to this JSON file "
                             "(CI uploads it as a workflow artifact)")
    parser.add_argument("--decode", action="store_true",
                        help="run the continuous-vs-bucket decode sweep; with "
                             "--smoke it gates the tokens/s ratio >= 1.5 and "
                             "the p95 SLO")
    parser.add_argument("--out", type=str, default=None,
                        help="with --decode: write BENCH_10_decode.json here")
    args = parser.parse_args()
    if args.decode:
        out = args.out or str(Path(__file__).resolve().parent.parent
                              / "BENCH_10_decode.json")
        rc = smoke_decode(out_path=out) if args.smoke else \
            (0 if decode_sweep(out_path=out)["gate"]["ok"] else 1)
        if args.json:
            emit_json(args.json)
        sys.exit(rc)
    if args.smoke:
        rc = smoke_placement(hosts=args.hosts) if args.hosts > 1 else smoke()
        if args.json:
            emit_json(args.json)
        sys.exit(rc)
    from repro.core import Gateway

    def make_gateway(**kw):
        kw.setdefault("mode", "cold")
        kw.setdefault("n_hosts", 2)
        return Gateway(slots_per_host=3, hedging=False, **kw)

    run(make_gateway)
    if args.json:
        emit_json(args.json)
