"""Executor instantiation strategies — the paper's runtime taxonomy, ported.

Ordered fastest -> slowest start, with their Sec II/III analogues:

| driver            | paper analogue                  | start path                          |
|-------------------|---------------------------------|-------------------------------------|
| process           | bare process (`/bin/date`)      | reuse the resident donor executor   |
| fork              | fork()/clone(), solo5-spt       | alias donor weights (COW) + program |
| unikernel         | IncludeOS-hvt  (the paper's bet)| AOT deserialize || snapshot->device |
| unikernel_stream  | unikernel + lazy restore        | AOT head || first-use-ordered stream|
| paused            | Fn paused containers/Firecracker| cached program + host RAM -> device |
| warm              | warm Lambda / warm Fn-Docker    | pool checkout (no work, holds HBM)  |
| cold_jit_cached   | gVisor/runc                     | re-trace + XLA disk-cache hit + ckpt|
| cold_jit          | full Docker stack               | re-trace + full XLA compile + ckpt  |

Every driver is a *declaration*: ``plan(dep)`` returns a BootPlan over the
shared stage vocabulary in :mod:`repro.core.boot`, and the shared ``start``
body hands it to the BootEngine — which times every stage into
``Timeline.stage_s`` and overlaps the program and weights tracks. No driver
hand-rolls a serial start path anymore.

Invariants: only READY executors re-enter the warm pool (a crashed one would
poison every later checkout); donors are shared, never exited by a request
path, and evicted exactly once at shutdown so their residency is accounted;
``supports_preboot``/``supports_batch`` gate speculation and coalescing to
drivers whose plans are pure at declaration time.
"""
from __future__ import annotations

import threading
from typing import Dict, Optional

import jax
import numpy as np

from repro.core.boot import (
    ENGINE,
    AliasDonor,
    BootEngine,
    BootPlan,
    DevicePut,
    DeserializeProgram,
    FetchParked,
    FetchProgram,
    FetchProgramHead,
    Finalize,
    FinalizeStream,
    PoolCheckout,
    RestoreWeightsHost,
    ReuseDonor,
    StreamRestore,
    TraceCompile,
)
from repro.core.deploy import Deployment
from repro.core.executor import Executor, ExecutorState
from repro.core.metrics import Timeline


class Driver:
    name: str = "base"
    engine: BootEngine = ENGINE
    # the Host whose driver table this instance lives in (set by make_drivers);
    # boot stages use it to consult the host's tiered artifact cache before the
    # global stores. None for standalone driver instances (no cache tier).
    host = None
    # drivers whose boots are pure (no pool/donor state mutated before the
    # executor is claimed) may be started speculatively by the dispatcher
    supports_preboot: bool = False
    # drivers whose boot can target a coalesced batch shape (the coalescer in
    # repro.core.batching routes through these); pool/donor drivers hold
    # executors compiled for the base shape, so they stay unbatched
    supports_batch: bool = False
    # batch-capable drivers that boot from a serialized AOT image need the
    # bucket program built into the registry first (Deployment.ensure_bucket);
    # re-tracing drivers compile the bucket shape themselves
    needs_bucket_image: bool = False

    def plan(self, dep: Deployment) -> BootPlan:
        """Declare this driver's start path as a BootPlan."""
        raise NotImplementedError

    def start(self, dep: Deployment, tl: Timeline,
              bucket_rows: Optional[int] = None) -> Executor:
        """The ONE start body shared by every driver: execute the declaration."""
        return self.engine.execute(self.plan(dep), dep, tl, driver_name=self.name,
                                   bucket_rows=bucket_rows, host=self.host)

    def finish(self, dep: Deployment, ex: Executor) -> None:
        """Post-request lifecycle. Cold drivers exit; pool drivers return."""
        ex.exit()


class UnikernelDriver(Driver):
    """The paper's contribution: per-request cold start from a single-purpose
    image — program deserialize and snapshot restore run CONCURRENTLY."""

    name = "unikernel"
    supports_preboot = True
    supports_batch = True
    needs_bucket_image = True

    def plan(self, dep: Deployment) -> BootPlan:
        return BootPlan([
            FetchProgram(), DeserializeProgram(),            # program track
            RestoreWeightsHost("snapshot"), DevicePut(),     # weights track
            Finalize(),
        ])


class UnikernelStreamDriver(Driver):
    """Streamed cold start: serve the first request before full restore.

    Program track boots the AOT *head* sub-program (prefill + first token)
    when the deployment published a verified split; the weights track streams
    leaves to the device in first-use order behind per-leaf readiness gates
    (``StreamRestore``), and ``FinalizeStream`` hands back a PARTIAL executor
    whose tail — remaining leaves, tail/fused programs — completes in the
    background while the request already executes. TTFR stops scaling with
    image size; ``t_boot_wall`` keeps the honest full-restore accounting.

    Unbatched on purpose: bucket programs have no published split, so a batch
    boot would silently degrade to the fused path — route batches to the
    plain ``unikernel`` driver instead.
    """

    name = "unikernel_stream"
    supports_preboot = True
    supports_batch = False

    def plan(self, dep: Deployment) -> BootPlan:
        return BootPlan([
            FetchProgramHead(), DeserializeProgram(),        # program track
            StreamRestore(),                                 # weights track
            FinalizeStream(),
        ])


class ForkDriver(Driver):
    """COW clone of a donor: share immutable weight buffers + in-memory program."""

    name = "fork"

    def __init__(self, on_exit=None) -> None:
        self.on_exit = on_exit
        self._donors: Dict[str, Executor] = {}
        self._lock = threading.Lock()

    def ensure_donor(self, dep: Deployment) -> Executor:
        with self._lock:
            donor = self._donors.get(dep.image.key)
            if donor is None or donor.params is None:
                donor = self.engine.execute(
                    UnikernelDriver().plan(dep), dep, Timeline(),
                    driver_name="fork-donor", host=self.host)
                self._donors[dep.image.key] = donor
            return donor

    def plan(self, dep: Deployment) -> BootPlan:
        return BootPlan([AliasDonor(self.ensure_donor(dep)), Finalize()])

    def donor_nbytes(self) -> int:
        with self._lock:
            return sum(d.nbytes for d in self._donors.values() if d.params is not None)

    def evict_donors(self) -> list:
        """Exit all donors (gateway shutdown) so their HBM residency is
        accounted via on_exit instead of silently vanishing."""
        with self._lock:
            donors = [d for d in self._donors.values() if d.params is not None]
            self._donors.clear()
        for d in donors:
            d.exit()
            if self.on_exit is not None:
                self.on_exit(d)
        return donors


class ProcessDriver(ForkDriver):
    """Dispatch onto the resident donor itself — the pure platform-overhead floor."""

    name = "process"

    def plan(self, dep: Deployment) -> BootPlan:
        return BootPlan([ReuseDonor(self.ensure_donor(dep))])

    def finish(self, dep: Deployment, ex: Executor) -> None:
        pass  # donor stays resident


class PausedDriver(Driver):
    """Fn's paused containers: program cached, weights parked in host DRAM.

    Not pre-bootable: ``plan()`` on a cold park would run the full host-side
    parking (load_program + non-mmap weight read) synchronously on the
    dispatcher's submit thread, and the boot itself is just a device_put —
    speculation has nothing to overlap.
    """

    name = "paused"

    def __init__(self) -> None:
        self._parked: Dict[str, tuple] = {}
        self._lock = threading.Lock()

    def ensure_parked(self, dep: Deployment) -> tuple:
        with self._lock:
            entry = self._parked.get(dep.image.key)
            if entry is None:
                program = dep.load_program()
                host = dep.snapshots.load_host(dep.image.key, mmap=False)
                host = jax.tree.map(np.ascontiguousarray, host)
                entry = (program, host)
                self._parked[dep.image.key] = entry
            return entry

    def plan(self, dep: Deployment) -> BootPlan:
        program, host = self.ensure_parked(dep)
        return BootPlan([FetchParked(program, host), DevicePut(), Finalize()])


class WarmDriver(Driver):
    """The incumbent: a pool of fully-resident executors (falls back cold on miss)."""

    name = "warm"

    def __init__(self, fallback: Optional[Driver] = None, on_exit=None) -> None:
        self.fallback = fallback or UnikernelDriver()
        self.on_exit = on_exit
        self._pools: Dict[str, list] = {}
        self._lock = threading.Lock()

    def prewarm(self, dep: Deployment, n: int) -> None:
        for _ in range(n):
            ex = self.engine.execute(self.fallback.plan(dep), dep, Timeline(),
                                     driver_name=self.name, host=self.host)
            with self._lock:
                self._pools.setdefault(dep.image.key, []).append(ex)

    def _checkout(self, key: str) -> Optional[Executor]:
        with self._lock:
            pool = self._pools.setdefault(key, [])
            return pool.pop() if pool else None

    def plan(self, dep: Deployment) -> BootPlan:
        ex = self._checkout(dep.image.key)
        if ex is not None:
            return BootPlan([PoolCheckout(ex)])
        # cold miss: run (and per-stage time) the fallback driver's plan
        return self.fallback.plan(dep)

    def finish(self, dep: Deployment, ex: Executor) -> None:
        if ex.state is not ExecutorState.READY:
            # a crashed/EXITED executor must never re-enter the pool — it would
            # poison every subsequent checkout with a dead program
            return
        with self._lock:
            self._pools.setdefault(dep.image.key, []).append(ex)

    def pool_size(self, key: str) -> int:
        with self._lock:
            return len(self._pools.get(key, []))

    def expire_idle(self, key: str, keep: int) -> list:
        """Idle-timeout eviction (the knob the paper calls a lose-lose trade-off)."""
        expired = []
        with self._lock:
            pool = self._pools.setdefault(key, [])
            while len(pool) > keep:
                expired.append(pool.pop())
        for ex in expired:
            ex.exit()
            if self.on_exit is not None:
                self.on_exit(ex)
        return expired

    def resident_nbytes(self) -> int:
        with self._lock:
            return sum(e.nbytes for pool in self._pools.values() for e in pool)


class ColdJITDriver(Driver):
    """Full Docker-stack analogue: re-trace + full XLA compile, the persistent
    cache kept out of it, + generic checkpoint (the trace/compile still
    overlaps the checkpoint parse — even the slow path benefits from the
    staged pipeline)."""

    name = "cold_jit"
    supports_preboot = True
    supports_batch = True          # TraceCompile re-traces at the bucket shape
    disk_cache = False

    def plan(self, dep: Deployment) -> BootPlan:
        return BootPlan([
            TraceCompile(disk_cache=self.disk_cache),        # program track
            RestoreWeightsHost("generic"), DevicePut(),      # weights track
            Finalize(),
        ])


class ColdJITCachedDriver(ColdJITDriver):
    """gVisor-tier: still re-traces, but XLA's persistent disk cache absorbs the
    compile (enable via repro.core.compile_cache.enable_xla_disk_cache)."""

    name = "cold_jit_cached"
    disk_cache = True


ALL_DRIVERS = ("process", "fork", "unikernel", "unikernel_stream", "paused",
               "warm", "cold_jit_cached", "cold_jit")


def make_drivers(on_exit=None, host=None) -> Dict[str, Driver]:
    drivers: Dict[str, Driver] = {
        "process": ProcessDriver(on_exit=on_exit),
        "fork": ForkDriver(on_exit=on_exit),
        "unikernel": UnikernelDriver(),
        "unikernel_stream": UnikernelStreamDriver(),
        "paused": PausedDriver(),
        "warm": WarmDriver(on_exit=on_exit),
        "cold_jit_cached": ColdJITCachedDriver(),
        "cold_jit": ColdJITDriver(),
    }
    for drv in drivers.values():
        drv.host = host
    return drivers
