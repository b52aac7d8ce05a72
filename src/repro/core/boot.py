"""Staged boot pipeline: declarative, individually-timed, overlappable cold starts.

The paper decomposes a container cold start into layers (kernel, runtime,
dependency resolution, app init) and shows the unikernel build collapses them.
"How Low Can You Go?" (arXiv:2109.13319) pushes further: the remaining stages
must be *overlapped*, not just shrunk. This module is that decomposition for
XLA executors:

    BootPlan   = ordered list of declarative stages, each tagged with a track
    BootEngine = executes a plan; the PROGRAM track (fetch + deserialize or
                 trace + compile) and the WEIGHTS track (host restore + chunked
                 device_put) run CONCURRENTLY; JOIN stages (Finalize) run after
                 both tracks complete
    BootHandle = a cancellable in-flight boot — the dispatcher uses it for
                 speculative pre-boot (kick the boot off while the request is
                 still queued; cancel cleanly if a hedge or retry wins)

Every stage's duration lands in ``Timeline.stage_s[stage.name]`` and the
combined wall time in ``Timeline.t_boot_wall``, so the benchmarks can report a
per-stage startup breakdown exactly like the paper's container-layer tables —
and show the overlap win directly (wall < sum of stages). Each stage also runs
inside a ``boot.<stage name>`` span (:func:`repro.core.metrics.span`) on the
thread of its track, so a profiler trace shows the two tracks side by side.

Streamed boots (``StreamRestore``/``FinalizeStream``, the ``unikernel_stream``
driver) relax the all-at-once join: the weights track opens per-leaf
readiness gates as leaves land on device (in the manifest's first-use order)
and the JOIN stage may finalize a PARTIAL executor whose tail — remaining
leaves, the tail/fused programs — completes on a background thread, patching
the bound timelines (``restore_stream_tail_bg``, ``deserialize_program_bg``)
when it settles. ``BootResult.t_first_ready`` stamps the moment the executor
became dispatchable.

Invariants: a weights-track stage never reads context fields a program-track
stage writes (and vice versa) — cross-track products meet either at JOIN
stages or through the readiness gates, which hand a finalized PARTIAL
executor its tail exactly once (gate events are set-only, completion is
monotonic); cancellation lands at stage boundaries AND per-chunk inside the
streaming transfers (``streamed_device_put``/``stream_restore`` consult the
boot's cancel event), and a cancelled or failed boot disposes everything it
materialized (no leaked executors or device memory); stage names are unique
per plan, and a stage that rebinds its name records under the path that
actually ran.
"""
from __future__ import annotations

import queue
import threading
from concurrent.futures import Future
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.core.compile_cache import (CompileCache, persistent_cache_off,
                                      refuse_degrade_on_tpu)
from repro.core.executor import Executor, ReadinessGates, SplitServe
from repro.core.metrics import BOOT_SPAN_PREFIX, Timeline, now, span


def spawn_future(fn: Callable[[], Any], name: str) -> Future:
    """Run ``fn`` on a daemon thread, relaying result/exception via a Future.

    The primitive under the async load APIs (snapshot.load_host_async,
    CompileCache.load_program_async) that let callers overlap boot work
    without going through a full BootEngine plan.
    """
    fut: Future = Future()

    def work() -> None:
        try:
            fut.set_result(fn())
        except BaseException as e:  # noqa: BLE001 - relayed via Future
            fut.set_exception(e)

    threading.Thread(target=work, daemon=True, name=name).start()
    return fut

# Track tags: stages on different tracks may run concurrently; stages within a
# track run in declaration order; JOIN stages run after all tracks complete.
TRACK_PROGRAM = "program"
TRACK_WEIGHTS = "weights"
TRACK_JOIN = "join"


class BootCancelled(RuntimeError):
    """Raised inside a boot whose handle was cancelled before completion."""


class BootContext:
    """Mutable scratch space a plan's stages fill in as the boot progresses."""

    def __init__(self, dep, driver_name: str,
                 bucket_rows: Optional[int] = None, host=None) -> None:
        self.dep = dep
        self.driver_name = driver_name
        # coalesced batches boot a program compiled for this many token rows
        # (None = the deployment's base request shape)
        self.bucket_rows = bucket_rows
        # the Host this boot runs on (None for host-less boots, e.g. donor
        # setup in unit tests); fetch stages consult host.cache — the tiered
        # RAM cache from repro.core.scheduler — before the global stores
        self.host = host
        self.program_payload: Optional[bytes] = None
        self.program: Optional[Callable] = None
        # the host program-tier entry serving this boot, if any: after
        # DeserializeProgram runs, the loaded executable is parked back on it
        # so the next boot on this host skips the deserialize entirely
        self.program_entry: Any = None
        self.host_params: Any = None
        self.params: Any = None
        self.shared_weights: bool = False
        self.executor: Optional[Executor] = None
        # delta-restore accounting: bytes that actually moved for this boot
        # vs bytes already resident in the host chunk tier (dedup). Written
        # only by the weights track; read by the engine after the tracks join.
        self.bytes_fetched: int = 0
        self.bytes_deduped: int = 0
        # integrity trail (repro.core.blobstore): chunks re-hashed on read /
        # re-fetched from the store after a peer-side digest mismatch
        self.chunks_rehashed: int = 0
        self.chunks_refetched: int = 0
        # streamed-boot plumbing (set by the engine / StreamRestore):
        self.cancel: Optional[threading.Event] = None   # the handle's cancel
        # request deadline (repro.core.resilience.Deadline or None): stages
        # and chunk loops treat expiry like a cancel, so a boot that cannot
        # finish in time frees its host slot instead of completing uselessly
        self.deadline = None
        self.t_begin: float = 0.0
        self.gates: Optional[ReadinessGates] = None
        self.stream: Any = None                         # _StreamState
        self.split_program: bool = False                # head sub-program booted


class Stage:
    """One named, timed unit of boot work. Subclasses set ``name``/``track``.

    Stage instances are built fresh for every plan (one plan per boot), so a
    stage whose work depends on which path it took at runtime — host-tier hit,
    peer transfer, global-store fetch — may rebind ``self.name`` inside
    ``run`` and the engine records its duration under the name that actually
    happened (e.g. ``fetch_program_cached`` vs ``fetch_peer``). A stage may
    also set ``self.extra_s`` (sub-stage name -> seconds) inside ``run``; the
    engine records those splits beside the stage and carves them OUT of the
    stage's own time, so ``stage_s`` stays a partition of real work. The
    splits live on the stage instance, not the shared context — the engine
    reads them on the thread that ran the stage, so a concurrently-finishing
    stage on the other track can never consume them.
    """

    name: str = "stage"
    track: str = TRACK_JOIN

    def run(self, ctx: BootContext) -> None:
        raise NotImplementedError

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return f"<{type(self).__name__} name={self.name} track={self.track}>"


# --------------------------------------------------------------------- stages


class FetchProgram(Stage):
    """Acquire the serialized executable payload, cheapest source first.

    With a host tier available the lookup order is: host RAM cache (stage
    records as ``fetch_program_cached``), then a live peer's cache (records as
    ``fetch_peer``, charged the simulated peer-transfer cost), then the global
    image registry (``fetch_program``, charged the simulated store cost). Each
    miss path inserts the payload into the host tier, so the NEXT boot routed
    here — which the affinity scheduler makes likely — hits RAM.
    """

    name = "fetch_program"
    track = TRACK_PROGRAM

    # which artifact to fetch — FetchProgramHead points these at the AOT head
    def _key(self, ctx: BootContext) -> str:
        return ctx.dep.program_key(ctx.bucket_rows)

    def _payload(self, ctx: BootContext) -> Optional[bytes]:
        return ctx.dep.fetch_program_payload(ctx.bucket_rows)

    def run(self, ctx: BootContext) -> None:
        cache = getattr(ctx.host, "cache", None)
        if cache is None:
            payload = self._payload(ctx)
            if payload is None:                # deploy-verified in-process fallback
                ctx.program = ctx.dep.load_program(ctx.bucket_rows)
            else:
                ctx.program_payload = payload
            return
        key = self._key(ctx)
        entry = cache.get("program", key)
        if entry is not None:
            self.name = "fetch_program_cached"
            self._consume(ctx, entry)
            return
        entry = cache.fetch_from_peer("program", key)
        if entry is not None:
            self.name = "fetch_peer"
            self._consume(ctx, entry)
            return
        payload = self._payload(ctx)
        if payload is None:                    # deploy-verified in-process fallback
            ctx.program = ctx.dep.load_program(ctx.bucket_rows)
            return
        from repro.core.scheduler import ProgramArtifact
        entry = ProgramArtifact(payload)
        cache.fetch_from_store("program", key, entry, entry.nbytes)
        self._consume(ctx, entry)

    @staticmethod
    def _consume(ctx: BootContext, entry) -> None:
        if entry.loaded is not None:           # page-cache-warm: code already linked
            ctx.program = entry.loaded
        else:
            ctx.program_payload = entry.payload
            ctx.program_entry = entry


class FetchProgramHead(FetchProgram):
    """Streamed-boot program fetch: the AOT *head* sub-program when a verified
    split exists for this request shape, else exactly ``FetchProgram``.

    Sets ``ctx.split_program`` so Finalize knows to wrap the head in a
    ``SplitServe`` and to acquire the tail/fused programs in the background.
    Any failure on the split path degrades to the fused program — the split
    is a latency optimization, never a correctness dependency — except on a
    TPU, where the failure raises rather than hide a broken device path.
    """

    def __init__(self) -> None:
        self._split = False

    def _key(self, ctx: BootContext) -> str:
        if self._split:
            return ctx.dep.head_program_key()
        return super()._key(ctx)

    def _payload(self, ctx: BootContext) -> Optional[bytes]:
        if self._split:
            return ctx.dep.fetch_head_payload()
        return super()._payload(ctx)

    def run(self, ctx: BootContext) -> None:
        dep = ctx.dep
        self._split = bool(getattr(dep, "split_ok", False)) \
            and ctx.bucket_rows in (None, dep.base_rows)
        if not self._split:
            super().run(ctx)
            return
        try:
            super().run(ctx)
        except Exception as e:
            refuse_degrade_on_tpu("fetching the head sub-program", e)
            # degrade: forget any half-acquired head artifact, refetch fused
            self._split = False
            ctx.program = ctx.program_payload = ctx.program_entry = None
            super().run(ctx)
            return
        ctx.split_program = True


class DeserializeProgram(Stage):
    """Payload bytes -> loaded executable (the unikernel 'boot' proper)."""

    name = "deserialize_program"
    track = TRACK_PROGRAM

    def run(self, ctx: BootContext) -> None:
        if ctx.program is not None:            # fallback/tier-loaded program in hand
            return
        ctx.program = ctx.dep.cache.deserialize_program(ctx.program_payload)
        ctx.program_payload = None
        if ctx.program_entry is not None:
            # park the loaded executable on the host tier entry: subsequent
            # boots of this image on this host skip the deserialize (the
            # benign race — two boots both linking — just wastes one link)
            ctx.program_entry.loaded = ctx.program
            ctx.program_entry = None


class TraceCompile(Stage):
    """The Docker-stack tier: re-trace and re-compile. ``disk_cache=False``
    keeps the compile out of JAX's persistent cache (a full recompile);
    with True it may be a disk hit, as the process's cache settings allow."""

    name = "trace_compile"
    track = TRACK_PROGRAM

    def __init__(self, disk_cache: bool = True) -> None:
        self.disk_cache = disk_cache

    def run(self, ctx: BootContext) -> None:
        dep = ctx.dep
        fresh = jax.jit(lambda p, t: dep.serve_fn(p, t))   # fresh identity => re-trace
        lowered = fresh.lower(dep.abstract_params,
                              dep.abstract_tokens_for(ctx.bucket_rows))
        if self.disk_cache:
            ctx.program = lowered.compile()
        else:
            with persistent_cache_off():
                ctx.program = lowered.compile()


class RestoreWeightsHost(Stage):
    """Materialize host-side weights: delta restore from the chunk tier
    (v2 snapshots), snapshot mmap (v1), or generic parse+cast.

    With a host chunk tier and a chunked (v2) snapshot this is a DELTA
    restore: only chunks missing from the tier move — live peer first, global
    store last — and the stage records which path it took: a fully-memoized
    tree is ``restore_weights_cached``; otherwise the stage lands as
    ``restore_delta`` with ``fetch_chunks_peer``/``fetch_chunks_store``
    sub-timings, and the moved/skipped bytes go to
    ``Timeline.bytes_fetched``/``bytes_deduped``.
    """

    name = "restore_weights_host"
    track = TRACK_WEIGHTS

    def __init__(self, source: str = "snapshot", mmap: bool = True) -> None:
        assert source in ("snapshot", "generic")
        self.source = source
        self.mmap = mmap

    def run(self, ctx: BootContext) -> None:
        dep = ctx.dep
        if self.source != "snapshot":
            from repro.core.snapshot import load_generic_host
            ctx.host_params = load_generic_host(dep.generic_ckpt, dep.abstract_params)
            return
        cache = getattr(ctx.host, "cache", None)
        key = dep.image.key
        if dep.snapshots.blobs is not None and dep.snapshots.is_chunked(key):
            from repro.core.blobstore import delta_restore
            tree, stats = delta_restore(dep.snapshots, key, cache)
            if stats.source == "cached":
                self.name = "restore_weights_cached"
            elif cache is not None:
                self.name = "restore_delta"
                self.extra_s = {}
                if stats.t_peer_s > 0.0:
                    self.extra_s["fetch_chunks_peer"] = stats.t_peer_s
                if stats.t_store_s > 0.0:
                    self.extra_s["fetch_chunks_store"] = stats.t_store_s
            ctx.bytes_fetched += stats.bytes_fetched
            ctx.bytes_deduped += stats.bytes_deduped
            ctx.chunks_rehashed += stats.chunks_rehashed
            ctx.chunks_refetched += stats.chunks_refetched
            ctx.host_params = tree
            return
        tree = dep.snapshots.load_host(key, mmap=self.mmap)
        ctx.host_params = tree


class DevicePut(Stage):
    """Stream host leaves to the device in chunks, overlapping the host-side
    page-in of chunk k+1 with the transfer of chunk k (a read-ahead thread
    forces the mmap'd bytes resident while the device copy is in flight)."""

    name = "device_put"
    track = TRACK_WEIGHTS

    def __init__(self, chunk_bytes: int = 32 << 20, prefetch: int = 2) -> None:
        self.chunk_bytes = chunk_bytes
        self.prefetch = prefetch

    def run(self, ctx: BootContext) -> None:
        ctx.params = streamed_device_put(ctx.host_params, self.chunk_bytes,
                                         self.prefetch, cancel=ctx.cancel,
                                         deadline=ctx.deadline)
        ctx.host_params = None


class AliasDonor(Stage):
    """COW-clone path: alias the donor's program + weight buffers (no copy)."""

    name = "alias_donor"
    track = TRACK_WEIGHTS

    def __init__(self, donor: Executor) -> None:
        self.donor = donor

    def run(self, ctx: BootContext) -> None:
        ctx.program = self.donor.program
        ctx.params = self.donor.params
        ctx.shared_weights = True


class ReuseDonor(Stage):
    """Dispatch onto the resident donor itself — the platform-overhead floor."""

    name = "reuse_donor"
    track = TRACK_JOIN

    def __init__(self, donor: Executor) -> None:
        self.donor = donor

    def run(self, ctx: BootContext) -> None:
        ctx.executor = self.donor


class PoolCheckout(Stage):
    """Warm-pool hit: the executor was already checked out under the pool lock."""

    name = "pool_checkout"
    track = TRACK_JOIN

    def __init__(self, ex: Executor) -> None:
        self.ex = ex

    def run(self, ctx: BootContext) -> None:
        ctx.executor = self.ex


class FetchParked(Stage):
    """Paused-container path: program + host weights parked in DRAM at pause.

    Single-track on purpose: both artifacts are already in memory, so there is
    nothing to overlap — DevicePut (same track) consumes host_params after us.
    """

    name = "fetch_parked"
    track = TRACK_WEIGHTS

    def __init__(self, program: Callable, host: Any) -> None:
        self.program = program
        self.host = host

    def run(self, ctx: BootContext) -> None:
        ctx.program = self.program
        ctx.host_params = self.host


class Finalize(Stage):
    """Join point: assemble the Executor from the tracks' outputs."""

    name = "finalize"
    track = TRACK_JOIN

    def run(self, ctx: BootContext) -> None:
        if ctx.executor is not None:
            return
        ctx.executor = Executor(ctx.dep.image.key, ctx.driver_name, ctx.program,
                                ctx.params, shared_weights=ctx.shared_weights)


# ------------------------------------------------------------ streamed boot


class _StreamState:
    """Weights-stream handoff between StreamRestore and FinalizeStream."""

    def __init__(self) -> None:
        self.done = threading.Event()
        self.abort = threading.Event()         # dispose: stop a failed boot's stream
        self.error: Optional[BaseException] = None
        self.device_tree: Any = None
        self.bytes_fetched = 0
        self.bytes_deduped = 0
        self.chunks_rehashed = 0
        self.chunks_refetched = 0
        self.bytes_recorded = False            # True once ctx took the byte counts
        self.device_leaves: List[Any] = []


class StreamRestore(Stage):
    """Weights track of a streamed boot: restore + device_put leaves in
    first-use order on a background thread, opening a readiness gate per leaf.

    The stage itself returns once the deployment's *head* leaves are
    device-resident (the head sub-program's read set — every leaf for the real
    AOT split, a subset for synthetic programs); the remaining leaves keep
    streaming on the ``bootengine-stream`` thread and FinalizeStream's
    completion thread accounts them as ``restore_stream_tail_bg``. Works for
    both formats: v2 chunked snapshots via ``blobstore.stream_restore``
    (delta-aware: tier -> peer batch -> store), v1 ``.npy`` snapshots via
    ``SnapshotStore.iter_restore``.
    """

    name = "restore_stream_head"
    track = TRACK_WEIGHTS

    def run(self, ctx: BootContext) -> None:
        from repro.core.blobstore import RestoreAborted, stream_restore
        from repro.core.snapshot import _rebuild_structure
        dep = ctx.dep
        key = dep.image.key
        index = dep.snapshots.read_index(key)
        entries = index["leaves"]
        paths = [e["path"] for e in entries]
        path_set = set(paths)
        head = [p for p in getattr(dep, "head_leaves", ()) if p in path_set] \
            or list(paths)
        gates = ReadinessGates(paths, head)
        ctx.gates = gates
        state = _StreamState()
        ctx.stream = state
        cancel = ctx.cancel
        chunked = dep.snapshots.blobs is not None and dep.snapshots.is_chunked(key)
        cache = getattr(ctx.host, "cache", None)
        device_leaves: List[Any] = [None] * len(entries)
        state.device_leaves = device_leaves

        deadline = ctx.deadline

        def should_abort() -> bool:
            return state.abort.is_set() or \
                (cancel is not None and cancel.is_set()) or \
                (deadline is not None and deadline.expired())

        def on_leaf(i: int, path: str, leaf) -> None:
            device_leaves[i] = jax.device_put(leaf)
            gates.mark_ready(path)

        def worker() -> None:
            try:
                if chunked:
                    _tree, stats = stream_restore(dep.snapshots, key, cache,
                                                  on_leaf=on_leaf,
                                                  should_abort=should_abort)
                    state.bytes_fetched = stats.bytes_fetched
                    state.bytes_deduped = stats.bytes_deduped
                    state.chunks_rehashed = stats.chunks_rehashed
                    state.chunks_refetched = stats.chunks_refetched
                else:
                    for i, path, leaf in dep.snapshots.iter_restore(key):
                        if should_abort():
                            raise RestoreAborted(key)
                        on_leaf(i, path, leaf)
                ready = jax.block_until_ready(device_leaves)
                state.device_tree = _rebuild_structure(index["treedef"], ready)
            except BaseException as e:  # noqa: BLE001 - relayed via gates
                state.error = e
                gates.fail(e)
            finally:
                state.done.set()

        threading.Thread(target=worker, daemon=True,
                         name="bootengine-stream").start()

        if len(head) == len(paths):
            # the head needs every leaf (the real AOT split): nothing to
            # overlap with execution on the weights side — wait it out here
            # so the stage time reflects the actual critical path
            state.done.wait()
        else:
            try:
                gates.wait_leaves(head)
            except Exception:
                state.done.wait()      # surface the stream's own error below
        if state.error is not None:
            if isinstance(state.error, (RestoreAborted, BootCancelled)):
                if deadline is not None and deadline.expired():
                    from repro.core.resilience import DeadlineExceeded
                    raise DeadlineExceeded(f"stream deadline passed: {key}")
                raise BootCancelled(f"stream cancelled: {key}")
            raise state.error
        jax.block_until_ready([leaf for leaf in device_leaves
                               if leaf is not None])
        if state.done.is_set():
            ctx.bytes_fetched += state.bytes_fetched
            ctx.bytes_deduped += state.bytes_deduped
            ctx.chunks_rehashed += state.chunks_rehashed
            ctx.chunks_refetched += state.chunks_refetched
            state.bytes_recorded = True


def _acquire_program(cache, key: str,
                     payload_fn: Callable[[], bytes]) -> Callable:
    """Load an executable through the host program tier when one is attached
    (tier hit may be pre-linked; misses park the loaded executable back on the
    tier entry for the next boot), else deserialize the payload directly."""
    if cache is not None:
        entry = cache.get("program", key)
        if entry is None:
            entry = cache.fetch_from_peer("program", key)
        if entry is not None:
            if entry.loaded is None:
                entry.loaded = CompileCache.deserialize_program(entry.payload)
            return entry.loaded
        from repro.core.scheduler import ProgramArtifact
        payload = payload_fn()
        entry = ProgramArtifact(payload)
        cache.fetch_from_store("program", key, entry, entry.nbytes)
        entry.loaded = CompileCache.deserialize_program(payload)
        return entry.loaded
    return CompileCache.deserialize_program(payload_fn())


class FinalizeStream(Stage):
    """Readiness-gated join: finalize a (possibly PARTIAL) streamed executor.

    If the stream already delivered everything and the program track booted
    the fused program, this is plain Finalize. Otherwise the executor starts
    PARTIAL behind its gates and a ``bootengine-stream-complete`` thread
    finishes the boot: wait out the weight tail, acquire the tail sub-program
    (opening the SplitServe's tail gate) and the fused program (so a fully
    restored executor is eager-equivalent — split serving is only the
    cold-start bridge), swap them in via ``_complete_restore``, and patch
    every bound timeline with the background stages and the extended wall.
    """

    name = "finalize"
    track = TRACK_JOIN

    def run(self, ctx: BootContext) -> None:
        if ctx.executor is not None:
            return
        dep = ctx.dep
        gates, state = ctx.gates, ctx.stream
        assert gates is not None and state is not None, \
            "FinalizeStream requires StreamRestore in the plan"
        weights_done = state.done.is_set() and state.error is None
        params = state.device_tree if weights_done else None
        program: Callable = SplitServe(ctx.program, gates) \
            if ctx.split_program else ctx.program
        if weights_done and not ctx.split_program:
            gates.mark_complete()              # nothing left: READY immediately
            ctx.executor = Executor(dep.image.key, ctx.driver_name, program,
                                    params, gates=gates)
            return
        ex = Executor(dep.image.key, ctx.driver_name, program, params,
                      gates=gates)
        ctx.executor = ex
        host_cache = getattr(ctx.host, "cache", None)
        split = ctx.split_program

        def complete() -> None:
            t0 = now()
            try:
                state.done.wait()
                if state.error is not None:
                    raise state.error
                stage_extra: Dict[str, float] = {}
                if not weights_done:
                    stage_extra["restore_stream_tail_bg"] = now() - t0
                new_params = None if weights_done else state.device_tree
                fused = None
                if split:
                    t1 = now()
                    tail_prog = _acquire_program(
                        host_cache, dep.tail_program_key(),
                        lambda: dep.cache.read_program_bytes(
                            dep.tail_program_key()))
                    gates.set_tail_program(tail_prog)
                    # "fully restored" means eager-equivalent: the FUSED
                    # program must be resident before we declare completion
                    fused_payload = dep.fetch_program_payload(None)
                    if fused_payload is None:
                        fused = dep.load_program(None)
                    else:
                        fused = _acquire_program(host_cache, dep.image.key,
                                                 lambda: fused_payload)
                    stage_extra["deserialize_program_bg"] = now() - t1
                ex._complete_restore(params=new_params, program=fused)
                gates.mark_complete()
                bf = bd = cr = cf = 0
                if not state.bytes_recorded:
                    bf, bd = state.bytes_fetched, state.bytes_deduped
                    cr, cf = state.chunks_rehashed, state.chunks_refetched
                    state.bytes_recorded = True
                gates.finish_timelines(stage_extra, now() - t0,
                                       bytes_fetched=bf, bytes_deduped=bd,
                                       chunks_rehashed=cr, chunks_refetched=cf)
            except BaseException as e:  # noqa: BLE001 - relayed via gates
                gates.fail(e)

        threading.Thread(target=complete, daemon=True,
                         name="bootengine-stream-complete").start()


# ----------------------------------------------------------- streamed put


def streamed_device_put(host_tree: Any, chunk_bytes: int = 32 << 20,
                        prefetch: int = 2,
                        cancel: Optional[threading.Event] = None,
                        deadline=None) -> Any:
    """Chunked host->device transfer with read-ahead.

    Leaves are grouped into ~``chunk_bytes`` chunks; a producer thread forces
    each chunk's host bytes resident (``np.ascontiguousarray`` touches every
    mmap'd page) ``prefetch`` chunks ahead of the device_put consumer, so disk
    reads and PCIe/ICI transfers overlap instead of serializing.

    ``cancel`` (a boot handle's cancel event) is consulted per chunk on BOTH
    sides: the producer stops paging bytes in, the consumer stops issuing
    device transfers and raises :class:`BootCancelled` — a cancelled
    speculative pre-boot must not quietly complete the whole transfer.
    ``deadline`` (a resilience Deadline) is treated the same way per chunk,
    raising DeadlineExceeded so a too-slow transfer frees its slot.

    Backpressure contract: the bounded queue can NEVER silently drop a
    chunk. ``_put`` retries ``queue.Full`` forever while the consumer lives
    (``stop`` is set only in the consumer's ``finally``), so every chunk is
    delivered exactly once and in order; a False return — possible only
    after the consumer died — makes the producer stop entirely, which is
    deliberate shedding, not loss (tests/test_resilience.py pins this).
    """
    leaves, treedef = jax.tree.flatten(host_tree)
    if not leaves:
        return jax.tree.unflatten(treedef, leaves)

    chunks: List[List[int]] = [[]]
    acc = 0
    for i, leaf in enumerate(leaves):
        nbytes = getattr(leaf, "nbytes", 0)
        if chunks[-1] and acc + nbytes > chunk_bytes:
            chunks.append([])
            acc = 0
        chunks[-1].append(i)
        acc += nbytes

    q: "queue.Queue" = queue.Queue(maxsize=max(1, prefetch))
    stop = threading.Event()                       # consumer died: unwedge producer
    error: List[BaseException] = []

    def _put(item) -> bool:
        while not stop.is_set():
            try:
                q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def producer() -> None:
        try:
            for idxs in chunks:
                if cancel is not None and cancel.is_set():
                    return                         # cancelled: stop paging in
                if deadline is not None and deadline.expired():
                    return                         # too late: stop paging in
                if not _put([(i, np.ascontiguousarray(leaves[i])) for i in idxs]):
                    return                         # drop refs, don't pin the tree
        except BaseException as e:  # noqa: BLE001 - relayed to consumer
            error.append(e)
        finally:
            _put(None)

    threading.Thread(target=producer, daemon=True,
                     name="bootengine-readahead").start()

    out: List[Any] = [None] * len(leaves)
    try:
        while True:
            item = q.get()
            if item is None:
                break
            if cancel is not None and cancel.is_set():
                raise BootCancelled("cancelled mid device stream")
            if deadline is not None:
                deadline.check("device stream")
            for i, host_arr in item:
                out[i] = jax.device_put(host_arr)  # async dispatch: overlaps
    finally:
        stop.set()
    if error:
        raise error[0]
    out = jax.block_until_ready(out)
    return jax.tree.unflatten(treedef, out)


# --------------------------------------------------------------------- plans


class BootPlan:
    """An ordered, declarative list of stages (the driver's whole start logic).

    Stages on the program and weights tracks run concurrently, so a weights
    stage must never read context fields a program stage writes (and vice
    versa); cross-track products meet only at the JOIN stages.
    """

    def __init__(self, stages: Sequence[Stage]) -> None:
        self.stages: Tuple[Stage, ...] = tuple(stages)
        names = [s.name for s in self.stages]
        assert len(names) == len(set(names)), f"duplicate stage names: {names}"

    def by_track(self, track: str) -> List[Stage]:
        return [s for s in self.stages if s.track == track]

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return "BootPlan[" + " -> ".join(s.name for s in self.stages) + "]"


class BootResult:
    def __init__(self, executor: Executor, stage_s: Dict[str, float],
                 wall_s: float, bytes_fetched: int = 0,
                 bytes_deduped: int = 0, t_first_ready: float = 0.0,
                 chunks_rehashed: int = 0, chunks_refetched: int = 0) -> None:
        self.executor = executor
        self.stage_s = stage_s
        self.wall_s = wall_s
        self.bytes_fetched = bytes_fetched
        self.bytes_deduped = bytes_deduped
        self.chunks_rehashed = chunks_rehashed
        self.chunks_refetched = chunks_refetched
        # when the executor became dispatchable (PARTIAL counts) — for a
        # streamed boot this is the moment the head gates opened, while
        # t_boot_wall keeps growing until the background tail settles
        self.t_first_ready = t_first_ready


class BootHandle:
    """A cancellable in-flight boot (speculative pre-boot).

    ``claim()`` blocks for the result and marks it consumed; ``cancel()`` makes
    an unclaimed boot abort at the next stage boundary and exit any executor it
    already built — no leaked device memory either way.
    """

    def __init__(self, dep, driver_name: str) -> None:
        self.dep = dep
        self.driver_name = driver_name
        self._cancel = threading.Event()
        self._done = threading.Event()
        self._lock = threading.Lock()
        self._claimed = False
        self._result: Optional[BootResult] = None
        self._error: Optional[BaseException] = None
        # progress breadcrumb for claim-timeout diagnostics: the engine notes
        # each stage as it completes (benign race: worst case the message
        # under-reports by one stage)
        self.last_stage: Optional[str] = None

    # -- producer side (engine) ------------------------------------------
    def _note_stage(self, name: str) -> None:
        self.last_stage = name

    def _finish(self, result: Optional[BootResult],
                error: Optional[BaseException]) -> None:
        with self._lock:
            self._result, self._error = result, error
            self._done.set()
            # cancelled (or never claimed and already cancelled) => dispose
            if result is not None and self._cancel.is_set() and not self._claimed:
                result.executor.exit()

    # -- consumer side ----------------------------------------------------
    @property
    def cancelled(self) -> bool:
        return self._cancel.is_set()

    def done(self) -> bool:
        return self._done.is_set()

    def claim(self, timeout: float = 600.0) -> BootResult:
        """Take ownership of the boot's executor (exactly-once).

        ``timeout`` is configurable per call site (the agent threads its own
        ``claim_timeout_s`` through); the timeout error names the boot's last
        completed stage so a wedged boot is diagnosable from the message.
        """
        if not self._done.wait(timeout):
            raise TimeoutError(
                f"boot of {self.driver_name} did not complete within "
                f"{timeout:.1f}s (last completed stage: "
                f"{self.last_stage or 'none'})")
        with self._lock:
            if self._cancel.is_set():
                raise BootCancelled("boot was cancelled before claim")
            self._claimed = True
        if self._error is not None:
            raise self._error
        return self._result

    def cancel(self) -> None:
        """Abort an unclaimed boot; exits its executor if one was built."""
        with self._lock:
            if self._claimed:
                return
            self._cancel.set()
            result = self._result if self._done.is_set() else None
        if result is not None:
            result.executor.exit()


# -------------------------------------------------------------------- engine


class BootEngine:
    """Executes BootPlans: concurrent tracks, per-stage timing, cancellation."""

    def execute(self, plan: BootPlan, dep, tl: Timeline, driver_name: str,
                bucket_rows: Optional[int] = None, host=None) -> Executor:
        """Synchronous boot: run the plan, stamp ``tl``, return the executor.

        The request's deadline (if the gateway attached one to ``tl``) rides
        into the plan as cooperative cancellation: stage boundaries and chunk
        loops abort the boot the moment it can no longer finish in time.
        """
        result = self._run(plan, dep, driver_name, cancel=None,
                           bucket_rows=bucket_rows, host=host,
                           deadline=getattr(tl, "deadline", None))
        tl.record_boot(result.stage_s, result.wall_s,
                       bytes_fetched=result.bytes_fetched,
                       bytes_deduped=result.bytes_deduped,
                       t_first_ready=result.t_first_ready,
                       chunks_rehashed=result.chunks_rehashed,
                       chunks_refetched=result.chunks_refetched)
        return result.executor

    def launch(self, plan: BootPlan, dep, driver_name: str,
               bucket_rows: Optional[int] = None, host=None) -> BootHandle:
        """Speculative pre-boot: run the plan on a background thread."""
        handle = BootHandle(dep, driver_name)

        def run() -> None:
            try:
                result = self._run(plan, dep, driver_name, cancel=handle._cancel,
                                   bucket_rows=bucket_rows, host=host,
                                   on_stage=handle._note_stage)
            except BaseException as e:  # noqa: BLE001 - relayed via claim()
                handle._finish(None, e)
            else:
                handle._finish(result, None)

        threading.Thread(target=run, daemon=True, name="bootengine-preboot").start()
        return handle

    # ------------------------------------------------------------- internal
    def _run(self, plan: BootPlan, dep, driver_name: str,
             cancel: Optional[threading.Event],
             bucket_rows: Optional[int] = None, host=None,
             deadline=None, on_stage=None) -> BootResult:
        ctx = BootContext(dep, driver_name, bucket_rows=bucket_rows, host=host)
        stage_s: Dict[str, float] = {}
        timing_lock = threading.Lock()
        errors: List[BaseException] = []
        t_begin = now()
        ctx.cancel = cancel
        ctx.deadline = deadline
        ctx.t_begin = t_begin

        def run_track(stages: List[Stage]) -> None:
            try:
                for stage in stages:
                    if cancel is not None and cancel.is_set():
                        raise BootCancelled(f"cancelled before {stage.name}")
                    if deadline is not None:
                        deadline.check(f"boot stage {stage.name}")
                    t0 = now()
                    with span(BOOT_SPAN_PREFIX + stage.name):
                        stage.run(ctx)
                    dt = now() - t0
                    # sub-stage splits (e.g. restore_delta's chunk fetches)
                    # are carved OUT of the parent stage's time, so stage_s
                    # stays a partition of real work and sum(stage_s) - wall
                    # remains pure overlap; read from THIS stage's instance,
                    # on this track's thread — never from shared state
                    extras = getattr(stage, "extra_s", None)
                    with timing_lock:
                        if extras:
                            stage_s.update(extras)
                            dt = max(0.0, dt - sum(extras.values()))
                        stage_s[stage.name] = dt
                    if on_stage is not None:
                        on_stage(stage.name)
            except BaseException as e:  # noqa: BLE001 - re-raised below
                errors.append(e)

        program_track = plan.by_track(TRACK_PROGRAM)
        weights_track = plan.by_track(TRACK_WEIGHTS)
        if program_track and weights_track:
            # the tentpole overlap: program deserialize || weight restore
            t = threading.Thread(target=run_track, args=(weights_track,),
                                 daemon=True, name="bootengine-weights")
            t.start()
            run_track(program_track)
            t.join()
        else:
            run_track(program_track or weights_track)

        if not errors:
            run_track(plan.by_track(TRACK_JOIN))
        if errors:
            self._dispose(ctx)
            raise errors[0]
        assert ctx.executor is not None, f"plan built no executor: {plan}"
        return BootResult(ctx.executor, stage_s, now() - t_begin,
                          bytes_fetched=ctx.bytes_fetched,
                          bytes_deduped=ctx.bytes_deduped,
                          t_first_ready=now(),
                          chunks_rehashed=ctx.chunks_rehashed,
                          chunks_refetched=ctx.chunks_refetched)

    @staticmethod
    def _dispose(ctx: BootContext) -> None:
        """Drop everything a failed/cancelled boot materialized."""
        if ctx.stream is not None:
            ctx.stream.abort.set()             # stop an in-flight weight stream
        if ctx.executor is not None and not ctx.shared_weights \
                and ctx.executor.driver not in ("process", "fork-donor"):
            ctx.executor.exit()
        ctx.program = ctx.params = ctx.host_params = ctx.program_payload = None


ENGINE = BootEngine()
