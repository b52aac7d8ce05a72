"""Deploy-time image building — the analogue of ``fn deploy`` + the IncludeOS
``boot`` build (paper Sec IV-A: 3.5 s unikernel build vs 9-10 s Docker build).

``deploy()`` turns a FunctionSpec into a ready Deployment:
  1. build the model and the single-purpose serve program (prefill + K greedy
     decode steps fused into ONE compiled callable — nothing generic),
  2. AOT-compile and serialize it into the CompileCache — plus, for streamed
     boots, the head/tail split of the same program (``make_head_fn`` /
     ``make_tail_fn``), accepted only if bit-identical to the fused output,
  3. run the one-time first-touch profiling pass (``first_use_order``) and
     write the weight snapshot with the order persisted in its manifest
     (pre-laid-out; chunked v2 when the store has a blob store attached),
     plus the generic checkpoint (the slow-path comparison),
  4. record the ImageManifest.

Invariants: every serialized image is verified by loading and running it once
at deploy time — a CPU host whose AOT loader rejects the blob degrades to the
in-process program (flagged ``aot_verified: false``) instead of crashing
executors, while on a TPU every such degrade raises; compiles happen at
deploy time only (bucket shapes included via ``ensure_bucket``, once per
bucket, ever) — no request ever pays a compile;
``program_key``/``bucket_image_key`` are the single source of truth shared
with the scheduler's affinity probes and tier inserts.
"""
from __future__ import annotations

import dataclasses
import re
import threading
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core.artifact import ExecutorImage, FunctionSpec, ImageManifest
from repro.core.compile_cache import (
    CompileCache, decode_admit_key, decode_step_key, head_key, refuse_degrade_on_tpu,
    tail_key,
)
from repro.core.metrics import now
from repro.core.snapshot import SnapshotStore, save_generic_checkpoint
from repro.dist.sharding import abstract_state
from repro.models import build_model
from repro.models.model import Model


def make_serve_fn(model: Model, spec: FunctionSpec) -> Callable:
    """The function body: prefill the prompt, then greedy-decode K tokens."""
    capacity = spec.prompt_len + spec.decode_steps

    def serve(params, tokens):
        logits, cache = model.prefill(params, {"tokens": tokens}, capacity=capacity)

        def step(carry, _):
            lg, c = carry
            tok = jnp.argmax(lg, axis=-1)[:, None].astype(jnp.int32)
            lg2, c2 = model.decode(params, c, tok)
            return (lg2, c2), tok[:, 0]

        (_, _), toks = jax.lax.scan(step, (logits, cache), None,
                                    length=spec.decode_steps)
        return jnp.moveaxis(toks, 0, 1)                      # [B, decode_steps]

    return serve


def make_head_fn(model: Model, spec: FunctionSpec) -> Callable:
    """Streamed-boot head: prefill + the FIRST response token.

    The moment this sub-program's output is ready the response has begun —
    that is the TTFR stamp. It also returns the prefill logits and KV cache
    so the tail can resume the exact fused computation.
    """
    capacity = spec.prompt_len + spec.decode_steps

    def head(params, tokens):
        logits, cache = model.prefill(params, {"tokens": tokens}, capacity=capacity)
        tok0 = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
        return tok0, logits, cache

    return head


def make_tail_fn(model: Model, spec: FunctionSpec) -> Callable:
    """Streamed-boot tail: the decode scan of ``make_serve_fn``, verbatim.

    Takes the head's prefill logits + cache and re-derives token 0 inside the
    scan exactly like the fused program does, so head+tail output is
    bit-identical to the fused serve program (verified at deploy time).
    """

    def tail(params, logits, cache):
        def step(carry, _):
            lg, c = carry
            tok = jnp.argmax(lg, axis=-1)[:, None].astype(jnp.int32)
            lg2, c2 = model.decode(params, c, tok)
            return (lg2, c2), tok[:, 0]

        (_, _), toks = jax.lax.scan(step, (logits, cache), None,
                                    length=spec.decode_steps)
        return jnp.moveaxis(toks, 0, 1)                      # [B, decode_steps]

    return tail


def make_admit_fn(model: Model, max_pages: int, page_size: int) -> Callable:
    """Continuous-batching admit: prefill ONE request into its reserved pages.

    Prefills at the pool-table capacity (``max_pages * page_size``) so the
    head-major [L, nkv, capacity, hd] cache reshapes exactly into
    ``max_pages`` page-sized rows, then scatters those rows to the chain's
    device pages via ``page_ids`` ([max_pages] s32, padded with the null
    page — rows past the chain's reservation land on page 0, which is
    garbage territory by invariant). Returns the prompt's next-token logits ([V] — this is the
    request's FIRST response token, the TTFR stamp) plus the updated pools.
    """
    capacity = max_pages * page_size

    def admit(params, tokens, k_pages, v_pages, page_ids):
        logits, cache = model.prefill(params, {"tokens": tokens},
                                      capacity=capacity)
        inner = cache["inner"]

        def scatter(pool, new):
            # [L, nkv, capacity, hd] -> [L, max_pages, nkv, page_size, hd]
            L, nkv, _, hd = new[:, 0].shape
            rows = new[:, 0].reshape(L, nkv, max_pages, page_size, hd)
            rows = jnp.swapaxes(rows, 1, 2)
            return pool.at[:, page_ids].set(rows.astype(pool.dtype))

        return logits[0], scatter(k_pages, inner["k"]), scatter(v_pages,
                                                                inner["v"])

    return admit


def make_step_fn(model: Model) -> Callable:
    """Continuous-batching step: one token for every resident slot at once."""

    def step(params, k_pages, v_pages, page_table, pos, token):
        return model.decode_paged(params, k_pages, v_pages, page_table, pos,
                                  token)

    return step


def jit_decode_programs(model: Model, max_pages: int, page_size: int):
    """The admit and step programs, jitted with their K and V pools donated:
    each output pool is its input's buffer, updated in place, and a call
    deletes the pools it was given. Returns (admit, step)."""
    admit = jax.jit(make_admit_fn(model, max_pages, page_size),
                    donate_argnums=(2, 3))
    step = jax.jit(make_step_fn(model), donate_argnums=(1, 2))
    return admit, step


@dataclasses.dataclass
class DecodeBundle:
    """The two fixed-shape programs the decode step loop runs, plus geometry."""

    slots: int                     # batch rows of the step program
    page_size: int                 # tokens per KV page
    n_pages: int                   # device pool size INCLUDING the null page
    max_pages: int                 # page-table width (pages per chain, max)
    admit: Callable                # (params, tokens[1,S], k, v, ids) -> (logits[V], k, v)
    step: Callable                 # (params, k, v, table, pos, tok) -> (logits[B,V], k, v)
    aot_verified: bool = True      # False: host rejected the blobs, in-process
    # both programs' output pools alias their donated input pools: a call
    # updates the pools in place, and deletes the arrays it was given
    pools_in_place: bool = False


# an entry of the HLO module header's input_output_alias={...}:
# "{out}: (param, {}, may-alias)" for a top-level output and parameter
_ALIAS_ENTRY = re.compile(r"\{(\d+)\}: \((\d+), \{\}")


def pools_alias(compiled) -> bool:
    """Whether outputs 1 and 2 (the K and V pools of admit and step) alias
    two distinct parameters in a ``jax.stages.Compiled``'s optimized HLO, so
    the program updates the pools in place."""
    header = compiled.as_text().split("\n", 1)[0]
    aliases = dict(_ALIAS_ENTRY.findall(header))       # output -> parameter
    return "1" in aliases and "2" in aliases and aliases["1"] != aliases["2"]


def first_use_order(fn: Callable, abstract_params: Any, *abstract_args) -> List[str]:
    """Trace ``fn`` once and return param-leaf paths in first-touch order.

    A deploy-time-only profiling pass (no compile, no execution): the jaxpr's
    equation list is a topological order that tracks trace order, so walking
    the equations and recording when each param invar is first consumed gives
    the order execution will first need each leaf — embedding and early layers
    before late layers before the decode-only weights. Leaves the trace never
    touches (dead params) are appended in ordinal order so the result is
    always a permutation of every leaf path.

    The walk descends into nested jaxprs (pjit/scan/cond carry params as
    invars of inner jaxprs) when the inner signature matches 1:1; otherwise
    the whole equation counts as the consumption point — coarse but safe.
    """
    closed = jax.make_jaxpr(fn)(abstract_params, *abstract_args)
    flat, _ = jax.tree_util.tree_flatten_with_path(abstract_params)
    paths = [jax.tree_util.keystr(p) for p, _ in flat]
    n = len(paths)
    # map jaxpr invars back to leaf ordinals by object identity — Var/Literal
    # hashability differs across jax versions, id() does not
    top_pos = {id(v): i for i, v in enumerate(closed.jaxpr.invars[:n])}
    seen: List[int] = []
    seen_set: set = set()

    def visit(jaxpr, pos) -> None:
        for eqn in jaxpr.eqns:
            inner_jaxprs = []
            for val in eqn.params.values():
                vals = val if isinstance(val, (list, tuple)) else (val,)
                for v in vals:
                    # ClosedJaxpr forwards .eqns but not .invars — unwrap first
                    if hasattr(v, "jaxpr") and hasattr(v.jaxpr, "eqns"):
                        inner_jaxprs.append(v.jaxpr)
                    elif hasattr(v, "eqns") and hasattr(v, "invars"):
                        inner_jaxprs.append(v)
            recursed = False
            for inner in inner_jaxprs:
                if len(inner.invars) != len(eqn.invars):
                    continue
                sub_pos = dict(pos)
                for iv, ov in zip(inner.invars, eqn.invars):
                    if id(ov) in pos:
                        sub_pos[id(iv)] = pos[id(ov)]
                visit(inner, sub_pos)
                recursed = True
            if recursed:
                continue
            for v in eqn.invars:
                i = pos.get(id(v))
                if i is not None and i not in seen_set:
                    seen_set.add(i)
                    seen.append(i)

    visit(closed.jaxpr, top_pos)
    order = seen + [i for i in range(n) if i not in seen_set]
    return [paths[i] for i in order]


@dataclasses.dataclass
class Deployment:
    """Everything a driver needs to start executors for one function."""

    spec: FunctionSpec
    image: ExecutorImage
    model: Model
    serve_fn: Callable
    cache: CompileCache
    snapshots: SnapshotStore
    generic_ckpt: str
    abstract_params: Any           # SDS tree (template for jit / checkpoint load)
    abstract_tokens: jax.ShapeDtypeStruct
    build_seconds: float
    fallback_program: Any = None   # set when deploy-time verification rejects the
                                   # serialized blob (XLA:CPU AOT loader can refuse
                                   # executables on feature-mismatched hosts)
    # streamed-boot metadata (deploy-time profiling / split build):
    first_use_order: List[str] = dataclasses.field(default_factory=list)
    head_leaves: List[str] = dataclasses.field(default_factory=list)
    split_ok: bool = False         # head/tail sub-programs published + verified
                                   # bit-identical to the fused program
    # shape-bucket program registry (repro.core.batching): token-row count ->
    # in-process fallback program, or None when the serialized image is good.
    _buckets: Dict[int, Any] = dataclasses.field(default_factory=dict, repr=False)
    _bucket_lock: Any = dataclasses.field(default_factory=threading.Lock, repr=False)
    # continuous-batching decode bundle (built on demand by ensure_decode)
    _decode_bundle: Optional[DecodeBundle] = dataclasses.field(
        default=None, repr=False)

    @property
    def name(self) -> str:
        return self.spec.name

    @property
    def base_rows(self) -> int:
        """Token rows of the unbatched request shape (the deploy-time program)."""
        return self.spec.batch_size

    def bucket_image_key(self, rows: int) -> str:
        # single source of truth with the scheduler's affinity keys: routing
        # probes and tier inserts must agree on this exact string
        from repro.core.scheduler import program_artifact_key
        return program_artifact_key(self.image.key, rows)

    def abstract_tokens_for(self, rows: Optional[int]) -> jax.ShapeDtypeStruct:
        if rows is None or rows == self.base_rows:
            return self.abstract_tokens
        return jax.ShapeDtypeStruct((rows, self.spec.prompt_len), jnp.int32)

    def ensure_bucket(self, rows: int) -> None:
        """Compile + serialize the serve program for a coalesced batch shape.

        One compile per bucket, ever — every subsequent batch rounded to this
        bucket boots the cached image exactly like the base program. If the
        host's AOT loader rejects serialized blobs (see ``fallback_program``),
        the in-process compiled program is kept instead.
        """
        if rows == self.base_rows:
            return
        with self._bucket_lock:
            if rows in self._buckets:
                return
            bucketed = jax.jit(self.serve_fn).lower(
                self.abstract_params, self.abstract_tokens_for(rows)).compile()
            fallback = bucketed
            if self.fallback_program is None:
                bkey = self.bucket_image_key(rows)
                try:
                    self.cache.put_compiled(bkey, bucketed)
                    self.cache.load_program(bkey)      # verify it deserializes
                    fallback = None
                except Exception as e:
                    refuse_degrade_on_tpu(f"AOT load of bucket {rows}", e)
                    fallback = bucketed
            self._buckets[rows] = fallback

    def ensure_decode(self, slots: int, page_size: int,
                      max_pages: Optional[int] = None,
                      n_pages: Optional[int] = None) -> DecodeBundle:
        """Compile + serialize the continuous-batching decode bundle.

        Two programs, once per deployment, ever: the admit program (prefill
        one request into its reserved pages, yielding its first token) and
        the step program (one token for every resident slot). Both are fixed
        shape — ``slots`` batch rows, a ``[slots, max_pages]`` page table, a
        pool of ``n_pages`` pages — so no request ever pays a compile, same
        contract as ``ensure_bucket``. Defaults: ``max_pages`` covers the
        deploy spec's worst case (prompt + decode budget), ``n_pages`` gives
        every slot a full reservation plus the null page. Both programs take
        the pools donated (``jit_decode_programs``); ``pools_in_place``
        records that both alias them, and on a TPU a bundle that does not
        raises.
        """
        if max_pages is None:
            worst = self.spec.prompt_len + self.spec.decode_steps
            max_pages = -(-worst // page_size)
        if n_pages is None:
            n_pages = 1 + slots * max_pages
        with self._bucket_lock:
            if self._decode_bundle is not None:
                return self._decode_bundle
            model = self.model
            admit_fn, step_fn = jit_decode_programs(model, max_pages, page_size)
            pool = abstract_state(model.page_pool_specs(n_pages, page_size))
            a_kp, a_vp = pool["k_pages"], pool["v_pages"]
            a_tok1 = jax.ShapeDtypeStruct((1, self.spec.prompt_len), jnp.int32)
            a_ids = jax.ShapeDtypeStruct((max_pages,), jnp.int32)
            a_table = jax.ShapeDtypeStruct((slots, max_pages), jnp.int32)
            a_pos = jax.ShapeDtypeStruct((slots,), jnp.int32)
            a_tok = jax.ShapeDtypeStruct((slots, 1), jnp.int32)
            admit_c = admit_fn.lower(
                self.abstract_params, a_tok1, a_kp, a_vp, a_ids).compile()
            step_c = step_fn.lower(
                self.abstract_params, a_kp, a_vp, a_table, a_pos,
                a_tok).compile()
            in_place = pools_alias(admit_c) and pools_alias(step_c)
            if not in_place:
                refuse_degrade_on_tpu(
                    "in-place pools of the decode bundle",
                    RuntimeError("the admit or step program copies its pools"))
            admit_p, step_p, verified = admit_c, step_c, False
            if self.fallback_program is None:
                try:
                    self.cache.put_compiled(decode_admit_key(self.image.key),
                                            admit_c)
                    self.cache.put_compiled(decode_step_key(self.image.key),
                                            step_c)
                    admit_p = self.cache.load_program(
                        decode_admit_key(self.image.key))
                    step_p = self.cache.load_program(
                        decode_step_key(self.image.key))
                    verified = True
                except Exception as e:
                    refuse_degrade_on_tpu("AOT load of the decode bundle", e)
                    admit_p, step_p = admit_c, step_c
            self._decode_bundle = DecodeBundle(
                slots=slots, page_size=page_size, n_pages=n_pages,
                max_pages=max_pages, admit=admit_p, step=step_p,
                aot_verified=verified, pools_in_place=in_place)
            return self._decode_bundle

    def load_program(self, bucket_rows: Optional[int] = None) -> Callable:
        """The unikernel 'boot': deserialize from the image registry, or serve the
        deploy-verified in-process program if this host rejected the blob."""
        fallback = self._program_fallback(bucket_rows)
        if fallback is not None:
            return fallback
        return self.cache.load_program(self.program_key(bucket_rows))

    def fetch_program_payload(self, bucket_rows: Optional[int] = None) -> Optional[bytes]:
        """Serialized-program bytes for the boot pipeline's FetchProgram stage,
        or None when this host degraded to the in-process fallback program."""
        if self._program_fallback(bucket_rows) is not None:
            return None
        return self.cache.read_program_bytes(self.program_key(bucket_rows))

    def program_key(self, bucket_rows: Optional[int] = None) -> str:
        """Registry/cache key of the program artifact for a request shape —
        the unit of placement affinity (repro.core.scheduler) and of the
        per-host program tier."""
        if bucket_rows is None or bucket_rows == self.base_rows:
            return self.image.key
        return self.bucket_image_key(bucket_rows)

    def head_program_key(self) -> str:
        return head_key(self.image.key)

    def tail_program_key(self) -> str:
        return tail_key(self.image.key)

    def fetch_head_payload(self) -> Optional[bytes]:
        """Serialized head sub-program bytes, or None when no verified split
        exists (the streamed boot then degrades to the fused program)."""
        if not self.split_ok:
            return None
        return self.cache.read_program_bytes(self.head_program_key())

    def _program_fallback(self, bucket_rows: Optional[int]) -> Optional[Callable]:
        if bucket_rows is None or bucket_rows == self.base_rows:
            return self.fallback_program
        with self._bucket_lock:
            if bucket_rows not in self._buckets:
                raise KeyError(
                    f"bucket {bucket_rows} not built for {self.name}; "
                    "call Deployment.ensure_bucket first")
            return self._buckets[bucket_rows]

    def example_tokens(self, seed: int = 0) -> np.ndarray:
        rng = np.random.default_rng(seed)
        cfg = self.model.cfg
        return rng.integers(0, cfg.vocab_size,
                            (self.spec.batch_size, self.spec.prompt_len),
                            dtype=np.int32)


def deploy(spec: FunctionSpec, cache: CompileCache, snapshots: SnapshotStore,
           work_dir: str) -> Deployment:
    t_begin = now()
    cfg = get_config(spec.arch)
    if spec.reduced:
        cfg = cfg.reduced()
    capacity = spec.prompt_len + spec.decode_steps
    model = build_model(cfg, max_seq=capacity)
    serve_fn = make_serve_fn(model, spec)

    params = model.init(jax.random.PRNGKey(spec.seed))
    specs = model.param_specs()
    abstract_params = abstract_state(specs)
    abstract_tokens = jax.ShapeDtypeStruct((spec.batch_size, spec.prompt_len), jnp.int32)

    key = spec.cache_key()
    # 1) AOT program -> compile cache ("unikernel image build")
    compiled = jax.jit(serve_fn).lower(abstract_params, abstract_tokens).compile()
    program_bytes = cache.put_compiled(key, compiled)
    # deploy-time verification: boot the image once and run it. XLA:CPU's AOT
    # loader intermittently rejects executables whose compile-time machine
    # features differ from the host; a verified-bad image degrades to the
    # in-process program (flagged in the manifest) instead of crashing executors.
    fallback_program = None
    probe_tokens = jnp.zeros((spec.batch_size, spec.prompt_len), jnp.int32)
    try:
        probe = cache.load_program(key)
        fused_out = jax.block_until_ready(probe(params, probe_tokens))
    except Exception as e:
        refuse_degrade_on_tpu("AOT load of the serve program", e)
        fallback_program = compiled
        fused_out = jax.block_until_ready(compiled(params, probe_tokens))
    fused_out = np.asarray(fused_out)

    # 1b) split image for streamed boots: AOT head (prefill + first token) and
    # tail (decode scan) published under derived keys, accepted only if their
    # composed output is bit-identical to the fused program on a real probe.
    split_ok = False
    if fallback_program is None:
        try:
            head_c = jax.jit(make_head_fn(model, spec)).lower(
                abstract_params, abstract_tokens).compile()
            _tok0_s, logits_s, cache_s = jax.eval_shape(
                make_head_fn(model, spec), abstract_params, abstract_tokens)
            tail_c = jax.jit(make_tail_fn(model, spec)).lower(
                abstract_params, logits_s, cache_s).compile()
            cache.put_compiled(head_key(key), head_c)
            cache.put_compiled(tail_key(key), tail_c)
            head_p = cache.load_program(head_key(key))
            tail_p = cache.load_program(tail_key(key))
            tok0, logits, kv = head_p(params, probe_tokens)
            split_out = np.asarray(
                jax.block_until_ready(tail_p(params, logits, kv)))
            tok0 = np.asarray(jax.block_until_ready(tok0))
            split_ok = bool(np.array_equal(split_out, fused_out)
                            and np.array_equal(tok0[:, 0], fused_out[:, 0]))
            if not split_ok:
                raise ValueError("head+tail tokens differ from the fused program")
        except Exception as e:
            refuse_degrade_on_tpu("the head/tail split", e)
            split_ok = False
    if not split_ok:
        cache.evict(head_key(key))
        cache.evict(tail_key(key))

    # 1c) one-time traced profiling pass: which leaf does execution touch
    # first? Persisted into the snapshot manifest so restore streams leaves
    # in first-use order (never needed for correctness — gates guarantee that)
    try:
        use_order = first_use_order(serve_fn, abstract_params, abstract_tokens)
    except Exception:
        use_order = []
    flat_paths, _ = jax.tree_util.tree_flatten_with_path(abstract_params)
    all_paths = [jax.tree_util.keystr(p) for p, _ in flat_paths]
    # the AOT head's XLA signature consumes the whole params tree, so serving
    # the first request needs every leaf device-resident; subset gating is
    # exercised by synthetic (plain-callable) programs in tests
    head_leaves = list(all_paths) if split_ok else []

    # 2) pre-laid-out snapshot + generic checkpoint comparison path
    snapshot_bytes = snapshots.save(key, params, first_use_order=use_order)
    generic_ckpt = f"{work_dir}/{key}_generic.npz"
    save_generic_checkpoint(generic_ckpt, params)

    build_seconds = now() - t_begin
    extra: Dict[str, Any] = {"aot_verified": fallback_program is None,
                             "split_serve": split_ok,
                             "first_use_order_len": len(use_order)}
    if snapshots.blobs is not None:
        # chunked (v2) snapshot: record the manifest geometry so reports can
        # show dedup (unique chunk bytes in the store vs logical bytes)
        index = snapshots.read_index(key)
        extra.update(snapshot_format=2,
                     snapshot_chunks=sum(len(e["chunks"]) for e in index["leaves"]),
                     chunk_bytes=index["chunk_bytes"])
    manifest = ImageManifest(
        key=key, function=spec.name,
        program_bytes=program_bytes, snapshot_bytes=snapshot_bytes,
        param_count=int(sum(np.prod(s.shape) for s in jax.tree.leaves(abstract_params))),
        built_at=now(), build_seconds=build_seconds,
        extra=extra,
    )
    cache.put_manifest(key, manifest)
    image = ExecutorImage(manifest=manifest, spec=spec)
    return Deployment(
        spec=spec, image=image, model=model, serve_fn=serve_fn,
        cache=cache, snapshots=snapshots, generic_ckpt=generic_ckpt,
        abstract_params=abstract_params, abstract_tokens=abstract_tokens,
        build_seconds=build_seconds, fallback_program=fallback_program,
        first_use_order=use_order, head_leaves=head_leaves, split_ok=split_ok,
    )
