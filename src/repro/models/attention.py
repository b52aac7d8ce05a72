"""GQA attention in three modes: full (train), prefill (returns KV), cached decode.

KV caches are head-major — [B, nkv, S, hd] contiguous, [P, nkv, page_size,
hd] paged — so the decode kernels read each head's [S, hd] slab as tiles in
the trailing pair of dims, and no decode step transposes the cache.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.dist.sharding import active_rules, constrain, current_mesh
from repro.kernels import ops
from repro.models.layers import bias_spec, dense_spec, positional


def attention_specs(cfg, dtype, stack: Tuple[int, ...] = ()):
    d, hd = cfg.d_model, cfg.resolved_head_dim
    nq, nkv = cfg.n_heads, cfg.n_kv_heads
    s = {
        "wq": dense_spec(d, nq * hd, ("embed", "heads_flat"), dtype, stack=stack),
        "wk": dense_spec(d, nkv * hd, ("embed", "kv_flat"), dtype, stack=stack),
        "wv": dense_spec(d, nkv * hd, ("embed", "kv_flat"), dtype, stack=stack),
        "wo": dense_spec(nq * hd, d, ("heads_flat", "embed"), dtype, stack=stack),
    }
    if cfg.qkv_bias:
        s["bq"] = bias_spec(nq * hd, "heads_flat", dtype, stack=stack)
        s["bk"] = bias_spec(nkv * hd, "kv_flat", dtype, stack=stack)
        s["bv"] = bias_spec(nkv * hd, "kv_flat", dtype, stack=stack)
    if cfg.mlp_bias:
        s["bo"] = bias_spec(d, None, dtype, stack=stack)
    return s


def _proj_q(cfg, p, x):
    B, S, _ = x.shape
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"])
    if "bq" in p:
        q = q + p["bq"]
    return q.reshape(B, S, cfg.n_heads, cfg.resolved_head_dim)


def _proj_kv(cfg, p, x):
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    k = jnp.einsum("bsd,dh->bsh", x, p["wk"])
    v = jnp.einsum("bsd,dh->bsh", x, p["wv"])
    if "bk" in p:
        k, v = k + p["bk"], v + p["bv"]
    return (k.reshape(B, S, cfg.n_kv_heads, hd), v.reshape(B, S, cfg.n_kv_heads, hd))


def _out(cfg, p, o):
    B, S = o.shape[:2]
    y = jnp.einsum("bsh,hd->bsd", o.reshape(B, S, -1), p["wo"])
    if "bo" in p:
        y = y + p["bo"]
    return constrain(y, "batch", "seq", "embed")


def attention_full(cfg, p: dict, x: jax.Array, positions: Optional[jax.Array], *,
                   causal: bool = True, kv_from: Optional[jax.Array] = None,
                   q_offset=0):
    """Full-sequence attention. kv_from: encoder output for cross-attention.

    Returns (y [B,S,d], (k, v)) — k/v handed back head-major ([B,nkv,S,hd])
    so prefill can fill the cache.
    """
    q = _proj_q(cfg, p, x)
    src = x if kv_from is None else kv_from
    k, v = _proj_kv(cfg, p, src)
    if kv_from is None and positions is not None:
        q = positional(cfg, q, positions)
        k = positional(cfg, k, positions)
    q = constrain(q, "batch", "seq", "heads", "head_dim")
    k = constrain(k, "batch", "kv_seq", "kv_heads", "head_dim")
    v = constrain(v, "batch", "kv_seq", "kv_heads", "head_dim")
    o = ops.attention(q, k, v, causal=causal and kv_from is None, q_offset=q_offset)
    return _out(cfg, p, o), (jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2))


def attention_decode(cfg, p: dict, x_t: jax.Array, k_cache: jax.Array,
                     v_cache: jax.Array, pos: jax.Array, *, cross: bool = False):
    """One-token attention against a cache.

    x_t: [B,1,d]; k_cache/v_cache: [B,nkv,S,hd]; pos: int32 scalar (next
    position, lock-step batch) or int32 [B] (per-row positions — the
    step-granular decode loop, where each slot sits at its own depth).
    Returns (y [B,1,d], k_cache', v_cache').
    """
    B = x_t.shape[0]
    q = _proj_q(cfg, p, x_t)                                          # [B,1,nq,hd]
    if not cross:
        if cfg.rope != "none":
            ppos = _decode_positions(cfg, B, pos)
            q = positional(cfg, q, ppos)
        k_t, v_t = _proj_kv(cfg, p, x_t)                              # [B,1,nkv,hd]
        if cfg.rope != "none":
            k_t = positional(cfg, k_t, ppos)
        if jnp.ndim(pos) == 0:
            k_cache = jax.lax.dynamic_update_slice(
                k_cache, jnp.swapaxes(k_t, 1, 2).astype(k_cache.dtype), (0, 0, pos, 0))
            v_cache = jax.lax.dynamic_update_slice(
                v_cache, jnp.swapaxes(v_t, 1, 2).astype(v_cache.dtype), (0, 0, pos, 0))
        else:
            rows = jnp.arange(B)
            # rows and pos are split by a slice: the indexed dims lead, [B,nkv,hd]
            k_cache = k_cache.at[rows, :, pos].set(k_t[:, 0].astype(k_cache.dtype))
            v_cache = v_cache.at[rows, :, pos].set(v_t[:, 0].astype(v_cache.dtype))
        length = pos + 1
    else:
        length = k_cache.shape[2]
    k_cache = constrain(k_cache, "batch", "kv_heads", "kv_seq", "head_dim")
    v_cache = constrain(v_cache, "batch", "kv_heads", "kv_seq", "head_dim")
    # distributed flash decoding when the cache sequence dim is mesh-sharded
    from repro.dist.flash_decode import decode_attention_seqsharded, seq_shard_axis
    rules, mesh = active_rules(), current_mesh()
    axis = seq_shard_axis(rules, mesh, k_cache.shape[2])
    if axis is not None:
        o = decode_attention_seqsharded(q[:, 0], k_cache, v_cache, length,
                                        mesh, axis)
    else:
        o = ops.decode_attention(q[:, 0], k_cache, v_cache, length)   # [B,nq,hd]
    return _out(cfg, p, o[:, None]), k_cache, v_cache


def attention_decode_paged(cfg, p: dict, x_t: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, page_table: jax.Array,
                           pos: jax.Array, layer: jax.Array):
    """One-token attention against a paged KV cache (continuous batching).

    x_t: [B,1,d]; k_pages/v_pages: [L, P, nkv, page_size, hd] (every layer's
    shared pool); page_table: [B, max_pages] s32; pos: [B] s32 per-row
    positions; layer: [] s32. Writes each row's new K/V into ``layer``'s pool,
    in its chain's page at ``pos`` (empty slots carry an all-null page table,
    so their writes land on the reserved null page 0), with one scatter into
    the whole pool, then attends through the page table in the same buffer:
    no layer's pool is sliced out. Returns (y [B,1,d], k_pages', v_pages').
    """
    B = x_t.shape[0]
    page_size = k_pages.shape[3]
    q = _proj_q(cfg, p, x_t)                                          # [B,1,nq,hd]
    if cfg.rope != "none":
        ppos = _decode_positions(cfg, B, pos)
        q = positional(cfg, q, ppos)
    k_t, v_t = _proj_kv(cfg, p, x_t)                                  # [B,1,nkv,hd]
    if cfg.rope != "none":
        k_t = positional(cfg, k_t, ppos)
    rows = jnp.arange(B)
    page = page_table[rows, pos // page_size]                         # [B]
    off = pos % page_size
    # one index (layer, page, head, off) per row and head, so that each update
    # is one contiguous [hd] row of the pool's own layout: with a [nkv, hd]
    # window the TPU compiler lays the pool out page-major for the scatter
    # and copies all of it to and from the kernel's layout in every layer
    heads = jnp.arange(k_pages.shape[2])[None, :]
    page, off = page[:, None], off[:, None]
    k_pages = k_pages.at[layer, page, heads, off].set(k_t[:, 0].astype(k_pages.dtype))
    v_pages = v_pages.at[layer, page, heads, off].set(v_t[:, 0].astype(v_pages.dtype))
    o = ops.paged_decode_attention(q[:, 0], k_pages, v_pages, page_table,
                                   pos + 1, layer)                    # [B,nq,hd]
    return _out(cfg, p, o[:, None]), k_pages, v_pages


def _decode_positions(cfg, batch: int, pos) -> jax.Array:
    base = jnp.asarray(pos, jnp.int32)
    if base.ndim == 0:
        base = jnp.broadcast_to(base, (batch, 1))
    else:
        base = base.reshape(batch, 1)
    if cfg.rope == "mrope":
        return jnp.broadcast_to(base[None], (3, batch, 1))
    return base
