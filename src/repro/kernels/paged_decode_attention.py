"""Pallas TPU paged flash-decoding: one query token vs a page-table KV cache.

Continuous batching (repro.core.decode) stores KV state in fixed-size pages
drawn from a shared pool (repro.core.paging) instead of one contiguous
[B, S] cache per request — so requests can join and leave the step loop
without ever compacting or copying cache memory. This kernel consumes that
layout directly:

    q:          [B, Hq, D]           one query token per sequence (GQA)
    k_pages:    [L, P, Hkv, page_size, D]   every layer's page pool (head-major)
    v_pages:    [L, P, Hkv, page_size, D]
    page_table: [B, max_pages] s32   page ids of each sequence's chain
    lengths:    [B] s32              live positions (0 = empty slot)
    layer:      [] s32               which layer's pool to read

Grid: (B, Hkv, max_pages) — the page axis innermost and sequential, so the
online-softmax scratch (m, l, acc) carries across one sequence's page sweep
exactly like the contiguous kernel. Pages are head-major so each K/V block
is a [page_size, D] tile in the trailing pair of dims, which is what Mosaic
tiles. The page table, lengths and layer ride as scalar-prefetch operands:
each K/V block's HBM address is computed from ``(layer, table[b, ip])``
inside the BlockSpec index_map, so the gather costs no copy — not of the
pages, and not of one layer's pool out of the stacked one — and touches
only the pages a sequence actually owns a table entry for. The step program
carries the stacked pools through its layer loop and updates them in place;
a single-layer caller passes ``pool[None]`` and layer 0. Unused table slots
point at page 0 — the pool's reserved null
page — whose positions are >= length and die under the score mask; V is
zeroed under the same mask before the PV dot so whatever the null page holds
(including NaN) can never ride a 0 * x product into the accumulator.

Oracle: repro.kernels.ref.paged_decode_attention (gather + contiguous math).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_kernel(tbl_ref, len_ref, layer_ref, q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, page_size: int, n_pages: int):
    b = pl.program_id(0)
    ip = pl.program_id(2)

    @pl.when(ip == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0, :, :].astype(jnp.float32)                   # [G, D]
    k = k_ref[0, 0, 0].astype(jnp.float32)                      # [ps, D]
    v = v_ref[0, 0, 0].astype(jnp.float32)
    scale = 1.0 / jnp.sqrt(jnp.float32(q.shape[-1]))
    length = len_ref[b]

    kv_pos = ip * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (q.shape[0], page_size), 1)                  # [G, ps]
    valid = kv_pos < length
    # null-page / dead-region V may hold anything (the pool is recycled);
    # zero it under the mask so 0 * garbage never reaches the accumulator
    col = ip * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (page_size, 1), 0)                           # [ps, 1]
    v = jnp.where(col < length, v, 0.0)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_scr[...]                                          # [G, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new) * valid
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(ip == n_pages - 1)
    def _finalize():
        l = l_scr[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0, :, :] = (acc_scr[...] / l_safe).astype(o_ref.dtype)


def paged_decode_attention(q, k_pages, v_pages, page_table, lengths, layer, *,
                           interpret: bool = False):
    """q: [B, Hq, D]; k_pages, v_pages: [L, P, Hkv, page_size, D];
    page_table: [B, max_pages] s32; lengths: [] or [B] s32; layer: [] s32
    -> [B, Hq, D]."""
    B, Hq, D = q.shape
    _, _, Hkv, page_size, _ = k_pages.shape
    assert Hq % Hkv == 0
    G = Hq // Hkv
    max_pages = page_table.shape[1]
    lengths = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (B,))
    page_table = page_table.astype(jnp.int32)
    layer = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))
    qg = q.reshape(B, Hkv, G, D)

    kernel = functools.partial(_paged_kernel, page_size=page_size,
                               n_pages=max_pages)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,                 # page_table, lengths, layer
        grid=(B, Hkv, max_pages),
        in_specs=[
            pl.BlockSpec((1, 1, G, D),
                         lambda b, h, ip, tbl, ln, ly: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, 1, page_size, D),
                         lambda b, h, ip, tbl, ln, ly: (ly[0], tbl[b, ip], h, 0, 0)),
            pl.BlockSpec((1, 1, 1, page_size, D),
                         lambda b, h, ip, tbl, ln, ly: (ly[0], tbl[b, ip], h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D),
                               lambda b, h, ip, tbl, ln, ly: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        interpret=interpret,
    )(page_table, lengths, layer, qg, k_pages, v_pages)
    return out.reshape(B, Hq, D)
