"""Pallas TPU flash-decoding: one query token vs a deep KV cache.

Decode attention is bandwidth-bound (one pass over the KV cache per token, almost
no compute), so the kernel's whole job is streaming K/V through VMEM exactly once
with online-softmax state in scratch. Grid: (B, Hkv, kv_blocks) — kv innermost and
sequential, so (m, l, acc) scratch carries across the KV sweep per (batch, kv-head);
all G = Hq/Hkv query heads of the group ride in one [G, D] block (MXU-friendly for
GQA: the [G, D] x [D, block_kv] score matmul).

The cache is head-major, [B, Hkv, S, D], so each K/V block is a [block_kv, D]
tile in the trailing pair of dims, which is what Mosaic tiles. Lengths ride
as a scalar-prefetch operand in SMEM (positions >= length are dead — cache
slots not yet written). A ragged cache depth (S % block_kv != 0) is
handled the same way, inside the kernel: the grid rounds up and the tail
block's out-of-range positions fall under the mask. No host-side jnp.pad of
the caches — that was a whole-cache copy per decoded token. The tail block's
out-of-range K/V lanes are backed by unspecified memory (interpret mode fills
them with NaN), so V is zeroed under the mask before the PV dot; the score
mask is a select, so NaN K lanes never survive either.

Oracle: repro.kernels.ref.decode_attention.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30
DEFAULT_BLOCK_KV = 512


def _dec_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                block_kv: int, n_kv_blocks: int, s_max: int):
    b = pl.program_id(0)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0, :, :].astype(jnp.float32)                   # [G, D]
    k = k_ref[0, 0].astype(jnp.float32)                         # [bk, D]
    v = v_ref[0, 0].astype(jnp.float32)
    scale = 1.0 / jnp.sqrt(jnp.float32(q.shape[-1]))
    length = jnp.minimum(len_ref[b], s_max)

    kv_pos = ik * block_kv + jax.lax.broadcasted_iota(
        jnp.int32, (q.shape[0], block_kv), 1)                   # [G, bk]
    valid = kv_pos < length
    # the ragged tail block reads past S: those V lanes hold unspecified
    # values (NaN in interpret mode) and 0 * NaN would poison the PV dot —
    # zero them; the score mask below is a select, so K needs no scrub
    col = ik * block_kv + jax.lax.broadcasted_iota(
        jnp.int32, (block_kv, 1), 0)                            # [bk, 1]
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

    @pl.when(ik == n_kv_blocks - 1)
    def _finalize():
        l = l_scr[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0, :, :] = (acc_scr[...] / l_safe).astype(o_ref.dtype)


def decode_attention(q, k_cache, v_cache, length, *,
                     block_kv: int = DEFAULT_BLOCK_KV, interpret: bool = False):
    """q: [B, Hq, D]; k_cache, v_cache: [B, Hkv, S, D]; length: [] or [B] ->
    [B, Hq, D]."""
    B, Hq, D = q.shape
    _, Hkv, S, _ = k_cache.shape
    assert Hq % Hkv == 0
    G = Hq // Hkv
    block_kv = min(block_kv, max(8, 1 << (S - 1).bit_length()))

    # ceil grid: the tail block is masked inside the kernel — padding the
    # caches here would copy the whole KV cache once per decoded token
    nk = pl.cdiv(S, block_kv)
    lengths = jnp.broadcast_to(jnp.asarray(length, jnp.int32), (B,))
    qg = q.reshape(B, Hkv, G, D)

    kernel = functools.partial(_dec_kernel, block_kv=block_kv, n_kv_blocks=nk,
                               s_max=S)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,                 # lengths
        grid=(B, Hkv, nk),
        in_specs=[
            pl.BlockSpec((1, 1, G, D), lambda b, h, ik, ln: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_kv, D), lambda b, h, ik, ln: (b, h, ik, 0)),
            pl.BlockSpec((1, 1, block_kv, D), lambda b, h, ik, ln: (b, h, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D), lambda b, h, ik, ln: (b, h, 0, 0)),
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
    )(lengths, qg, k_cache, v_cache)
    return out.reshape(B, Hq, D)
