"""Pallas TPU flash attention (GQA, causal, q_offset) with BlockSpec VMEM tiling.

Grid: (batch, q_heads, q_blocks, kv_blocks) — the kv axis is innermost, so on TPU it
executes sequentially per (b, h, iq) and the online-softmax state (m, l, acc) lives
in VMEM scratch across those steps (HBM->VMEM traffic is exactly one pass over K/V
per q block — the flash property). The kernel runs on head-major [B, H, S, D]
operands: Mosaic tiles the last two block dims, so a [block, D] tile must be
the trailing pair. The public [B, S, H, D] signature stays; the wrapper
transposes once per call (prefill is compute-bound, the copy is small next to
the S^2 score work). The MXU sees [block_q, D] x [D, block_kv] and
[block_q, block_kv] x [block_kv, D] matmuls; blocks default to 128x128 to match the
128x128 systolic array, with fp32 accumulation.

Backward: custom_vjp whose bwd is the VJP of the chunked jnp reference (recompute,
flash-style memory) — correctness-first; a fused bwd kernel is a further TPU
optimization, noted in DESIGN.md.

Oracle: repro.kernels.ref.flash_attention / naive_attention (tests sweep shapes and
dtypes in interpret mode).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref

NEG_INF = -1e30
DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_KV = 128


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
               causal: bool, q_offset: int, skv: int, block_q: int, block_kv: int,
               n_kv_blocks: int):
    ik = pl.program_id(3)
    iq = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)                     # [bq, D]
    k = k_ref[0, 0].astype(jnp.float32)                     # [bk, D]
    v = v_ref[0, 0].astype(jnp.float32)
    scale = 1.0 / jnp.sqrt(jnp.float32(q.shape[-1]))

    q_pos = q_offset + iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_kv), 0)
    kv_pos = ik * block_kv + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_kv), 1)
    valid = kv_pos < skv
    if causal:
        valid = valid & (kv_pos <= q_pos)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_scr[...]                                      # [bq, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new) * valid
    corr = jnp.exp(m_prev - m_new)                           # [bq, 1]
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(ik == n_kv_blocks - 1)
    def _finalize():
        l = l_scr[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[...] / l_safe).astype(o_ref.dtype)


def _pad_seq(x, block, axis):
    pad = (-x.shape[axis]) % block
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def flash_attention_fwd_only(q, k, v, *, causal: bool = True, q_offset: int = 0,
                             block_q: int = DEFAULT_BLOCK_Q,
                             block_kv: int = DEFAULT_BLOCK_KV,
                             interpret: bool = False):
    """q: [B,Sq,Hq,D]; k,v: [B,Skv,Hkv,D] -> [B,Sq,Hq,D] (no autodiff rule)."""
    B, Sq, Hq, D = q.shape
    _, Skv, Hkv, _ = k.shape
    assert Hq % Hkv == 0
    G = Hq // Hkv
    block_q = min(block_q, max(8, 1 << (Sq - 1).bit_length()))
    block_kv = min(block_kv, max(8, 1 << (Skv - 1).bit_length()))

    # head-major [B, H, S, D]: the [block, D] tile is the trailing pair.
    # Transpose before padding, so the compiler shares the k/v transpose with
    # a caller that keeps head-major k/v (prefill filling its KV cache).
    qp = _pad_seq(jnp.swapaxes(q, 1, 2), block_q, 2)
    kp = _pad_seq(jnp.swapaxes(k, 1, 2), block_kv, 2)
    vp = _pad_seq(jnp.swapaxes(v, 1, 2), block_kv, 2)
    nq = qp.shape[2] // block_q
    nk = kp.shape[2] // block_kv

    kernel = functools.partial(
        _fa_kernel, causal=causal, q_offset=q_offset, skv=Skv,
        block_q=block_q, block_kv=block_kv, n_kv_blocks=nk)

    out = pl.pallas_call(
        kernel,
        grid=(B, Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_kv, D), lambda b, h, iq, ik: (b, h // G, ik, 0)),
            pl.BlockSpec((1, 1, block_kv, D), lambda b, h, iq, ik: (b, h // G, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D), lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct(qp.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        interpret=interpret,
    )(qp, kp, vp)
    return jnp.swapaxes(out, 1, 2)[:, :Sq]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, causal: bool, q_offset: int, interpret: bool):
    return flash_attention_fwd_only(q, k, v, causal=causal, q_offset=q_offset,
                                    interpret=interpret)


def _flash_fwd(q, k, v, causal, q_offset, interpret):
    return _flash(q, k, v, causal, q_offset, interpret), (q, k, v)


def _flash_bwd(causal, q_offset, interpret, res, g):
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: ref.flash_attention(q_, k_, v_, causal=causal,
                                               q_offset=q_offset), q, k, v)
    return vjp(g)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, *, causal: bool = True, q_offset=0,
                    interpret: bool = False):
    """Differentiable entry point (Pallas fwd, recompute-reference bwd)."""
    if not isinstance(q_offset, int):
        raise TypeError("flash_attention needs a static int q_offset, got "
                        f"{type(q_offset).__name__}")
    return _flash(q, k, v, causal, q_offset, interpret)
