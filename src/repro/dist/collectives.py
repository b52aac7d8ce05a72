"""Compressed collectives: int8 wire codec, error feedback, all-reduce.

Cross-pod gradient traffic rides DCI links an order of magnitude slower than
in-pod ICI, so the ``pod`` axis all-reduce goes over the wire in int8: each
shard quantizes (symmetric, per-tensor fp32 scale), all-gathers the int8
payload + scales, and dequantizes locally — 4x less wire than fp32 psum for
a bounded (<1/127 of amax) elementwise error.  :class:`ErrorFeedback` keeps
the quantization residual and folds it into the next step's transmission
(1-bit-Adam / EF-SGD style), so the *time-averaged* transmitted gradient is
unbiased even though each individual message is quantized.
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp


# ------------------------------------------------------------------ int8 codec

def quantize_int8(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Symmetric per-tensor int8: returns (q int8, scale fp32 scalar)."""
    xf = jnp.asarray(x, jnp.float32)
    amax = jnp.max(jnp.abs(xf))
    scale = jnp.maximum(amax, 1e-30) / 127.0
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_int8(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


# -------------------------------------------------------------- error feedback

class ErrorFeedback(NamedTuple):
    """Carries the un-transmitted quantization residual between steps."""

    residual: jax.Array

    @classmethod
    def init(cls, like: jax.Array) -> "ErrorFeedback":
        return cls(jnp.zeros(jnp.shape(like), jnp.float32))


def ef_compress(x: jax.Array, ef: ErrorFeedback
                ) -> Tuple[jax.Array, jax.Array, ErrorFeedback]:
    """Quantize (x + residual); the new residual is what the wire dropped."""
    t = jnp.asarray(x, jnp.float32) + ef.residual
    q, scale = quantize_int8(t)
    return q, scale, ErrorFeedback(t - dequantize_int8(q, scale))


# ----------------------------------------------------------------- all-reduce

def compressed_allreduce(x: jax.Array, axis_name: str, *,
                         mean: bool = True) -> jax.Array:
    """int8-wire all-reduce (mean by default) along ``axis_name``.

    Must run inside ``shard_map`` (it uses named-axis collectives).  Only the
    int8 payload and the scalar scales cross the wire; the reduction itself
    happens post-dequantize in fp32 on every shard.
    """
    q, scale = quantize_int8(x)
    qs = jax.lax.all_gather(q, axis_name)                 # [N, ...] int8 wire
    ss = jax.lax.all_gather(scale, axis_name)             # [N] fp32 scales
    vals = qs.astype(jnp.float32) * ss.reshape((-1,) + (1,) * q.ndim)
    total = jnp.sum(vals, axis=0)
    if mean:
        total = total / qs.shape[0]
    return total.astype(jnp.asarray(x).dtype)
