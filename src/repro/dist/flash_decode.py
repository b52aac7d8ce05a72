"""Distributed flash decoding: LSE-merge over a sequence-sharded KV cache.

For long-context decode the KV cache is the dominant tensor; the
``serve_seqkv`` preset shards its *sequence* dim across the mesh so every
device holds a contiguous S/N slice.  Each shard runs the ordinary
flash-decoding inner loop (``kernels.ref.decode_attention`` with
``return_stats=True``) over its local slice, producing online-softmax partials
(m, l, acc); the shards then merge with the standard log-sum-exp combine

    M = max_i m_i;   l = sum_i l_i e^{m_i - M};   acc = sum_i acc_i e^{m_i - M}

which reconstructs the exact single-device softmax (same math the intra-device
block loop already uses, lifted to a psum/pmax across the mesh axis).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.dist.sharding import Rules


def seq_shard_axis(rules: Optional[Rules], mesh, seq_len: int) -> Optional[str]:
    """The mesh axis the active rules shard a ``kv_seq`` dim of ``seq_len``
    over, or None (replicated cache -> ordinary single-device decode path)."""
    if rules is None or mesh is None:
        return None
    part = rules.spec(("kv_seq",), (int(seq_len),))[0]
    if part is None:
        return None
    names = (part,) if isinstance(part, str) else tuple(part)
    if len(names) != 1:
        return None         # only single-axis sequence sharding is supported
    return names[0]


def decode_attention_seqsharded(q, k_cache, v_cache, length, mesh=None,
                                axis: Optional[str] = None, *,
                                block_kv: int = 1024):
    """Decode attention over a cache whose seq dim is sharded along ``axis``.

    q: [B, Hq, D]; k_cache, v_cache: [B, Hkv, S, D] (S divisible by the axis
    size); length: int32 [] or [B].  Returns [B, Hq, D], numerically matching
    ``kernels.ref.decode_attention`` on the unsharded cache.
    """
    from repro.kernels import ref   # deferred: kernels also import repro.dist

    if mesh is None or axis is None:
        raise ValueError("decode_attention_seqsharded needs a mesh and an axis")
    n_shards = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]
    B, S = k_cache.shape[0], k_cache.shape[2]
    if S % n_shards != 0:
        raise ValueError(f"cache seq {S} not divisible by {axis}={n_shards}")
    lengths = jnp.broadcast_to(jnp.asarray(length, jnp.int32), (B,))

    def shard_body(qb, kb, vb, lb):
        s_local = kb.shape[2]
        offset = jax.lax.axis_index(axis) * s_local
        local_len = jnp.clip(lb - offset, 0, s_local)
        m, l, acc = ref.decode_attention(qb, kb, vb, local_len,
                                         block_kv=block_kv, return_stats=True)
        g_m = jax.lax.pmax(m, axis)
        w = jnp.exp(m - g_m)                     # 0 for empty shards (m=-inf)
        l_g = jax.lax.psum(l * w, axis)
        acc_g = jax.lax.psum(acc * w[..., None], axis)
        l_safe = jnp.where(l_g == 0, 1.0, l_g)
        out = acc_g / l_safe[..., None]          # [B, Hkv, G, D]
        return out.reshape(qb.shape).astype(qb.dtype)

    fn = jax.shard_map(
        shard_body, mesh=mesh,
        in_specs=(P(None, None, None), P(None, None, axis, None),
                  P(None, None, axis, None), P(None)),
        out_specs=P(None, None, None), check_vma=False)
    return fn(q, k_cache, v_cache, lengths)
