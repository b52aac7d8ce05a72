"""Kernel-layer micro-bench: jit'd reference implementations on CPU.

Wall-clock here is CPU (the TPU path is the Pallas kernels, validated in
interpret mode by tests/test_kernels.py); the derived column reports achieved
CPU GFLOP/s as a sanity signal and the analytic FLOPs used by the roofline.
"""
import time

import jax
import jax.numpy as jnp

from benchmarks.common import emit
from repro.kernels import ref


def _time(fn, *args, iters=3):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
        jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def run() -> None:
    key = jax.random.PRNGKey(0)

    # flash attention (prefill-like): B1 S1024 H8/2 D64
    B, S, Hq, Hkv, D = 1, 1024, 8, 2, 64
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, S, Hq, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, Hkv, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, Hkv, D), jnp.float32)
    f = jax.jit(lambda q, k, v: ref.flash_attention(q, k, v))
    dt = _time(f, q, k, v)
    flops = 4 * Hq * D * B * S * (S + 1) / 2
    emit("kernel/flash_attention_1k", dt * 1e6, f"GFLOPs={flops/dt/1e9:.1f}")

    # decode attention: B8 S4096 cache
    B, S = 8, 4096
    kc = jax.random.normal(ks[1], (B, Hkv, S, D), jnp.float32)   # head-major
    vc = jax.random.normal(ks[2], (B, Hkv, S, D), jnp.float32)
    qd = jax.random.normal(ks[0], (B, Hq, D), jnp.float32)
    f = jax.jit(lambda q, k, v: ref.decode_attention(q, k, v, S))
    dt = _time(f, qd, kc, vc)
    gb = 2 * B * S * Hkv * D * 4 / 1e9
    emit("kernel/decode_attention_4k", dt * 1e6, f"GBps={gb/dt:.1f}")

    # paged decode attention on the SAME logical cache: scatter the 4k cache
    # into shuffled pages and pay the table gather — the derived column is the
    # paged/contiguous wall ratio (the rent the page indirection charges)
    page_size, max_pages = 64, S // 64
    perm = jax.random.permutation(ks[2], B * max_pages) + 1
    table = perm.reshape(B, max_pages).astype(jnp.int32)
    P = 1 + B * max_pages

    def paged(cache):                # [B, Hkv, S, D] -> [P, Hkv, page_size, D]
        rows = jnp.swapaxes(cache.reshape(B, Hkv, max_pages, page_size, D), 1, 2)
        return jnp.zeros((P, Hkv, page_size, D), jnp.float32).at[
            table.reshape(-1)].set(rows.reshape(B * max_pages, Hkv, page_size, D))

    kp, vp = paged(kc)[None], paged(vc)[None]        # a one-layer pool, layer 0
    lengths = jnp.full((B,), S, jnp.int32)
    f = jax.jit(lambda q, k, v, t, ln: ref.paged_decode_attention(q, k, v, t, ln, 0))
    dt_paged = _time(f, qd, kp, vp, table, lengths)
    emit("kernel/paged_decode_attention_4k", dt_paged * 1e6,
         f"GBps={gb/dt_paged:.1f};vs_contig={dt_paged/dt:.2f}x")

    # selective scan: B2 S512 Di256 Ds16
    B, S, Di, Ds = 2, 512, 256, 16
    x = jax.random.normal(ks[0], (B, S, Di))
    dtt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, Di)))
    al = jax.random.normal(ks[2], (Di, Ds)) * 0.5
    bm = jax.random.normal(ks[0], (B, S, Ds))
    cm = jax.random.normal(ks[1], (B, S, Ds))
    dsk = jnp.ones((Di,))
    f = jax.jit(lambda *a: ref.selective_scan(*a)[0])
    dt = _time(f, x, dtt, al, bm, cm, dsk)
    emit("kernel/selective_scan", dt * 1e6,
         f"tok_per_s={B*S/dt:.0f}")

    # mlstm chunked: B2 S512 H4 Dk64 Dv64
    B, S, H, Dk, Dv = 2, 512, 4, 64, 64
    q = jax.random.normal(ks[0], (B, S, H, Dk))
    k2 = jax.random.normal(ks[1], (B, S, H, Dk))
    v2 = jax.random.normal(ks[2], (B, S, H, Dv))
    ig = jax.random.normal(ks[0], (B, S, H))
    fg = jax.random.normal(ks[1], (B, S, H)) + 1
    f = jax.jit(lambda *a: ref.mlstm_chunked(*a)[0])
    dt = _time(f, q, k2, v2, ig, fg)
    emit("kernel/mlstm_chunked", dt * 1e6, f"tok_per_s={B*S/dt:.0f}")
