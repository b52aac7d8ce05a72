"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs pure-jnp oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.mlstm import mlstm
from repro.kernels.selective_scan import selective_scan

KEY = jax.random.PRNGKey(42)


def tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 2e-5


# ------------------------------------------------------------- flash attention

FLASH_CASES = [
    # B, Sq, Skv, Hq, Hkv, D, causal, q_offset
    (2, 128, 128, 4, 2, 64, True, 0),
    (1, 64, 64, 8, 8, 32, True, 0),
    (2, 64, 192, 4, 1, 128, True, 128),      # GQA=4, prefill continuation
    (1, 128, 128, 2, 2, 64, False, 0),       # bidirectional (whisper encoder)
    (1, 96, 96, 6, 3, 64, True, 0),          # non-power-of-two seq (padding)
]


@pytest.mark.parametrize("case", FLASH_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_vs_ref(case, dtype):
    B, Sq, Skv, Hq, Hkv, D, causal, qoff = case
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, Sq, Hq, D), dtype)
    k = jax.random.normal(ks[1], (B, Skv, Hkv, D), dtype)
    v = jax.random.normal(ks[2], (B, Skv, Hkv, D), dtype)
    out = flash_attention(q, k, v, causal=causal, q_offset=qoff, interpret=True)
    exp = ref.flash_attention(q, k, v, causal=causal, q_offset=qoff)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), atol=tol(dtype))


def test_flash_ref_vs_naive_oracle():
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (2, 37, 8, 16))
    k = jax.random.normal(ks[1], (2, 37, 2, 16))
    v = jax.random.normal(ks[2], (2, 37, 2, 16))
    a = ref.flash_attention(q, k, v, block_q=16, block_kv=8)
    b = ref.naive_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


def test_flash_attention_grads_match_ref():
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 64, 4, 32))
    k = jax.random.normal(ks[1], (1, 64, 2, 32))
    v = jax.random.normal(ks[2], (1, 64, 2, 32))

    def loss_pal(q_, k_, v_):
        return jnp.sum(flash_attention(q_, k_, v_, interpret=True) ** 2)

    def loss_ref(q_, k_, v_):
        return jnp.sum(ref.flash_attention(q_, k_, v_) ** 2)

    g1 = jax.grad(loss_pal, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


# ------------------------------------------------------------ decode attention

DECODE_CASES = [
    (2, 256, 8, 2, 64), (3, 100, 4, 4, 32), (1, 512, 16, 8, 128), (2, 64, 2, 1, 64),
]


@pytest.mark.parametrize("case", DECODE_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_vs_ref(case, dtype):
    B, S, Hq, Hkv, D = case
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (B, Hq, D), dtype)
    kc = jax.random.normal(ks[1], (B, Hkv, S, D), dtype)     # head-major cache
    vc = jax.random.normal(ks[2], (B, Hkv, S, D), dtype)
    length = jax.random.randint(ks[3], (B,), 1, S + 1)
    out = decode_attention(q, kc, vc, length, block_kv=64, interpret=True)
    exp = ref.decode_attention(q, kc, vc, length)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), atol=tol(dtype))


def test_decode_ref_vs_naive_oracle():
    ks = jax.random.split(KEY, 3)
    B, S, Hq, Hkv, D = 2, 50, 4, 2, 16
    q = jax.random.normal(ks[0], (B, Hq, D))
    kc = jax.random.normal(ks[1], (B, Hkv, S, D))            # head-major cache
    vc = jax.random.normal(ks[2], (B, Hkv, S, D))
    got = ref.decode_attention(q, kc, vc, jnp.int32(S), block_kv=16)
    exp = ref.naive_attention(q[:, None], jnp.swapaxes(kc, 1, 2),
                              jnp.swapaxes(vc, 1, 2), causal=False)[:, 0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp), atol=2e-5)


# -------------------------------------------------------------- selective scan

SCAN_CASES = [(2, 96, 64, 16), (1, 33, 128, 8), (2, 128, 256, 4)]


@pytest.mark.parametrize("case", SCAN_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_selective_scan_vs_ref(case, dtype):
    B, S, Di, Ds = case
    ks = jax.random.split(KEY, 7)
    x = jax.random.normal(ks[0], (B, S, Di), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, Di))).astype(dtype)
    a_log = jax.random.normal(ks[2], (Di, Ds)) * 0.5
    b = jax.random.normal(ks[3], (B, S, Ds), dtype)
    c = jax.random.normal(ks[4], (B, S, Ds), dtype)
    d_skip = jax.random.normal(ks[5], (Di,))
    h0 = jax.random.normal(ks[6], (B, Di, Ds))
    y1, h1 = ref.selective_scan(x, dt, a_log, b, c, d_skip, h0=h0, block=8)
    y2, h2 = selective_scan(x, dt, a_log, b, c, d_skip, h0=h0,
                            block_di=min(Di, 64), chunk=16, interpret=True)
    np.testing.assert_allclose(np.asarray(y1, np.float32), np.asarray(y2, np.float32),
                               atol=10 * tol(dtype))
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), atol=10 * tol(dtype))


def test_selective_scan_ref_vs_step_oracle():
    B, S, Di, Ds = 2, 19, 8, 4
    ks = jax.random.split(KEY, 6)
    x = jax.random.normal(ks[0], (B, S, Di))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, Di)))
    a_log = jax.random.normal(ks[2], (Di, Ds)) * 0.5
    b = jax.random.normal(ks[3], (B, S, Ds))
    c = jax.random.normal(ks[4], (B, S, Ds))
    d_skip = jax.random.normal(ks[5], (Di,))
    y, hf = ref.selective_scan(x, dt, a_log, b, c, d_skip, block=4)
    h = jnp.zeros((B, Di, Ds))
    for t in range(S):
        yt, h = ref.mamba_step(x[:, t], dt[:, t], a_log, b[:, t], c[:, t], d_skip, h)
        np.testing.assert_allclose(np.asarray(y[:, t]), np.asarray(yt), atol=1e-4)
    np.testing.assert_allclose(np.asarray(hf), np.asarray(h), atol=1e-4)


# ----------------------------------------------------------------------- mlstm

MLSTM_CASES = [(2, 96, 2, 32, 64), (1, 50, 4, 16, 16), (2, 64, 1, 64, 128)]


@pytest.mark.parametrize("case", MLSTM_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_mlstm_vs_ref(case, dtype):
    B, S, H, Dk, Dv = case
    ks = jax.random.split(KEY, 5)
    q = jax.random.normal(ks[0], (B, S, H, Dk), dtype)
    k = jax.random.normal(ks[1], (B, S, H, Dk), dtype)
    v = jax.random.normal(ks[2], (B, S, H, Dv), dtype)
    ig = jax.random.normal(ks[3], (B, S, H))
    fg = jax.random.normal(ks[4], (B, S, H)) + 1.0
    h1, st1 = ref.mlstm_chunked(q, k, v, ig, fg, block=16)
    h2, st2 = mlstm(q, k, v, ig, fg, chunk=16, interpret=True)
    np.testing.assert_allclose(np.asarray(h1, np.float32), np.asarray(h2, np.float32),
                               atol=10 * tol(dtype))
    for a, b in zip(st1, st2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=10 * tol(dtype))


def test_mlstm_ref_vs_recurrent_oracle():
    B, S, H, Dk, Dv = 2, 29, 2, 8, 12
    ks = jax.random.split(KEY, 5)
    q = jax.random.normal(ks[0], (B, S, H, Dk))
    k = jax.random.normal(ks[1], (B, S, H, Dk))
    v = jax.random.normal(ks[2], (B, S, H, Dv))
    ig = jax.random.normal(ks[3], (B, S, H))
    fg = jax.random.normal(ks[4], (B, S, H)) + 1.0
    h1, st1 = ref.mlstm_chunked(q, k, v, ig, fg, block=8)
    h2, st2 = ref.mlstm_recurrent(q, k, v, ig, fg)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), atol=1e-4)
    for a, b in zip(st1, st2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_mlstm_state_continuation():
    """Chunked-with-carried-state == one long chunked pass."""
    B, S, H, Dk, Dv = 1, 64, 2, 16, 16
    ks = jax.random.split(KEY, 5)
    q = jax.random.normal(ks[0], (B, S, H, Dk))
    k = jax.random.normal(ks[1], (B, S, H, Dk))
    v = jax.random.normal(ks[2], (B, S, H, Dv))
    ig = jax.random.normal(ks[3], (B, S, H))
    fg = jax.random.normal(ks[4], (B, S, H)) + 1.0
    h_full, _ = ref.mlstm_chunked(q, k, v, ig, fg, block=16)
    half = S // 2
    h1, st = ref.mlstm_chunked(q[:, :half], k[:, :half], v[:, :half],
                               ig[:, :half], fg[:, :half], block=16)
    h2, _ = ref.mlstm_chunked(q[:, half:], k[:, half:], v[:, half:],
                              ig[:, half:], fg[:, half:], state=st, block=16)
    got = jnp.concatenate([h1, h2], axis=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(h_full), atol=1e-4)
