"""Differential tests for paged decode attention (satellites of PR 10).

Three implementations must agree for every tested shape: the Pallas paged
kernel (interpret mode), the pure-jnp paged oracle (gather + contiguous
math), and the contiguous decode path run on a hand-gathered cache. Coverage:
GQA group sizes, bf16/fp32, ragged lengths, length-0 rows, lengths that
straddle a page boundary, shuffled page assignments, and the null-page
convention (garbage — including NaN — in unreferenced pages never leaks).
Every case runs on stacked pools [L, P, Hkv, page_size, D]: one layer (a
single-layer caller's ``pool[None]``) and several, read at more than one
layer index, with every other layer's pool filled with NaN so a read of the
wrong layer cannot pass.

Also pins the satellite fix to the contiguous kernel: a ragged cache depth is
masked in-kernel, never handled by a host-side ``jnp.pad`` of the caches.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.paging import NULL_PAGE
from repro.kernels import decode_attention as da
from repro.kernels import paged_decode_attention as pda
from repro.kernels import ref

KEY = jax.random.PRNGKey(42)


def tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 2e-5


# (layers in the pool, the layer read): one layer, and two reads of three
LAYOUTS = [(1, 0), (3, 1), (3, 2)]
LAYOUT_IDS = ["L1", "L3-layer1", "L3-layer2"]


def _build_paged(key, B, max_pages, page_size, Hq, Hkv, D, dtype, *,
                 lengths, null_fill=0.0, shuffle_seed=None, map_dead=True,
                 layout=(1, 0)):
    """Scatter a contiguous head-major [B, Hkv, S, D] cache into layer
    ``layout[1]`` of stacked head-major page pools [L, P, Hkv, page_size, D],
    ``L = layout[0]``; every other layer's pool holds NaN.

    Returns (q, k_cache, v_cache, k_pages, v_pages, table, lengths_arr).
    ``map_dead=False`` leaves table entries past each row's live pages at the
    null page, which itself is filled with ``null_fill``.
    """
    S = max_pages * page_size
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, Hq, D), dtype)
    k_cache = jax.random.normal(kk, (B, Hkv, S, D), dtype)
    v_cache = jax.random.normal(kv, (B, Hkv, S, D), dtype)

    P = 1 + B * max_pages
    ids = np.arange(1, P)
    if shuffle_seed is not None:
        ids = np.random.RandomState(shuffle_seed).permutation(ids)
    k_pages = jnp.full((P, Hkv, page_size, D), null_fill, dtype)
    v_pages = jnp.full((P, Hkv, page_size, D), null_fill, dtype)

    def page_rows(cache, live):      # [Hkv, S, D] -> [live, Hkv, page_size, D]
        rows = cache.reshape(Hkv, max_pages, page_size, D)
        return jnp.swapaxes(rows, 0, 1)[:live]

    table = np.full((B, max_pages), NULL_PAGE, np.int32)
    for b in range(B):
        live = max_pages if map_dead else -(-int(lengths[b]) // page_size)
        pages = ids[b * max_pages:b * max_pages + live]
        table[b, :live] = pages
        k_pages = k_pages.at[pages].set(page_rows(k_cache[b], live))
        v_pages = v_pages.at[pages].set(page_rows(v_cache[b], live))
    n_layers, layer = layout

    def stack(pool):
        return jnp.full((n_layers,) + pool.shape, np.nan, dtype).at[layer].set(pool)

    return (q, k_cache, v_cache, stack(k_pages), stack(v_pages),
            jnp.asarray(table), jnp.asarray(lengths, jnp.int32))


# page_size 8, 3 pages -> S = 24; lengths cover empty, single-token,
# exact page boundary, boundary straddle, mid-page, and full
LENGTHS = [0, 1, 8, 9, 17, 24]


@pytest.mark.parametrize("layout", LAYOUTS, ids=LAYOUT_IDS)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("Hq,Hkv", [(4, 4), (8, 2), (6, 1)],
                         ids=["mha", "gqa4", "mqa6"])
def test_paged_kernel_matches_ref_and_contiguous(Hq, Hkv, dtype, layout):
    q, k_cache, v_cache, k_pages, v_pages, table, lengths = _build_paged(
        KEY, len(LENGTHS), 3, 8, Hq, Hkv, 16, dtype,
        lengths=LENGTHS, shuffle_seed=7, layout=layout)
    layer = layout[1]

    got = pda.paged_decode_attention(q, k_pages, v_pages, table, lengths,
                                     layer, interpret=True)
    want_paged = ref.paged_decode_attention(q, k_pages, v_pages, table, lengths,
                                            layer)
    # the oracle-of-the-oracle: the contiguous reference on the cache the
    # pages were scattered FROM (independent of the gather path entirely)
    want_dense = ref.decode_attention(q, k_cache, v_cache, lengths)

    np.testing.assert_allclose(np.asarray(want_paged, np.float32),
                               np.asarray(want_dense, np.float32),
                               atol=tol(dtype), rtol=tol(dtype))
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want_dense, np.float32),
                               atol=tol(dtype), rtol=tol(dtype))


@pytest.mark.parametrize("layout", LAYOUTS, ids=LAYOUT_IDS)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_paged_matches_contiguous_kernel(dtype, layout):
    """Paged vs contiguous Pallas kernels (both interpret) on identical data."""
    q, k_cache, v_cache, k_pages, v_pages, table, lengths = _build_paged(
        jax.random.fold_in(KEY, 1), len(LENGTHS), 3, 8, 8, 2, 16, dtype,
        lengths=LENGTHS, shuffle_seed=3, layout=layout)
    paged = pda.paged_decode_attention(q, k_pages, v_pages, table, lengths,
                                       layout[1], interpret=True)
    contig = da.decode_attention(q, k_cache, v_cache, lengths, interpret=True)
    np.testing.assert_allclose(np.asarray(paged, np.float32),
                               np.asarray(contig, np.float32),
                               atol=tol(dtype), rtol=tol(dtype))


@pytest.mark.parametrize("layout", LAYOUTS, ids=LAYOUT_IDS)
def test_page_assignment_is_invisible(layout):
    """The same logical cache under two different physical page layouts must
    produce bit-identical outputs — the table fully hides placement."""
    outs = []
    for seed in (None, 11):
        q, _, _, k_pages, v_pages, table, lengths = _build_paged(
            jax.random.fold_in(KEY, 2), 4, 4, 4, 4, 2, 8, jnp.float32,
            lengths=[0, 5, 8, 16], shuffle_seed=seed, layout=layout)
        outs.append(np.asarray(pda.paged_decode_attention(
            q, k_pages, v_pages, table, lengths, layout[1], interpret=True)))
    assert np.isfinite(outs[0]).all()
    np.testing.assert_array_equal(outs[0], outs[1])


@pytest.mark.parametrize("layout", LAYOUTS, ids=LAYOUT_IDS)
def test_null_page_garbage_never_leaks(layout):
    """Unused table entries point at the null page; fill it with NaN and the
    kernel must still match the oracle computed on a zero-filled pool (the
    in-kernel V scrub is what makes this hold — the jnp oracle itself is not
    NaN-proof, which is exactly why the kernel cannot rely on 0 * x == 0)."""
    lengths = [0, 3, 9, 16]
    build = lambda fill: _build_paged(
        jax.random.fold_in(KEY, 3), 4, 4, 4, 4, 2, 8, jnp.float32,
        lengths=lengths, null_fill=fill, map_dead=False, layout=layout)
    q, _, _, k_nan, v_nan, table, ln = build(np.nan)
    _, _, _, k_zero, v_zero, _, _ = build(0.0)
    got = pda.paged_decode_attention(q, k_nan, v_nan, table, ln, layout[1],
                                     interpret=True)
    want = ref.paged_decode_attention(q, k_zero, v_zero, table, ln, layout[1])
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=tol(jnp.float32), rtol=tol(jnp.float32))


@pytest.mark.parametrize("layout", LAYOUTS, ids=LAYOUT_IDS)
def test_length_zero_rows_emit_exact_zero(layout):
    q, _, _, k_pages, v_pages, table, lengths = _build_paged(
        jax.random.fold_in(KEY, 4), 3, 2, 8, 4, 2, 8, jnp.float32,
        lengths=[0, 0, 16], shuffle_seed=5, layout=layout)
    for out in (pda.paged_decode_attention(q, k_pages, v_pages, table,
                                           lengths, layout[1], interpret=True),
                ref.paged_decode_attention(q, k_pages, v_pages, table,
                                           lengths, layout[1])):
        arr = np.asarray(out)
        assert np.isfinite(arr).all()
        np.testing.assert_array_equal(arr[:2], 0.0)
        assert np.abs(arr[2]).sum() > 0


# ------------------------------------------------------- satellite: no host pad

class _NoPad:
    """Proxy for the jnp module that forbids ``pad`` — the ragged tail must be
    masked inside the kernel, not fixed up by copying the whole cache."""

    def __getattr__(self, name):
        if name == "pad":
            raise AssertionError("decode_attention must not jnp.pad the cache")
        return getattr(jnp, name)


@pytest.mark.parametrize("S", [20, 23, 40], ids=["s20", "s23", "s40"])
def test_contiguous_kernel_ragged_tail_without_host_pad(S, monkeypatch):
    monkeypatch.setattr(da, "jnp", _NoPad())
    kq, kk, kv = jax.random.split(jax.random.fold_in(KEY, 5), 3)
    q = jax.random.normal(kq, (2, 4, 16), jnp.float32)
    k_cache = jax.random.normal(kk, (2, 2, S, 16), jnp.float32)
    v_cache = jax.random.normal(kv, (2, 2, S, 16), jnp.float32)
    lengths = jnp.asarray([S, max(1, S - 7)], jnp.int32)
    got = da.decode_attention(q, k_cache, v_cache, lengths,
                              block_kv=16, interpret=True)
    want = ref.decode_attention(q, k_cache, v_cache, lengths)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=tol(jnp.float32), rtol=tol(jnp.float32))
