"""The served path's Pallas kernels compile for a TPU v5e chip.

Interpret mode cannot see Mosaic's tiling rules: a block whose last two dims
are neither (8, 128)-divisible nor whole compiles in interpret mode and is
refused by the chip's compiler. These tests compile each attention kernel at
OLMo-1B widths (16 heads of 128) for one chip of a described ``v5e:2x2``
topology — the TPU compiler is installed, no chip is needed — and check that
the kernel survived into the program as a ``tpu_custom_call``.

The topology is described inside a fixture, never at import: only one process
may load the TPU library at a time, and every test worker imports this file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.compile_cache import persistent_cache_off
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.paged_decode_attention import paged_decode_attention

HEADS, HEAD_DIM = 16, 128          # OLMo-1B: MHA, 16 heads of 128
PROMPT = 512
CACHE = 2048
PAGE_SIZE, SLOTS = 16, 8


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2. The TPU compiler (``jax[tpu]``) is a
    test dependency: without it this fixture errors, it does not skip."""
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp, persistent_cache_off():
        # a compile for a described chip is written to the persistent cache
        # but cannot be read back without one: keep the cache out of it
        mp.setenv("TPU_LOG_DIR", "disabled")
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        yield SingleDeviceSharding(topo.devices[0])


def _compile_text(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def test_flash_attention_compiles_for_v5e(one_chip):
    qkv = ((1, PROMPT, HEADS, HEAD_DIM), jnp.bfloat16)
    text = _compile_text(lambda q, k, v: flash_attention(q, k, v, causal=True),
                         one_chip, qkv, qkv, qkv)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("depth", [CACHE, 520])      # full and ragged cache
def test_decode_attention_compiles_for_v5e(one_chip, depth):
    cache = ((SLOTS, HEADS, depth, HEAD_DIM), jnp.bfloat16)
    text = _compile_text(decode_attention, one_chip,
                         ((SLOTS, HEADS, HEAD_DIM), jnp.bfloat16), cache, cache,
                         ((SLOTS,), jnp.int32))
    assert "tpu_custom_call" in text


def test_paged_decode_attention_compiles_for_v5e(one_chip):
    max_pages = CACHE // PAGE_SIZE
    pages = ((1 + SLOTS * max_pages, HEADS, PAGE_SIZE, HEAD_DIM), jnp.bfloat16)
    text = _compile_text(paged_decode_attention, one_chip,
                         ((SLOTS, HEADS, HEAD_DIM), jnp.bfloat16), pages, pages,
                         ((SLOTS, max_pages), jnp.int32), ((SLOTS,), jnp.int32))
    assert "tpu_custom_call" in text
