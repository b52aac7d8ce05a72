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

import importlib
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.compile_cache import persistent_cache_off
from repro.dist.sharding import abstract_state
from repro.kernels import ops
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.paged_decode_attention import paged_decode_attention
from repro.models import build_model

# the module, not the function of the same name that repro.core exports
deploy = importlib.import_module("repro.core.deploy")

HEADS, HEAD_DIM = 16, 128          # OLMo-1B: MHA, 16 heads of 128
LAYERS = 16
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
    """Every layer's pool stacked, read at a traced layer index."""
    max_pages = CACHE // PAGE_SIZE
    pages = ((LAYERS, 1 + SLOTS * max_pages, HEADS, PAGE_SIZE, HEAD_DIM),
             jnp.bfloat16)
    text = _compile_text(paged_decode_attention, one_chip,
                         ((SLOTS, HEADS, HEAD_DIM), jnp.bfloat16), pages, pages,
                         ((SLOTS, max_pages), jnp.int32), ((SLOTS,), jnp.int32),
                         ((), jnp.int32))
    assert "tpu_custom_call" in text


# the first shape an instruction of these opcodes produces (for copy-start,
# the first element of its tuple)
_MOVES = re.compile(r"= \(?(\w+)\[([\d,]*)\]\S* (copy|copy-start|dynamic-slice)\(")


def _moved_bytes(hlo_text):
    """Bytes of the largest buffer a copy or dynamic-slice produces, anywhere
    in the program (fused computations included)."""
    largest = 0
    for dtype, dims, _op in _MOVES.findall(hlo_text):
        bits = re.search(r"\d+", dtype)          # bf16, f32, s32; pred is a byte
        n = int(bits.group()) // 8 if bits else 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        largest = max(largest, n)
    return largest


@pytest.mark.parametrize("program", ["admit", "step"])
def test_decode_programs_update_the_pools_in_place_for_v5e(one_chip, program):
    """OLMo-1B's admit and step at the benchmark's geometry (32 slots of 8
    pages of 128 tokens, a 4.3 GB pool pair) alias both donated pools, and no
    copy or slice anywhere in them moves a buffer as large as one layer's
    pool; the step keeps no temporary that large either."""
    slots, page_size, max_pages = 32, 128, 8
    model = build_model(get_config("olmo-1b"), max_seq=max_pages * page_size)

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    def ints(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    params = on_chip(abstract_state(model.param_specs()))
    pool = on_chip(abstract_state(model.page_pool_specs(1 + slots * max_pages,
                                                        page_size)))
    k, v = pool["k_pages"], pool["v_pages"]
    admit, step = deploy.jit_decode_programs(model, max_pages, page_size)
    with ops.impl_scope("pallas"):
        if program == "admit":
            compiled = admit.lower(params, ints(1, 512), k, v,
                                   ints(max_pages)).compile()
        else:
            compiled = step.lower(params, k, v, ints(slots, max_pages),
                                  ints(slots), ints(slots, 1)).compile()
    layer_pool = k.size // k.shape[0] * k.dtype.itemsize
    assert deploy.pools_alias(compiled)
    assert _moved_bytes(compiled.as_text()) < layer_pool
    if program == "step":
        assert "tpu_custom_call" in compiled.as_text()
        assert compiled.memory_analysis().temp_size_in_bytes < layer_pool
