"""The float32 reference against the program's model on the same seeded weights.

At a reduced size on the CPU: the reference makes bit for bit the weights the
program makes from the seed; run in float32 at the highest matmul precision,
the program's model agrees with the reference to float32 round-off, while the
program's bf16 computation misses the same tolerance by an order of magnitude.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.ref.model import forward_logits, init_weights
from chipbench.tests.small import small_cell

# largest |program - reference| over the largest |reference| logit: float32
# round-off through two layers is about 1e-6; bf16 activations give about 1e-2
TOL = 1e-4
SEED = 2 ** 31 - 5


def _program(config):
    from repro.configs import get_config
    from repro.models import build_model
    cell = small_cell(config)
    arch = get_config(cell.config["arch"]).reduced()
    return cell.config, arch, build_model


@pytest.mark.parametrize("config", ["olmo-1b", "starcoder2-3b"])
def test_reference_matches_the_program_in_f32_and_bf16_misses(config):
    cfg, arch, build_model = _program(config)
    model = build_model(arch, max_seq=64)
    params = model.init(jax.random.PRNGKey(SEED))
    weights = init_weights(cfg, SEED)
    same = jax.tree.map(lambda a, b: bool(jnp.array_equal(a, b)), params, weights)
    assert all(jax.tree.leaves(same))

    tokens = np.random.default_rng(3).integers(0, arch.vocab_size, (2, 48)).astype(np.int32)
    ref = np.asarray(forward_logits(cfg, weights, tokens))[:, -1]
    scale = np.abs(ref).max()

    f32 = build_model(dataclasses.replace(arch, dtype="float32"), max_seq=64)
    with jax.default_matmul_precision("highest"):
        got32, _ = f32.prefill(jax.tree.map(lambda w: w.astype(jnp.float32), params),
                               {"tokens": jnp.asarray(tokens)})
    got16, _ = model.prefill(params, {"tokens": jnp.asarray(tokens)})
    err32 = np.abs(np.asarray(got32, np.float32) - ref).max() / scale
    err16 = np.abs(np.asarray(got16, np.float32) - ref).max() / scale
    assert err32 < TOL < err16 / 10


def test_reference_logits_from_a_position_are_the_tail_of_the_full_run():
    cfg, arch, _ = _program("starcoder2-3b")
    weights = init_weights(cfg, 11)
    tokens = np.random.default_rng(4).integers(0, arch.vocab_size, (1, 20)).astype(np.int32)
    full = np.asarray(forward_logits(cfg, weights, tokens))
    tail = np.asarray(forward_logits(cfg, weights, tokens, from_pos=12))
    np.testing.assert_array_equal(full[:, 12:], tail)
