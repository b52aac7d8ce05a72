"""Public model API: one object per (architecture x max_seq) with init / loss /
prefill / decode, plus abstract input specs for the dry-run.

This is the "function body" the FaaS layer deploys: ``Model`` + a shape make a
deterministic, AOT-compilable program (see repro.core.artifact.ExecutorImage).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, ShapeSpec
from repro.models import frontends
from repro.models.layers import (
    ParamSpec, apply_norm, embed_tokens, embedding_specs, init_tree, logits_head,
    norm_specs,
)
from repro.models.transformer import (
    encoder_forward, make_positions, stack_cache_specs, stack_decode,
    stack_decode_paged, stack_forward, stack_page_pool_specs,
)

LM_Z_LOSS = 1e-4


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ArchConfig
    max_seq: int

    # ------------------------------------------------------------------ params
    def param_specs(self):
        dtype = jnp.dtype(self.cfg.dtype)
        from repro.models.transformer import stack_specs
        return {
            "embed": embedding_specs(self.cfg, dtype, self.max_seq),
            "stack": stack_specs(self.cfg, dtype),
            "final": norm_specs(self.cfg, dtype),
        }

    def init(self, key: jax.Array):
        return init_tree(self.param_specs(), key)

    # ------------------------------------------------------------------ shared
    def _embed(self, params, batch: Dict, tokens: jax.Array, pos_offset=0):
        x = embed_tokens(self.cfg, params["embed"], tokens, pos_offset)
        if self.cfg.frontend == "vision" and "patches" in batch:
            npatch = batch["patches"].shape[1]
            x = jnp.concatenate([batch["patches"].astype(x.dtype), x[:, npatch:]], axis=1)
        return x

    def _n_patches(self, batch) -> int:
        if self.cfg.frontend == "vision" and "patches" in batch:
            return batch["patches"].shape[1]
        return 0

    def _enc_out(self, params, batch):
        if not self.cfg.enc_dec:
            return None
        return encoder_forward(self.cfg, params["stack"], batch["frames"])

    def _head(self, params, x):
        x = apply_norm(self.cfg, params["final"], x)
        return logits_head(self.cfg, params["embed"], x)

    # -------------------------------------------------------------------- loss
    def loss(self, params, batch: Dict) -> Tuple[jax.Array, Dict]:
        tokens = batch["tokens"]                                       # [B, S+1]
        inputs, labels = tokens[:, :-1], tokens[:, 1:]
        B, S = inputs.shape
        positions = make_positions(self.cfg, B, S, self._n_patches(batch))
        enc_out = self._enc_out(params, batch)
        x = self._embed(params, batch, inputs)
        x, _, aux = stack_forward(self.cfg, params["stack"], x, positions, "train",
                                  enc_out=enc_out)
        logits = self._head(params, x).astype(jnp.float32)             # [B, S, V]
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
        ce = jnp.mean(logz - gold)
        zloss = LM_Z_LOSS * jnp.mean(jnp.square(logz))
        total = ce + aux + zloss
        metrics = {"loss": total, "ce": ce, "aux": aux, "zloss": zloss}
        return total, metrics

    # ----------------------------------------------------------------- prefill
    def prefill(self, params, batch: Dict, capacity: Optional[int] = None):
        tokens = batch["tokens"]                                       # [B, S]
        B, S = tokens.shape
        capacity = capacity or S
        positions = make_positions(self.cfg, B, S, self._n_patches(batch))
        enc_out = self._enc_out(params, batch)
        x = self._embed(params, batch, tokens)
        x, inner, _ = stack_forward(self.cfg, params["stack"], x, positions, "prefill",
                                    enc_out=enc_out)
        logits = self._head(params, x[:, -1:])[:, 0]                   # [B, V]
        inner = self._pad_cache(inner, B, capacity)
        return logits, {"inner": inner, "pos": jnp.int32(S)}

    def _pad_cache(self, inner, batch: int, capacity: int):
        target = jax.tree.map(lambda s: s.shape,
                              stack_cache_specs(self.cfg, batch, capacity),
                              is_leaf=lambda s: isinstance(s, ParamSpec))

        def pad(leaf, tshape):
            if leaf.shape == tuple(tshape):
                return leaf
            widths = [(0, t - c) for c, t in zip(leaf.shape, tshape)]
            return jnp.pad(leaf, widths)

        return jax.tree.map(pad, inner, target)

    # ------------------------------------------------------------------ decode
    def decode(self, params, cache, token: jax.Array):
        """token: [B, 1] int32 -> (logits [B, V], cache')."""
        pos = cache["pos"]
        x = self._embed(params, {}, token, pos_offset=pos)
        x, inner = stack_decode(self.cfg, params["stack"], x, cache["inner"], pos)
        logits = self._head(params, x)[:, 0]
        return logits, {"inner": inner, "pos": pos + 1}

    # ------------------------------------------------------------- paged decode
    def decode_paged(self, params, k_pages, v_pages, page_table, pos,
                     token: jax.Array):
        """One continuous-batching step against the shared page pool.

        k_pages/v_pages: [L, P, nkv, page_size, hd]; page_table:
        [B, max_pages] s32; pos: [B] s32 (per-row current length — the host
        step loop owns it, mirroring the PagePool's chain state); token:
        [B, 1] s32. Returns (logits [B, V], k_pages', v_pages'). Rows whose
        page-table row is all zeros are empty slots: their reads and writes
        land on the reserved null page and their logits are garbage the step
        loop discards. Uniform stack only.
        """
        x = self._embed(params, {}, token, pos_offset=pos)
        x, k_pages, v_pages = stack_decode_paged(
            self.cfg, params["stack"], x, k_pages, v_pages, page_table, pos)
        logits = self._head(params, x)[:, 0]
        return logits, k_pages, v_pages

    def page_pool_specs(self, n_pages: int, page_size: int):
        return stack_page_pool_specs(self.cfg, n_pages, page_size)

    def init_page_pool(self, n_pages: int, page_size: int):
        return init_tree(self.page_pool_specs(n_pages, page_size),
                         jax.random.PRNGKey(0))

    # ------------------------------------------------------------------- cache
    def cache_specs(self, batch: int, capacity: int):
        return {
            "inner": stack_cache_specs(self.cfg, batch, capacity),
            "pos": ParamSpec((), jnp.int32, (), lambda k, s, d: jnp.zeros(s, d)),
        }

    def init_cache(self, batch: int, capacity: int):
        return init_tree(self.cache_specs(batch, capacity), jax.random.PRNGKey(0))


def build_model(cfg: ArchConfig, max_seq: int) -> Model:
    return Model(cfg, max_seq)


def input_specs(cfg: ArchConfig, shape: ShapeSpec, batch_override: Optional[int] = None):
    """Abstract (ShapeDtypeStruct) inputs for the step selected by ``shape.kind``.

    train  -> {'tokens': [B, S+1]} (+frontend)
    prefill-> {'tokens': [B, S]}   (+frontend)
    decode -> {'token':  [B, 1]}   (cache comes from Model.cache_specs)
    """
    B = batch_override or shape.global_batch
    S = shape.seq_len
    if shape.kind == "train":
        d = {"tokens": jax.ShapeDtypeStruct((B, S + 1), jnp.int32)}
        d.update(frontends.frontend_input_specs(cfg, B, S))
    elif shape.kind == "prefill":
        d = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32)}
        d.update(frontends.frontend_input_specs(cfg, B, S))
    elif shape.kind == "decode":
        d = {"token": jax.ShapeDtypeStruct((B, 1), jnp.int32)}
    else:
        raise ValueError(shape.kind)
    return d
