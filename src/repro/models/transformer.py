"""Layer-stack builders for all assigned families.

Four stack shapes cover the 10 architectures:

* ``uniform``  — attention + (MLP | MoE) every layer, scan-over-layers; optional
                 unstacked first-k-dense head layers (Kimi). dense / moe / vlm archs.
* ``jamba``    — period stack: ``attn_every``-layer periods of (N-1 Mamba + 1 attention),
                 MoE every ``moe_every``-th global layer. Scan over periods.
* ``xlstm``    — period stack of (N-1 mLSTM + 1 sLSTM) blocks.
* ``encdec``   — Whisper: bidirectional encoder + causal decoder w/ cross-attention.

Each family provides: param specs, full forward (train / prefill — prefill collects a
cache), decode step (cache in/out), and cache specs. Caches for scanned stacks are
stacked on the leading layer axis and threaded through ``lax.scan`` as xs/ys.
"""
from __future__ import annotations

import math
from typing import List

import jax
import jax.numpy as jnp

from repro.dist.sharding import constrain
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm
from repro.models.layers import (
    ParamSpec, apply_mlp, apply_norm, mlp_specs, norm_specs,
)
from repro.util import rscan

TRAIN_CF = 1.25   # MoE capacity factor (train)
EVAL_CF = 2.0     # MoE capacity factor (inference)

_tmap = jax.tree.map


def _slice(tree, i: int):
    return _tmap(lambda a: a[i], tree)


def _zeros_spec(shape, dtype, axes):
    return ParamSpec(tuple(shape), dtype, tuple(axes), lambda k, s, d: jnp.zeros(s, d))


def maybe_remat(cfg, fn):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn)


def family_kind(cfg) -> str:
    if cfg.enc_dec:
        return "encdec"
    if cfg.ssm is not None:
        return "jamba" if cfg.ssm.kind == "mamba" else "xlstm"
    return "uniform"


def make_positions(cfg, batch: int, seq: int, n_patches: int = 0):
    """Position ids for rope ([B,S]) or mrope ([3,B,S]); None if cfg.rope == 'none'."""
    if cfg.rope == "none":
        return None
    base = jnp.broadcast_to(jnp.arange(seq, dtype=jnp.int32), (batch, seq))
    if cfg.rope != "mrope":
        return base
    if n_patches == 0:
        return jnp.broadcast_to(base[None], (3, batch, seq))
    g = max(int(math.isqrt(n_patches)), 1)
    s = jnp.arange(seq, dtype=jnp.int32)
    in_img = s < n_patches
    t = jnp.where(in_img, 0, s)
    h = jnp.where(in_img, s // g, s)
    w = jnp.where(in_img, s % g, s)
    pos = jnp.stack([t, h, w])                                          # [3, S]
    return jnp.broadcast_to(pos[:, None, :], (3, batch, seq))


# =============================================================== uniform stack

def _ffn_kind_uniform(cfg) -> str:
    return "moe" if cfg.moe is not None else "mlp"


def uniform_specs(cfg, dtype):
    m = cfg.moe
    first_k = m.first_k_dense if m else 0
    Ls = cfg.n_layers - first_k
    layer = {
        "ln1": norm_specs(cfg, dtype, stack=(Ls,)),
        "attn": attn.attention_specs(cfg, dtype, stack=(Ls,)),
        "ln2": norm_specs(cfg, dtype, stack=(Ls,)),
    }
    if m is not None:
        layer["moe"] = moe_mod.moe_specs(cfg, dtype, stack=(Ls,))
    else:
        layer["mlp"] = mlp_specs(cfg, dtype, stack=(Ls,))
    specs = {"layers": layer}
    if first_k:
        specs["head"] = [
            {
                "ln1": norm_specs(cfg, dtype),
                "attn": attn.attention_specs(cfg, dtype),
                "ln2": norm_specs(cfg, dtype),
                "mlp": mlp_specs(cfg, dtype, d_ff=m.d_ff_dense or cfg.d_ff),
            }
            for _ in range(first_k)
        ]
    return specs


def _attn_block_full(cfg, p, x, positions, cf):
    h = apply_norm(cfg, p["ln1"], x)
    a, kv = attn.attention_full(cfg, p["attn"], h, positions)
    x = x + a
    h2 = apply_norm(cfg, p["ln2"], x)
    if "moe" in p:
        y, aux = moe_mod.moe_forward(cfg, p["moe"], h2, capacity_factor=cf)
    else:
        y, aux = apply_mlp(cfg, p["mlp"], h2), jnp.float32(0.0)
    return x + y, kv, aux


def uniform_forward(cfg, sp, x, positions, mode: str):
    cf = TRAIN_CF if mode == "train" else EVAL_CF
    collect = mode == "prefill"
    aux = jnp.float32(0.0)
    head_cache = []
    for p_l in sp.get("head", []):
        x, kv, a = _attn_block_full(cfg, p_l, x, positions, cf)
        aux = aux + a
        if collect:
            head_cache.append({"k": kv[0], "v": kv[1]})

    def body(carry, p_l):
        xx, ax = carry
        xx, kv, a = _attn_block_full(cfg, p_l, xx, positions, cf)
        ys = {"k": kv[0], "v": kv[1]} if collect else None
        return (xx, ax + a), ys

    (x, aux), kvs = rscan(maybe_remat(cfg, body), (x, aux), sp["layers"])
    cache = None
    if collect:
        cache = {"k": kvs["k"], "v": kvs["v"]}
        if head_cache:
            cache["head"] = head_cache
    return x, cache, aux


def _attn_block_decode(cfg, p, x_t, k_c, v_c, pos, cf):
    h = apply_norm(cfg, p["ln1"], x_t)
    a, k_c, v_c = attn.attention_decode(cfg, p["attn"], h, k_c, v_c, pos)
    x_t = x_t + a
    h2 = apply_norm(cfg, p["ln2"], x_t)
    if "moe" in p:
        y, _ = moe_mod.moe_forward(cfg, p["moe"], h2, capacity_factor=cf)
    else:
        y = apply_mlp(cfg, p["mlp"], h2)
    return x_t + y, k_c, v_c


def uniform_decode(cfg, sp, x_t, cache, pos):
    new_cache = dict(cache)
    if "head" in cache:
        new_head = []
        for p_l, c_l in zip(sp["head"], cache["head"]):
            x_t, k2, v2 = _attn_block_decode(cfg, p_l, x_t, c_l["k"], c_l["v"], pos, EVAL_CF)
            new_head.append({"k": k2, "v": v2})
        new_cache["head"] = new_head

    def body(xx, inp):
        p_l, k_l, v_l = inp
        xx, k2, v2 = _attn_block_decode(cfg, p_l, xx, k_l, v_l, pos, EVAL_CF)
        return xx, (k2, v2)

    x_t, (ks, vs) = rscan(body, x_t, (sp["layers"], cache["k"], cache["v"]))
    new_cache["k"], new_cache["v"] = ks, vs
    return x_t, new_cache


def _attn_block_decode_paged(cfg, p, x_t, k_pg, v_pg, page_table, pos, layer, cf):
    h = apply_norm(cfg, p["ln1"], x_t)
    a, k_pg, v_pg = attn.attention_decode_paged(cfg, p["attn"], h, k_pg, v_pg,
                                                page_table, pos, layer)
    x_t = x_t + a
    h2 = apply_norm(cfg, p["ln2"], x_t)
    if "moe" in p:
        y, _ = moe_mod.moe_forward(cfg, p["moe"], h2, capacity_factor=cf)
    else:
        y = apply_mlp(cfg, p["mlp"], h2)
    return x_t + y, k_pg, v_pg


def uniform_decode_paged(cfg, sp, x_t, k_pages, v_pages, page_table, pos):
    """Paged decode step for the uniform stack (continuous batching).

    k_pages/v_pages: [Ls, P, nkv, page_size, hd] — one head-major page pool per scanned
    layer, sharing ONE page table (a logical page spans every layer, so the
    allocator accounts it once). pos: [B] s32 per-row. Unstacked head layers
    (Kimi first-k-dense) keep per-request caches and are not supported here.

    The scan runs over the layer params only; the whole pools ride in its
    carry, and each layer writes its rows into them and reads them by layer
    index, so a caller that donates the pools has them updated in place.
    """
    if "head" in sp:
        raise ValueError("paged decode does not support unstacked head layers")

    def body(carry, inp):
        xx, k_pg, v_pg = carry
        p_l, layer = inp
        return _attn_block_decode_paged(cfg, p_l, xx, k_pg, v_pg, page_table,
                                        pos, layer, EVAL_CF), None

    layers = jnp.arange(k_pages.shape[0], dtype=jnp.int32)
    (x_t, k_pages, v_pages), _ = rscan(body, (x_t, k_pages, v_pages),
                                       (sp["layers"], layers))
    return x_t, k_pages, v_pages


def uniform_page_pool_specs(cfg, n_pages: int, page_size: int):
    """Zero-init page-pool specs for the uniform stack: K and V pools shaped
    [Ls, n_pages, nkv, page_size, hd], head-major within a page (page 0 is
    the reserved null page)."""
    m = cfg.moe
    first_k = m.first_k_dense if m else 0
    if first_k:
        raise ValueError("paged decode does not support unstacked head layers")
    Ls = cfg.n_layers
    hd, nkv = cfg.resolved_head_dim, cfg.n_kv_heads
    dt = jnp.dtype(cfg.dtype)
    axes = ("layers", None, "kv_heads", "kv_seq", "head_dim")
    return {
        "k_pages": _zeros_spec((Ls, n_pages, nkv, page_size, hd), dt, axes),
        "v_pages": _zeros_spec((Ls, n_pages, nkv, page_size, hd), dt, axes),
    }


def uniform_cache_specs(cfg, batch: int, capacity: int):
    m = cfg.moe
    first_k = m.first_k_dense if m else 0
    Ls = cfg.n_layers - first_k
    hd, nkv = cfg.resolved_head_dim, cfg.n_kv_heads
    dt = jnp.dtype(cfg.dtype)
    kv_axes = ("layers", "batch", "kv_heads", "kv_seq", "head_dim")
    specs = {
        "k": _zeros_spec((Ls, batch, nkv, capacity, hd), dt, kv_axes),
        "v": _zeros_spec((Ls, batch, nkv, capacity, hd), dt, kv_axes),
    }
    if first_k:
        specs["head"] = [
            {
                "k": _zeros_spec((batch, nkv, capacity, hd), dt, kv_axes[1:]),
                "v": _zeros_spec((batch, nkv, capacity, hd), dt, kv_axes[1:]),
            }
            for _ in range(first_k)
        ]
    return specs


# ================================================================= jamba stack

def _jamba_layout(cfg):
    period = cfg.ssm.attn_every
    P = cfg.n_layers // period
    me = cfg.moe.moe_every if cfg.moe else 0
    moe_slots = [i for i in range(period) if me and i % me == me - 1]
    mlp_slots = [i for i in range(period) if i not in moe_slots]
    return period, P, moe_slots, mlp_slots


def jamba_specs(cfg, dtype):
    period, P, moe_slots, mlp_slots = _jamba_layout(cfg)
    n_mix = period - 1
    layer = {
        "ln_mix": norm_specs(cfg, dtype, stack=(P, period)),
        "ln_ffn": norm_specs(cfg, dtype, stack=(P, period)),
        "mamba": ssm.mamba_specs(cfg, dtype, stack=(P, n_mix)),
        "attn": attn.attention_specs(cfg, dtype, stack=(P,)),
    }
    if moe_slots:
        layer["moe"] = moe_mod.moe_specs(cfg, dtype, stack=(P, len(moe_slots)))
    if mlp_slots:
        layer["mlp"] = mlp_specs(cfg, dtype,
                                 d_ff=(cfg.moe.d_ff_dense if cfg.moe else cfg.d_ff),
                                 stack=(P, len(mlp_slots)))
    return {"layers": layer}


def _jamba_period(cfg, pp, x, positions, cf, collect):
    """One period of `period` sublayers (prefill/train start from zero state)."""
    period, _, moe_slots, mlp_slots = _jamba_layout(cfg)
    moe_rank = {s: j for j, s in enumerate(moe_slots)}
    mlp_rank = {s: j for j, s in enumerate(mlp_slots)}
    aux = jnp.float32(0.0)
    convs, ssms = [], []
    kv = None
    for i in range(period):
        h = apply_norm(cfg, _slice(pp["ln_mix"], i), x)
        if i == period - 1:
            a, kv = attn.attention_full(cfg, pp["attn"], h, positions)
        else:
            a, (cs, hs) = ssm.mamba_forward(cfg, _slice(pp["mamba"], i), h, state=None)
            if collect:
                convs.append(cs)
                ssms.append(hs)
        x = x + a
        h2 = apply_norm(cfg, _slice(pp["ln_ffn"], i), x)
        if i in moe_rank:
            y, a_l = moe_mod.moe_forward(cfg, _slice(pp["moe"], moe_rank[i]), h2,
                                         capacity_factor=cf)
            aux = aux + a_l
        else:
            y = apply_mlp(cfg, _slice(pp["mlp"], mlp_rank[i]), h2)
        x = x + y
    out_cache = None
    if collect:
        out_cache = {
            "conv": jnp.stack(convs), "ssm": jnp.stack(ssms),
            "k": kv[0], "v": kv[1],
        }
    return x, out_cache, aux


def jamba_forward(cfg, sp, x, positions, mode: str):
    cf = TRAIN_CF if mode == "train" else EVAL_CF
    collect = mode == "prefill"

    def body(carry, pp):
        xx, ax = carry
        xx, out_cache, a = _jamba_period(cfg, pp, xx, positions, cf, collect)
        return (xx, ax + a), out_cache

    (x, aux), caches = rscan(maybe_remat(cfg, body),
                                    (x, jnp.float32(0.0)), sp["layers"])
    return x, caches, aux


def jamba_decode(cfg, sp, x_t, cache, pos):
    period, _, moe_slots, mlp_slots = _jamba_layout(cfg)
    moe_rank = {s: j for j, s in enumerate(moe_slots)}
    mlp_rank = {s: j for j, s in enumerate(mlp_slots)}

    def body(xx, inp):
        pp, c = inp
        convs, ssms = [], []
        for i in range(period):
            h = apply_norm(cfg, _slice(pp["ln_mix"], i), xx)
            if i == period - 1:
                a, k2, v2 = attn.attention_decode(cfg, pp["attn"], h, c["k"], c["v"], pos)
            else:
                a, (cs, hs) = ssm.mamba_step(cfg, _slice(pp["mamba"], i), h,
                                             (c["conv"][i], c["ssm"][i]))
                convs.append(cs)
                ssms.append(hs)
            xx = xx + a
            h2 = apply_norm(cfg, _slice(pp["ln_ffn"], i), xx)
            if i in moe_rank:
                y, _ = moe_mod.moe_forward(cfg, _slice(pp["moe"], moe_rank[i]), h2,
                                           capacity_factor=EVAL_CF)
            else:
                y = apply_mlp(cfg, _slice(pp["mlp"], mlp_rank[i]), h2)
            xx = xx + y
        new_c = {"conv": jnp.stack(convs), "ssm": jnp.stack(ssms), "k": k2, "v": v2}
        return xx, new_c

    x_t, new_cache = rscan(body, x_t, (sp["layers"], cache))
    return x_t, new_cache


def jamba_cache_specs(cfg, batch: int, capacity: int):
    period, P, _, _ = _jamba_layout(cfg)
    n_mix = period - 1
    d_in, _, ds, cw = ssm.mamba_dims(cfg)
    hd, nkv = cfg.resolved_head_dim, cfg.n_kv_heads
    dt = jnp.dtype(cfg.dtype)
    return {
        "conv": _zeros_spec((P, n_mix, batch, cw - 1, d_in), dt,
                            ("layers", "layers", "batch", None, "ffn")),
        "ssm": _zeros_spec((P, n_mix, batch, d_in, ds), jnp.float32,
                           ("layers", "layers", "batch", "ffn", None)),
        "k": _zeros_spec((P, batch, nkv, capacity, hd), dt,
                         ("layers", "batch", "kv_heads", "kv_seq", None)),
        "v": _zeros_spec((P, batch, nkv, capacity, hd), dt,
                         ("layers", "batch", "kv_heads", "kv_seq", None)),
    }


# ================================================================= xlstm stack

def _xlstm_layout(cfg):
    period = cfg.ssm.slstm_every or cfg.n_layers
    period = min(period, cfg.n_layers)
    P = cfg.n_layers // period
    return period, P


def xlstm_specs(cfg, dtype):
    period, P = _xlstm_layout(cfg)
    layer = {
        "ln": norm_specs(cfg, dtype, stack=(P, period)),
        "mlstm": ssm.mlstm_specs(cfg, dtype, stack=(P, period - 1)),
        "slstm": ssm.slstm_specs(cfg, dtype, stack=(P,)),
    }
    return {"layers": layer}


def xlstm_forward(cfg, sp, x, positions, mode: str):
    period, P = _xlstm_layout(cfg)
    collect = mode == "prefill"

    def body(carry, pp):
        xx = carry
        m_states: List = []
        s_state = None
        for i in range(period):
            h = apply_norm(cfg, _slice(pp["ln"], i), xx)
            if i == period - 1:
                a, s_state = ssm.slstm_forward(cfg, pp["slstm"], h)
            else:
                a, m_st = ssm.mlstm_forward(cfg, _slice(pp["mlstm"], i), h)
                m_states.append(m_st)
            xx = xx + a
        ys = None
        if collect:
            stackd = lambda idx: jnp.stack([st[idx] for st in m_states])
            ys = {
                "mlstm": {"C": stackd(0), "n": stackd(1), "m": stackd(2), "conv": stackd(3)},
                "slstm": {"c": s_state[0], "n": s_state[1], "h": s_state[2], "m": s_state[3]},
            }
        return xx, ys

    x, caches = rscan(maybe_remat(cfg, body), x, sp["layers"])
    return x, caches, jnp.float32(0.0)


def xlstm_decode(cfg, sp, x_t, cache, pos):
    period, P = _xlstm_layout(cfg)

    def body(xx, inp):
        pp, c = inp
        new_m = {"C": [], "n": [], "m": [], "conv": []}
        for i in range(period - 1):
            h = apply_norm(cfg, _slice(pp["ln"], i), xx)
            st = (c["mlstm"]["C"][i], c["mlstm"]["n"][i], c["mlstm"]["m"][i],
                  c["mlstm"]["conv"][i])
            a, st2 = ssm.mlstm_decode_step(cfg, _slice(pp["mlstm"], i), h, st)
            for key, val in zip(("C", "n", "m", "conv"), st2):
                new_m[key].append(val)
            xx = xx + a
        h = apply_norm(cfg, _slice(pp["ln"], period - 1), xx)
        s_st = (c["slstm"]["c"], c["slstm"]["n"], c["slstm"]["h"], c["slstm"]["m"])
        a, s2 = ssm.slstm_step(cfg, pp["slstm"], h, s_st)
        xx = xx + a
        new_c = {
            "mlstm": {k2: jnp.stack(v2) for k2, v2 in new_m.items()},
            "slstm": {"c": s2[0], "n": s2[1], "h": s2[2], "m": s2[3]},
        }
        return xx, new_c

    x_t, new_cache = rscan(body, x_t, (sp["layers"], cache))
    return x_t, new_cache


def xlstm_cache_specs(cfg, batch: int, capacity: int):
    period, P = _xlstm_layout(cfg)
    return {
        "mlstm": ssm.mlstm_state_specs(cfg, batch, stack=(P, period - 1)),
        "slstm": ssm.slstm_state_specs(cfg, batch, stack=(P,)),
    }


# ================================================================ encdec stack

def encdec_specs(cfg, dtype):
    Le, Ld = cfg.n_encoder_layers, cfg.n_layers
    enc_layer = {
        "ln1": norm_specs(cfg, dtype, stack=(Le,)),
        "attn": attn.attention_specs(cfg, dtype, stack=(Le,)),
        "ln2": norm_specs(cfg, dtype, stack=(Le,)),
        "mlp": mlp_specs(cfg, dtype, stack=(Le,)),
    }
    dec_layer = {
        "ln1": norm_specs(cfg, dtype, stack=(Ld,)),
        "attn": attn.attention_specs(cfg, dtype, stack=(Ld,)),
        "lnx": norm_specs(cfg, dtype, stack=(Ld,)),
        "xattn": attn.attention_specs(cfg, dtype, stack=(Ld,)),
        "ln2": norm_specs(cfg, dtype, stack=(Ld,)),
        "mlp": mlp_specs(cfg, dtype, stack=(Ld,)),
    }
    from repro.models.layers import normal_init
    return {
        "enc_pos": ParamSpec((cfg.encoder_seq, cfg.d_model), dtype, (None, "embed"),
                             normal_init(0.02)),
        "enc_layers": enc_layer,
        "enc_final": norm_specs(cfg, dtype),
        "layers": dec_layer,
    }


def encoder_forward(cfg, sp, frames):
    """frames: [B, enc_seq, d] (stub frontend embeddings) -> [B, enc_seq, d]."""
    x = frames.astype(jnp.dtype(cfg.dtype)) + sp["enc_pos"][None]
    x = constrain(x, "batch", "seq", "embed")

    def body(xx, p_l):
        h = apply_norm(cfg, p_l["ln1"], xx)
        a, _ = attn.attention_full(cfg, p_l["attn"], h, None, causal=False)
        xx = xx + a
        h2 = apply_norm(cfg, p_l["ln2"], xx)
        return xx + apply_mlp(cfg, p_l["mlp"], h2), None

    x, _ = rscan(maybe_remat(cfg, body), x, sp["enc_layers"])
    return apply_norm(cfg, sp["enc_final"], x)


def encdec_forward(cfg, sp, x, positions, mode: str, enc_out):
    collect = mode == "prefill"

    def body(carry, p_l):
        xx = carry
        h = apply_norm(cfg, p_l["ln1"], xx)
        a, kv = attn.attention_full(cfg, p_l["attn"], h, positions)
        xx = xx + a
        hx = apply_norm(cfg, p_l["lnx"], xx)
        ax, xkv = attn.attention_full(cfg, p_l["xattn"], hx, None, kv_from=enc_out)
        xx = xx + ax
        h2 = apply_norm(cfg, p_l["ln2"], xx)
        xx = xx + apply_mlp(cfg, p_l["mlp"], h2)
        ys = {"k": kv[0], "v": kv[1], "xk": xkv[0], "xv": xkv[1]} if collect else None
        return xx, ys

    x, caches = rscan(maybe_remat(cfg, body), x, sp["layers"])
    return x, caches, jnp.float32(0.0)


def encdec_decode(cfg, sp, x_t, cache, pos):
    def body(xx, inp):
        p_l, c = inp
        h = apply_norm(cfg, p_l["ln1"], xx)
        a, k2, v2 = attn.attention_decode(cfg, p_l["attn"], h, c["k"], c["v"], pos)
        xx = xx + a
        hx = apply_norm(cfg, p_l["lnx"], xx)
        ax, _, _ = attn.attention_decode(cfg, p_l["xattn"], hx, c["xk"], c["xv"], pos,
                                         cross=True)
        xx = xx + ax
        h2 = apply_norm(cfg, p_l["ln2"], xx)
        xx = xx + apply_mlp(cfg, p_l["mlp"], h2)
        return xx, {"k": k2, "v": v2, "xk": c["xk"], "xv": c["xv"]}

    x_t, new_cache = rscan(body, x_t, (sp["layers"], cache))
    return x_t, new_cache


def encdec_cache_specs(cfg, batch: int, capacity: int):
    Ld = cfg.n_layers
    hd, nkv = cfg.resolved_head_dim, cfg.n_kv_heads
    dt = jnp.dtype(cfg.dtype)
    kv_axes = ("layers", "batch", "kv_heads", "kv_seq", "head_dim")
    return {
        "k": _zeros_spec((Ld, batch, nkv, capacity, hd), dt, kv_axes),
        "v": _zeros_spec((Ld, batch, nkv, capacity, hd), dt, kv_axes),
        "xk": _zeros_spec((Ld, batch, nkv, cfg.encoder_seq, hd), dt, kv_axes),
        "xv": _zeros_spec((Ld, batch, nkv, cfg.encoder_seq, hd), dt, kv_axes),
    }


# ================================================================== dispatcher

def stack_specs(cfg, dtype):
    kind = family_kind(cfg)
    return {
        "uniform": uniform_specs,
        "jamba": jamba_specs,
        "xlstm": xlstm_specs,
        "encdec": encdec_specs,
    }[kind](cfg, dtype)


def stack_forward(cfg, sp, x, positions, mode: str, enc_out=None):
    kind = family_kind(cfg)
    if kind == "uniform":
        return uniform_forward(cfg, sp, x, positions, mode)
    if kind == "jamba":
        return jamba_forward(cfg, sp, x, positions, mode)
    if kind == "xlstm":
        return xlstm_forward(cfg, sp, x, positions, mode)
    return encdec_forward(cfg, sp, x, positions, mode, enc_out)


def stack_decode(cfg, sp, x_t, cache, pos):
    kind = family_kind(cfg)
    if kind == "uniform":
        return uniform_decode(cfg, sp, x_t, cache, pos)
    if kind == "jamba":
        return jamba_decode(cfg, sp, x_t, cache, pos)
    if kind == "xlstm":
        return xlstm_decode(cfg, sp, x_t, cache, pos)
    return encdec_decode(cfg, sp, x_t, cache, pos)


def stack_decode_paged(cfg, sp, x_t, k_pages, v_pages, page_table, pos):
    if family_kind(cfg) != "uniform":
        raise ValueError(
            f"paged decode supports the uniform stack only, not {family_kind(cfg)}")
    return uniform_decode_paged(cfg, sp, x_t, k_pages, v_pages, page_table, pos)


def stack_page_pool_specs(cfg, n_pages: int, page_size: int):
    if family_kind(cfg) != "uniform":
        raise ValueError(
            f"paged decode supports the uniform stack only, not {family_kind(cfg)}")
    return uniform_page_pool_specs(cfg, n_pages, page_size)


def stack_cache_specs(cfg, batch: int, capacity: int):
    kind = family_kind(cfg)
    return {
        "uniform": uniform_cache_specs,
        "jamba": jamba_cache_specs,
        "xlstm": xlstm_cache_specs,
        "encdec": encdec_cache_specs,
    }[kind](cfg, batch, capacity)
