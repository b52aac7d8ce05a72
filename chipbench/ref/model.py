"""Plain float32 reference of the dense decoder the benchmark's configurations run.

Written from the published descriptions (OLMo, arXiv:2402.00838; StarCoder2,
arXiv:2402.19173) in straightforward ``jax.numpy``: no kernels, no cache, no
batching tricks. It imports nothing of the system under test. It reads only
the configuration file's sizes and the run's seed.

Weights. The system makes its random weights from the seed; the reference
makes the same ones on its own: one ``jax.random.split`` of
``PRNGKey(seed)`` into a key per leaf, leaves taken in the sorted order of
their nested names, each a standard normal in float32 scaled by
``1/sqrt(fan_in)``, then rounded to the served dtype (biases zero, norm
scales one). ``init_weights`` does it in one jitted call on the device.

Forward. ``forward_logits`` runs the whole sequence layer by layer, each
layer one jitted call with every matmul at ``precision="highest"``, so the
stacked bf16 weights are widened to float32 one layer at a time and a
StarCoder2-sized model fits beside its activations.

Control. ``quant="int8"`` runs the same forward with every matmul's operands
rounded to symmetric int8 (weights per output column, activations per row)
and multiplied in float32: the next precision below the configuration's
bf16, the step a later change might be tempted to take.
"""
from __future__ import annotations

import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
LN_EPS = 1e-5


def dims(cfg: Dict) -> Dict:
    """The sizes the reference needs, from a configuration file's keys."""
    d = cfg["hidden_size"]
    return dict(
        d=d,
        L=cfg["num_hidden_layers"],
        nq=cfg["num_attention_heads"],
        nkv=cfg["num_key_value_heads"],
        hd=cfg.get("head_dim") or d // cfg["num_attention_heads"],
        ff=cfg["intermediate_size"],
        V=cfg["vocab_size"],
        act=cfg["hidden_act"],                 # "silu" (SwiGLU) or "gelu_pytorch_tanh"
        norm=cfg["norm"],                      # "layernorm_nonparametric" or "layernorm"
        bias=bool(cfg.get("use_bias", False)),
        theta=float(cfg["rope_theta"]),
        dtype=jnp.dtype(cfg.get("torch_dtype", "bfloat16")),
    )


# ------------------------------------------------------------------ weights

def _layout(m: Dict) -> Dict:
    """Nested {name: (shape, kind)}; kind is 'w' (scaled normal, fan-in on the
    second-to-last axis), 'emb' (fan-in on the last axis), 'zero' or 'one'."""
    L, d, nq, nkv, hd, ff = m["L"], m["d"], m["nq"], m["nkv"], m["hd"], m["ff"]
    attn = {"wq": ((L, d, nq * hd), "w"), "wk": ((L, d, nkv * hd), "w"),
            "wv": ((L, d, nkv * hd), "w"), "wo": ((L, nq * hd, d), "w")}
    if m["bias"]:
        attn.update(bq=((L, nq * hd), "zero"), bk=((L, nkv * hd), "zero"),
                    bv=((L, nkv * hd), "zero"), bo=((L, d), "zero"))
    if m["act"] == "silu":
        mlp = {"w_gate": ((L, d, ff), "w"), "w_up": ((L, d, ff), "w"),
               "w_down": ((L, ff, d), "w")}
    else:
        mlp = {"w_up": ((L, d, ff), "w"), "w_down": ((L, ff, d), "w")}
        if m["bias"]:
            mlp.update(b_up=((L, ff), "zero"), b_down=((L, d), "zero"))

    def norm(stack):
        if m["norm"] == "layernorm_nonparametric":
            return {}
        return {"scale": ((*stack, d), "one"), "bias": ((*stack, d), "zero")}

    return {
        "embed": {"tok": ((m["V"], d), "emb")},
        "final": norm(()),
        "stack": {"layers": {"attn": attn, "ln1": norm((L,)), "ln2": norm((L,)),
                             "mlp": mlp}},
    }


def _is_entry(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[1], str)


def init_weights(cfg: Dict, seed: int):
    """The served weights, made from ``seed`` on the default device."""
    m = dims(cfg)
    entries, treedef = jax.tree.flatten(_layout(m), is_leaf=_is_entry)

    def make(key):
        keys = jax.random.split(key, len(entries))
        out = []
        for (shape, kind), k in zip(entries, keys):
            if kind == "zero":
                out.append(jnp.zeros(shape, m["dtype"]))
            elif kind == "one":
                out.append(jnp.ones(shape, m["dtype"]))
            else:
                fan_in = shape[-1] if kind == "emb" else shape[-2]
                std = 1.0 / np.sqrt(fan_in)
                # barriers keep XLA from folding the scale into the sampler's
                # own constants, so the bits are those of the ops run one by one
                w = jax.lax.optimization_barrier(jax.random.normal(k, shape, jnp.float32))
                w = jax.lax.optimization_barrier(w * std)
                out.append(w.astype(m["dtype"]))
        return out

    return jax.tree.unflatten(treedef, jax.jit(make)(jax.random.PRNGKey(seed)))


# ------------------------------------------------------------------ forward

def _quant_rows(x):
    """Symmetric int8 along the last axis (one scale per row), back in f32."""
    s = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.round(x / s).clip(-127, 127) * s


def _quant_cols(w):
    """Symmetric int8 along the second-to-last axis (one scale per column)."""
    s = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.round(w / s).clip(-127, 127) * s


def _mm(x, w, quant):
    """x[..., k] @ w[k, n] in float32."""
    x, w = x.astype(jnp.float32), w.astype(jnp.float32)
    if quant:
        x, w = _quant_rows(x), _quant_cols(w)
    return jnp.matmul(x, w, precision=HIGHEST)


def _norm(x, p):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    y = (x - mu) / jnp.sqrt(var + LN_EPS)
    if p:
        y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return y


def _rope(x, theta):
    """Rotary embedding, rotate-half form. x: [R, S, H, hd]."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    ang = np.arange(x.shape[1], dtype=np.float64)[:, None] * inv[None]   # [S, hd/2]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(m, quant, x, p):
    """One pre-norm decoder layer over the whole sequence. x: [R, S, d] f32."""
    R, S, _ = x.shape
    nq, nkv, hd = m["nq"], m["nkv"], m["hd"]
    a = p["attn"]

    def lin(h, w, b=None):
        y = _mm(h, w, quant)
        return y if b is None else y + b.astype(jnp.float32)

    h = _norm(x, p["ln1"])
    q = lin(h, a["wq"], a.get("bq")).reshape(R, S, nq, hd)
    k = lin(h, a["wk"], a.get("bk")).reshape(R, S, nkv, hd)
    v = lin(h, a["wv"], a.get("bv")).reshape(R, S, nkv, hd)
    q, k = _rope(q, m["theta"]), _rope(k, m["theta"])
    g = nq // nkv                              # query head j reads kv head j // g
    k = jnp.repeat(k, g, axis=2)
    v = jnp.repeat(v, g, axis=2)
    if quant:
        q, k, v = _quant_rows(q), _quant_rows(k), _quant_rows(v)
    s = jnp.einsum("rqhd,rkhd->rhqk", q, k, precision=HIGHEST) / math.sqrt(hd)
    causal = np.tril(np.ones((S, S), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    pr = jax.nn.softmax(s, axis=-1)
    if quant:
        pr = _quant_rows(pr)
    o = jnp.einsum("rhqk,rkhd->rqhd", pr, v, precision=HIGHEST).reshape(R, S, nq * hd)
    x = _stored(x + lin(o, a["wo"], a.get("bo")), quant)

    h = _norm(x, p["ln2"])
    f = p["mlp"]
    if m["act"] == "silu":
        y = jax.nn.silu(lin(h, f["w_gate"])) * lin(h, f["w_up"])
    else:
        y = jax.nn.gelu(lin(h, f["w_up"], f.get("b_up")), approximate=True)
    return _stored(x + lin(y, f["w_down"], f.get("b_down")), quant)


def _stored(x, quant):
    """The residual stream as the layer hands it on: int8 under the control."""
    return _quant_rows(x) if quant else x


def _head(m, quant, x, final, tok):
    return _mm(_norm(x, final), tok.T, quant)


def forward_logits(cfg: Dict, weights, tokens: np.ndarray,
                   quant: Optional[str] = None, from_pos: int = 0) -> jax.Array:
    """Logits [R, S - from_pos, V] in float32 for the positions of ``tokens``
    [R, S] from ``from_pos`` on."""
    m = dims(cfg)
    q = quant == "int8"
    layer = jax.jit(lambda x, p: _layer(m, q, x, p))
    head = jax.jit(lambda x, final, tok: _head(m, q, x, final, tok))
    tok = weights["embed"]["tok"]
    x = jnp.take(tok, jnp.asarray(tokens), axis=0).astype(jnp.float32)
    layers = weights["stack"]["layers"]
    for i in range(m["L"]):
        x = layer(x, jax.tree.map(lambda w: w[i], layers))
    return head(x[:, from_pos:], weights["final"], tok)
