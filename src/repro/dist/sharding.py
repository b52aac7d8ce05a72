"""Shape-aware sharding rules: logical axes -> mesh axes, with divisibility.

A :class:`Rules` object is a *preset* (a logical-axis -> mesh-axis mapping)
bound to a concrete mesh.  ``Rules.spec(axes, shape)`` turns the logical axes
of one tensor into a ``PartitionSpec``, enforcing three invariants:

* **divisibility** — a mesh axis (or mesh-axis product) is only assigned to a
  dim it divides evenly; otherwise the dim stays replicated and the mesh axis
  remains available for a later dim (*fall-through*, e.g. ``kv_heads=2`` can't
  take ``model=16`` so ``head_dim`` picks it up);
* **tuple-target prefixes** — a mapping value like ``("pod", "data")`` means
  "shard over as long a prefix of these axes as fits": the full product if it
  divides, else a shorter prefix, else nothing.  Axes absent from the mesh
  (or of size 1) are dropped first, so the same preset works on single-pod
  and multi-pod meshes;
* **no mesh-axis reuse** — within one PartitionSpec every mesh axis appears at
  most once (GSPMD would reject the spec otherwise).

The module also carries the execution context (``use_rules`` /
``active_rules`` / ``current_mesh``), the ``constrain`` annotation helper
(a no-op outside a mesh context so single-device paths pay nothing), and the
ParamSpec-tree derivations ``abstract_state`` (ShapeDtypeStructs for dry-run
lowering) and ``param_shardings`` (NamedShardings for pjit).
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Any, Dict, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P


# a mapping value: replicate / one mesh axis / a prefix-tuple of mesh axes
Target = Union[None, str, Tuple[str, ...]]


@dataclasses.dataclass(frozen=True)
class Rules:
    name: str
    mapping: Dict[str, Any]
    mesh_axes: Tuple[str, ...]
    mesh_axis_sizes: Dict[str, int]

    # ------------------------------------------------------------------- spec
    def spec(self, axes: Sequence[Optional[str]], shape: Sequence[int]) -> P:
        """PartitionSpec for a tensor with the given logical axes and shape."""
        if len(axes) != len(shape):
            raise ValueError(f"rank mismatch: axes {axes} vs shape {shape}")
        used: set = set()
        return P(*(self._assign(name, int(dim), used)
                   for name, dim in zip(axes, shape)))

    def _assign(self, name: Optional[str], dim: int, used: set):
        target = self.mapping.get(name) if name is not None else None
        if target is None:
            return None
        if isinstance(target, str):
            target = (target,)
        # drop axes the mesh doesn't have (or that are trivial / already taken)
        avail = [ax for ax in target
                 if self.mesh_axis_sizes.get(ax, 1) > 1 and ax not in used]
        for k in range(len(avail), 0, -1):
            prefix = avail[:k]
            prod = 1
            for ax in prefix:
                prod *= self.mesh_axis_sizes[ax]
            if dim % prod == 0:
                used.update(prefix)
                return prefix[0] if k == 1 else tuple(prefix)
        return None


# ------------------------------------------------------------------- presets
#
# Logical axes in play (see models/layers.py, models/moe.py, transformer.py):
#   activations: batch seq embed ffn vocab heads head_dim kv_seq kv_heads
#   params:      layers embed ffn vocab heads_flat kv_flat experts expert_ffn
# Mesh axes: pod (cross-DCI pure DP) / data / model.
#
# Non-axis keys (consumed elsewhere): "moe_dispatch" ("global" | "local",
# read by models/moe.py to pick per-data-shard dispatch).

_TRAIN: Dict[str, Any] = {
    "batch": ("pod", "data"),
    "layers": None, "seq": None, "embed": None, "head_dim": None,
    "kv_seq": None,
    "ffn": "model", "heads_flat": "model", "kv_flat": "model",
    "vocab": "model", "heads": "model", "kv_heads": "model",
    "experts": "model", "expert_ffn": "model",
    "moe_dispatch": "global",
}

_SERVE_TP: Dict[str, Any] = {
    "batch": "data",
    "layers": None, "seq": None, "embed": None, "kv_seq": None,
    "ffn": "model", "heads_flat": "model", "kv_flat": "model",
    "vocab": "model", "heads": "model", "kv_heads": "model",
    "head_dim": "model",          # fall-through when kv_heads < model size
    "experts": "model", "expert_ffn": "model",
    "moe_dispatch": "global",
}

PRESETS: Dict[str, Dict[str, Any]] = {
    # training: DP over (pod, data); TP/EP over model; grads psum over pod+data
    "train": dict(_TRAIN),
    # training with 2D expert parallelism: experts over data, expert mlp over
    # model (the 384-expert Kimi layout — see models/moe.py)
    "train_ep2d": {**_TRAIN, "experts": "data", "expert_ffn": "model"},
    # serving, tensor-parallel weights, data-parallel batch
    "serve_tp": dict(_SERVE_TP),
    # serving for models too big to replicate over data: 2D weight sharding
    "serve_2d": {**_SERVE_TP, "batch": None, "embed": "data",
                 "vocab": ("model", "data")},
    # long-context decode: the KV cache sequence dim is sharded over model and
    # merged with distributed flash decoding (repro.dist.flash_decode)
    "serve_seqkv": {**_SERVE_TP, "kv_seq": "model", "kv_heads": None,
                    "heads": None, "head_dim": None},
}


def preset_names() -> Tuple[str, ...]:
    return tuple(sorted(PRESETS))


def make_rules(preset: str, mesh, **overrides) -> Rules:
    """Bind a preset (plus per-run overrides, e.g. ``moe_dispatch="local"``)
    to a concrete mesh."""
    if preset not in PRESETS:
        raise KeyError(f"unknown rules preset {preset!r}; have {preset_names()}")
    mapping = dict(PRESETS[preset])
    mapping.update(overrides)
    sizes = {name: int(size)
             for name, size in zip(mesh.axis_names, mesh.devices.shape)}
    return Rules(preset, mapping, tuple(mesh.axis_names), sizes)


# ------------------------------------------------------------------- context

class _Context(threading.local):
    def __init__(self) -> None:
        self.stack: list = []


_ctx = _Context()


@contextlib.contextmanager
def use_rules(rules: Optional[Rules], mesh):
    """Activate (rules, mesh) for the dynamic extent — usually around tracing,
    so ``constrain`` calls inside model code resolve against them."""
    _ctx.stack.append((rules, mesh))
    try:
        yield
    finally:
        _ctx.stack.pop()


def active_rules() -> Optional[Rules]:
    return _ctx.stack[-1][0] if _ctx.stack else None


def current_mesh():
    return _ctx.stack[-1][1] if _ctx.stack else None


def constrain(x: jax.Array, *axes: Optional[str]) -> jax.Array:
    """Sharding annotation by logical axis names.  Identity (returns ``x``
    itself) outside a ``use_rules`` context, so single-device code paths and
    tests never touch GSPMD."""
    rules, mesh = active_rules(), current_mesh()
    if rules is None or mesh is None:
        return x
    if len(axes) != x.ndim:
        raise ValueError(f"constrain rank mismatch: {axes} vs {x.shape}")
    spec = rules.spec(axes, x.shape)
    if all(part is None for part in spec):
        return x        # fully-replicated constraint would *forbid* sharding
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


# ----------------------------------------------------- ParamSpec derivations

def _is_param_spec(leaf) -> bool:
    # duck-typed to avoid importing repro.models.layers (which imports us)
    return (hasattr(leaf, "shape") and hasattr(leaf, "dtype")
            and hasattr(leaf, "axes") and hasattr(leaf, "init"))


def abstract_state(specs):
    """ParamSpec pytree -> ShapeDtypeStruct pytree (dry-run: no allocation)."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(tuple(s.shape), jnp.dtype(s.dtype)),
        specs, is_leaf=_is_param_spec)


def param_shardings(specs, rules: Rules, mesh):
    """ParamSpec pytree -> NamedSharding pytree for pjit in/out_shardings."""
    return jax.tree.map(
        lambda s: NamedSharding(mesh, rules.spec(s.axes, s.shape)),
        specs, is_leaf=_is_param_spec)
