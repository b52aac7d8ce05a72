"""Operations and bytes of the served programs, from shapes and live lengths.

Kept with the benchmark so that every change is measured with the same
arithmetic. Everything is counted from the configuration file's sizes and
from the traffic (each live row's length), never from how a kernel blocks
its work, so the count reads the same whatever implements it. A
multiply-add is two operations.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, Sequence

from chipbench.ref.model import dims

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> Dict:
    """Published peaks of one chip of ``device_kind``; unknown kinds raise."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {PEAKS_FILE.name}")
    return table[device_kind]


def layer_params(cfg: Dict) -> int:
    """Parameters of one decoder layer (matmul weights and biases)."""
    m = dims(cfg)
    d, nq, nkv, hd, ff = m["d"], m["nq"], m["nkv"], m["hd"], m["ff"]
    attn = 2 * d * nq * hd + 2 * d * nkv * hd
    mlp = (3 if m["act"] == "silu" else 2) * d * ff
    if m["bias"]:
        attn += (nq + 2 * nkv) * hd + d
        mlp += ff + d
    return attn + mlp


def nonembed_params(cfg: Dict) -> int:
    return dims(cfg)["L"] * layer_params(cfg)


def _attn_flops(cfg: Dict, q_len: int, ctx: int) -> int:
    """Scores and weighted values for ``q_len`` queries over ``ctx`` keys each."""
    m = dims(cfg)
    return m["L"] * 2 * 2 * m["nq"] * m["hd"] * q_len * ctx


def head_flops(cfg: Dict) -> int:
    m = dims(cfg)
    return 2 * m["d"] * m["V"]


def step_flops(cfg: Dict, lengths: Sequence[int]) -> int:
    """One decode step: each live row's one token through every layer, its
    attention over its own context (``length`` keys, the new one included),
    and the head."""
    per_row = 2 * nonembed_params(cfg) + head_flops(cfg)
    return sum(per_row + _attn_flops(cfg, 1, int(n)) for n in lengths)


def admit_flops(cfg: Dict, prompt_len: int) -> int:
    """One admit: the prompt through every layer, causal attention (each query
    over the keys up to and including its own), and the head at the last
    position only."""
    p = int(prompt_len)
    causal_pairs = p * (p + 1) // 2
    return (2 * nonembed_params(cfg) * p + _attn_flops(cfg, 1, causal_pairs)
            + head_flops(cfg))


def paged_attn_bytes(cfg: Dict, lengths: Sequence[int]) -> int:
    """Bytes one step's paged attention must move, over all layers: each live
    row's K and V for ``length`` tokens, plus its q in and out back, in bf16."""
    m = dims(cfg)
    per_token = 2 * m["nkv"] * m["hd"] * 2          # K and V, bf16
    per_row_fixed = 2 * m["nq"] * m["hd"] * 2       # q read, out written
    return m["L"] * sum(per_token * int(n) + per_row_fixed for n in lengths)
