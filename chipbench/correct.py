"""The comparison that decides ``correct``: served tokens against the reference.

After the window has closed and the system's state is freed, a sample of the
requests that finished in the window, drawn from the seed with the longest
always in it, is run once through the float32 reference: each prompt with
its served tokens, teacher-forced. At each served position the reference's
best logit is compared with its logit of the token the system served; the
number compared is the widest gap over all sampled positions. Greedy decoding
in bf16 may pick a token whose reference logit is a hair below the best when
the two are nearly tied; a wrong weight, mask, page or position serves
tokens far below it.

The control reads the same number for a lower precision put in the system's
place: at each position the token the int8 reference puts first, read on the
float32 reference's logits.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import jax.numpy as jnp
import numpy as np

from chipbench.ref.model import forward_logits, init_weights

SAMPLE_ROWS = 8


def sample(served: Sequence[np.ndarray], seed: int, rows: int = SAMPLE_ROWS) -> List[int]:
    """Indices of ``rows`` finished requests: the longest, then others drawn
    from the seed."""
    if not served:
        return []
    order = sorted(range(len(served)), key=lambda i: (-len(served[i]), i))
    rest = np.random.default_rng([seed, 0x5a3]).permutation(order[1:]).tolist()
    return [order[0]] + rest[: rows - 1]


def _gaps(ref_logits, picks, counts) -> float:
    """Widest (best reference logit - reference logit of the picked token)."""
    best = jnp.max(ref_logits, axis=-1)
    got = jnp.take_along_axis(ref_logits, picks[..., None], axis=-1)[..., 0]
    gap = np.asarray(best - got)
    return float(max(gap[r, :n].max() for r, n in enumerate(counts)))


def served_gap(cfg: Dict, weight_seed: int, prompts: Sequence[np.ndarray],
               served: Sequence[np.ndarray], total_len: int,
               control: bool = False) -> Dict[str, Optional[float]]:
    """The widest gap of the served tokens, and of the int8 control's picks
    with ``control``. ``prompts`` are [P] rows, ``served`` the tokens each
    request got; every row is padded to ``total_len`` so the reference
    compiles one shape per configuration."""
    P = len(prompts[0])
    R = SAMPLE_ROWS
    tokens = np.zeros((R, total_len), np.int32)
    picks = np.zeros((R, total_len - P + 1), np.int32)
    counts = []
    for r, (p, s) in enumerate(zip(prompts, served)):
        s = np.asarray(s, np.int32)
        tokens[r, :P] = p
        tokens[r, P:P + len(s) - 1] = s[:-1]
        picks[r, :len(s)] = s
        counts.append(len(s))
    weights = init_weights(cfg, weight_seed)
    ref = forward_logits(cfg, weights, tokens, from_pos=P - 1)
    out: Dict[str, Optional[float]] = {"logit_gap": _gaps(ref, jnp.asarray(picks), counts)}
    if control:
        low = forward_logits(cfg, weights, tokens, quant="int8", from_pos=P - 1)
        out["control_logit_gap"] = _gaps(ref, jnp.argmax(low, axis=-1), counts)
        del low
    del ref, weights
    return out
