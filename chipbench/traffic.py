"""One general generator of open-loop request schedules from a traffic file.

A traffic file (``chipbench/traffic/<name>.json``) holds parameters only:

- ``arrivals``: ``{"kind": "poisson", "rate_rps": r}`` or
  ``{"kind": "bursts", "size": n, "rate_in_burst_rps": r, "period_s": p}``;
- ``output``: ``{"median": m, "sigma": s, "min": lo, "max": hi}``, a
  lognormal of ``max_new`` clipped to ``[lo, hi]``;
- ``lead_in_s``: seconds of the same traffic sent before the window opens;
- ``cooled_start``: whether the window opens with the tier cooled to zero;
- ``trace_window_s``: ``[start, length]`` of the profiler window, in seconds
  from the window's start, for ``--trace 1`` runs.

Every seed gets the same multiset of gaps and output lengths, in another
order: gaps are the quantiles ``(i + 1/2)/n`` of the exponential and lengths
those of the lognormal, shuffled by the seed. So two seeds offer the same
work, and a difference between them is the system's, not the draw's. For
Poisson arrivals the shuffle is stratified: every block of ``ORDER_BLOCK``
consecutive requests draws its gaps and its lengths from all parts of their
distributions, so no seed puts most of its long requests, or most of its
short gaps, into one stretch of the window, where they would decide how much
work the window holds. Within a block the order is random.

Other keys (``source``, ``reduced``, ``assumed``) document where the mix
comes from and are not read.
Prompts are random token ids from the seed, at the deployment's one length.
"""
from __future__ import annotations

import dataclasses
import math
import statistics
from typing import Dict, List

import numpy as np

_NORMAL = statistics.NormalDist()
WARMUP_INDEX = 2 ** 32 - 1      # the warm-up requests' prompt, apart from the schedule's
ORDER_BLOCK = 8                 # requests per stratified block of a Poisson schedule


@dataclasses.dataclass(frozen=True)
class Request:
    due_s: float          # seconds from the window's start (negative: lead-in)
    max_new: int
    index: int            # position in the schedule; seeds the prompt


def _exp_quantiles(n: int, rate: float) -> np.ndarray:
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / rate


def _lognormal_quantiles(n: int, spec: Dict) -> np.ndarray:
    z = np.array([_NORMAL.inv_cdf((i + 0.5) / n) for i in range(n)])
    vals = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(vals), spec["min"], spec["max"]).astype(int)


def _order(values: np.ndarray, rng: np.random.Generator, block: int) -> np.ndarray:
    """``values`` in the seed's order, stratified in blocks of ``block``."""
    v = np.sort(values)
    n_blocks = -(-len(v) // block)
    blocks: List[List[int]] = [[] for _ in range(n_blocks)]
    for s in range(block):                      # stratum s: the s-th run of n_blocks values
        stratum = np.arange(s * n_blocks, min((s + 1) * n_blocks, len(v)))
        for i, b in zip(stratum, rng.permutation(n_blocks)):
            blocks[b].append(int(i))
    return v[np.concatenate([rng.permutation(b) for b in blocks if b])]


def schedule(traffic: Dict, seed: int, seconds: float) -> List[Request]:
    """Requests due in ``[-lead_in_s, seconds)``, in due order."""
    rng = np.random.default_rng([seed % 2 ** 64, 0x7a11])
    arr, out = traffic["arrivals"], traffic["output"]
    lead = float(traffic.get("lead_in_s", 0.0))
    if arr["kind"] == "poisson":
        span = lead + seconds
        n = max(1, int(round(arr["rate_rps"] * span)))
        gaps = _order(_exp_quantiles(n, arr["rate_rps"]), rng, ORDER_BLOCK)
        # the first request is due at once: the lead-in starts on a resident tier
        due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]]) - lead
        lens = _order(_lognormal_quantiles(n, out), rng, ORDER_BLOCK)
    elif arr["kind"] == "bursts":
        n_bursts = max(1, math.ceil((seconds + lead) / arr["period_s"]))
        dues, lens_l = [], []
        for b in range(n_bursts):
            gaps = rng.permutation(_exp_quantiles(arr["size"],
                                                  arr["rate_in_burst_rps"]))
            start = b * arr["period_s"] - lead
            dues.append(start + np.concatenate([[0.0], np.cumsum(gaps)[:-1]]))
            lens_l.append(rng.permutation(_lognormal_quantiles(arr["size"], out)))
        due, lens = np.concatenate(dues), np.concatenate(lens_l)
    else:
        raise ValueError(f"unknown arrivals kind {arr['kind']!r}")
    keep = due < seconds
    return [Request(float(t), int(k), i)
            for i, (t, k) in enumerate(zip(due[keep], lens[keep]))]


def prompt(seed: int, index: int, prompt_len: int, vocab: int) -> np.ndarray:
    """The ``[1, prompt_len]`` int32 prompt of request ``index``."""
    rng = np.random.default_rng([seed % 2 ** 64, index, 0x9e37])
    return rng.integers(0, vocab, (1, prompt_len), dtype=np.int32)
