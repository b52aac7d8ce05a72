"""A cell cut to a size the CPU runs in seconds, for the benchmark's own tests.

The configuration keeps its architecture (``function.reduced``: the program's
``ArchConfig.reduced()``, two layers of width 128, vocabulary 512) and the
traffic keeps its kind, at a few requests a second for a few seconds. The
correctness limit is the one set for this size, not the configuration's.
"""
from __future__ import annotations

import json

from chipbench.harness import BENCH_DIR, Cell

# The correctness limit at this size, where the sample holds about 100 served
# tokens of a two-layer model: over 8 seeds on the CPU sound runs read gaps of
# 0 to 0.018 and the int8 control 0.018 to 0.149 (0.091 on the tests' seed).
SMALL_LOGIT_GAP_LIMIT = 0.04

SMALL = {"num_hidden_layers": 2, "hidden_size": 128, "num_attention_heads": 4,
         "head_dim": 32, "intermediate_size": 256, "vocab_size": 512}


def small_cell(config: str = "olmo-1b", traffic: str = "chat_steady") -> Cell:
    cfg = json.loads((BENCH_DIR / "configs" / f"{config}.json").read_text())
    mha = cfg["num_key_value_heads"] == cfg["num_attention_heads"]
    cfg.update(SMALL, num_key_value_heads=4 if mha else 2)
    cfg["function"] = dict(cfg["function"], prompt_len=32, decode_steps=32, reduced=True)
    cfg["decode"] = dict(cfg["decode"], slots=4)
    cfg["logit_gap_limit"] = SMALL_LOGIT_GAP_LIMIT
    tr = json.loads((BENCH_DIR / "traffic" / f"{traffic}.json").read_text())
    tr["output"] = dict(tr["output"], median=8, min=2, max=32)
    if tr["arrivals"]["kind"] == "poisson":
        tr["arrivals"] = {"kind": "poisson", "rate_rps": 4.0}
        tr["lead_in_s"] = 1.0
    else:
        tr["arrivals"] = dict(tr["arrivals"], size=6, rate_in_burst_rps=8.0, period_s=2.0)
    return Cell(f"small_{config}_{traffic}", 1, config, cfg, traffic, tr)
