"""The operation and byte counts, worked by hand for one step of each model."""
import json

import pytest

from chipbench import counts
from chipbench.harness import BENCH_DIR


def _cfg(name):
    return json.loads((BENCH_DIR / "configs" / f"{name}.json").read_text())


def test_olmo_1b_one_step_one_row_at_600():
    cfg = _cfg("olmo-1b")
    # a layer: q, k, v, o of 2048 x 2048, SwiGLU 3 x 2048 x 8192, no biases
    assert counts.layer_params(cfg) == 4 * 2048 * 2048 + 3 * 2048 * 8192 == 67_108_864
    assert counts.nonembed_params(cfg) == 16 * 67_108_864 == 1_073_741_824
    # 2 per parameter, the tied head 2 x 2048 x 50304, attention 16 layers x
    # 2 (scores, values) x 2 x 16 heads x 128 x 600 keys
    assert counts.step_flops(cfg, [600]) == (2 * 1_073_741_824 + 206_045_184
                                             + 78_643_200) == 2_432_172_032
    # K and V: 16 heads x 128 x 2 B each, per token per layer = 8 KiB (128 KiB a
    # token over 16 layers); q in and out back: 2 x 16 x 128 x 2 B
    assert counts.paged_attn_bytes(cfg, [600]) == 16 * (8192 * 600 + 8192) == 78_774_272
    assert counts.paged_attn_bytes(cfg, [600, 10]) == 78_774_272 + 16 * (8192 * 10 + 8192)


def test_starcoder2_3b_one_step_one_row_at_1100():
    cfg = _cfg("starcoder2-3b")
    attn = 2 * 3072 * 3072 + 2 * 3072 * 256 + (24 + 2 * 2) * 128 + 3072   # GQA kv 2, biases
    mlp = 2 * 3072 * 12288 + 12288 + 3072                                   # GELU, biases
    assert counts.layer_params(cfg) == attn + mlp == 95_966_720
    assert counts.step_flops(cfg, [1100]) == (2 * 30 * 95_966_720 + 2 * 3072 * 49152
                                              + 30 * 2 * 2 * 24 * 128 * 1100) == 6_465_497_088
    # K and V of 2 kv heads x 128 x 2 B = 1 KiB a token a layer (30 KiB over 30 layers)
    assert counts.paged_attn_bytes(cfg, [1100]) == 30 * (1024 * 1100 + 2 * 24 * 128 * 2) \
        == 34_160_640


def test_admit_counts_causal_pairs_and_one_head_position():
    cfg = _cfg("olmo-1b")
    p = 512
    want = 2 * 1_073_741_824 * p + 16 * 2 * 2 * 16 * 128 * (p * (p + 1) // 2) + 206_045_184
    assert counts.admit_flops(cfg, p) == want


def test_peaks_are_keyed_by_device_kind_and_unknown_kinds_raise():
    v5e = counts.peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in v5e["source"]
    with pytest.raises(KeyError):
        counts.peaks("cpu")
