"""The readers that work from the decode tier's counters and the requests'
stamps, on hand-made runs."""
import types

import numpy as np
import pytest

from chipbench import counts, harness
from chipbench.harness import load_reader
from chipbench.tests.small import small_cell
from chipbench.traffic import Request


def _served(t_ttfr, t_done, n):
    tl = types.SimpleNamespace(t_ttfr=t_ttfr, t_done=t_done)
    return harness.Served(Request(0.0, n, 0), 0.0, 0.0, None,
                          tokens=np.zeros(n, np.int32), timeline=tl)


def test_row_lengths_spread_a_requests_steps_evenly_between_its_stamps():
    # 5 tokens: the admit at 1.0, then 4 steps at 1.5, 2.0, 2.5, 3.0 with
    # contexts of 513 .. 516 keys
    s = _served(1.0, 3.0, 5)
    assert harness.row_lengths([s], 512, 0.0, 10.0) == [513, 514, 515, 516]
    assert harness.row_lengths([s], 512, 1.9, 2.6) == [514, 515]
    assert harness.row_lengths([_served(1.0, 1.0, 1)], 512, 0.0, 10.0) == []


def _run(**kw):
    cell = small_cell()
    base = dict(cell=cell, seconds=10.0, t0=0.0, setup_s=1.0, window=[], lead_in=[],
                hbm_bytes=[], counters={}, slots=4, device_kind="TPU v5 lite")
    base.update(kw)
    return harness.Run(**base)


def test_tokens_per_s_counts_the_tokens_made_inside_the_window():
    run = _run(counters={"start": {"steps": 10, "step_rows": 30, "admits": 5},
                         "end": {"steps": 110, "step_rows": 330, "admits": 25}})
    assert load_reader("tokens_per_s")(run) == (300 + 20) / 10.0
    assert load_reader("tokens_per_s")(_run()) is None


def test_per_step_counts_take_the_rows_a_step_held_from_the_counters():
    run = _run(trace_counters={"start": {"steps": 0, "step_rows": 0},
                               "end": {"steps": 10, "step_rows": 25}},
               row_lengths=[600, 700])
    cfg = run.cell.config
    per_row = counts.paged_attn_bytes(cfg, [600, 700]) / 2
    assert run.per_step(counts.paged_attn_bytes) == pytest.approx(per_row * 2.5)
    assert _run(row_lengths=[600]).per_step(counts.step_flops) is None


def test_the_knee_is_passed_by_the_ttft_rule_or_an_unserved_request():
    low = {"ttft_p95_ms": 110.0, "unfinished": 0}
    assert not harness.past_knee(dict(low, ttft_p95_ms=219.0), low)
    assert harness.past_knee(dict(low, ttft_p95_ms=221.0), low)
    assert harness.past_knee(dict(low, unfinished=1), low)
