"""The comparison that decides ``correct``, driven through a whole run.

Each test runs a small cell on the CPU through ``harness.run_cell``, which is
all of a run but the look for a chip: deploy, warm-up, the open loop, the
settle, the reference check. A sound run comes out correct; the int8 control
comes out not correct; and each fault a served cell can
have, planted in the timed path under the scheduler, comes out not correct.
"""
import time

import numpy as np
import pytest

from chipbench import harness
from chipbench.tests.small import small_cell

SEED = 2 ** 31 + 77                 # larger than 32 signed bits, like the driver's


def _run(fault=None, control=False, traffic="chat_steady", config="olmo-1b"):
    return harness.run_cell(small_cell(config, traffic), SEED, 3.0, False,
                            time.perf_counter(), control=control, fault=fault)


def _altered_token(decoder):
    """A served token altered where it is produced: row 0's step logits are
    rolled, so its argmax names another token."""
    step = decoder.bundle.step

    def altered(params, k, v, table, pos, tok):
        logits, k2, v2 = step(params, k, v, table, pos, tok)
        return logits.at[0].set(np.roll(np.asarray(logits[0]), 7)), k2, v2

    decoder.bundle.step = altered


def _state_unchanged(decoder):
    """A step that returns its KV pages unchanged: the new token's K and V
    are never written."""
    step = decoder.bundle.step

    def stale(params, k, v, table, pos, tok):
        logits, _, _ = step(params, k, v, table, pos, tok)
        return logits, k, v

    decoder.bundle.step = stale


@pytest.mark.parametrize("config", ["olmo-1b", "starcoder2-3b"])
def test_sound_run_is_correct(config):
    check = _run(config=config)["check"]
    assert check["correct"], check
    assert check["checks"]["failed_requests"]["value"] == 0
    assert check["checks"]["logit_gap"]["value"] < check["checks"]["logit_gap"]["limit"]


def test_the_int8_control_comes_out_not_correct():
    # the int8 picks go through the same comparison in the served tokens'
    # place; the served tokens' own gap, read on the same sample, passes
    check = _run(control=True)["check"]
    gap = check["checks"]["logit_gap"]
    assert not check["correct"], check
    assert gap["value"] > gap["limit"] > check["program_logit_gap"]


@pytest.mark.parametrize("fault", [_altered_token, _state_unchanged])
def test_a_fault_under_the_scheduler_comes_out_not_correct(fault):
    check = _run(fault=fault)["check"]
    assert not check["correct"], check
    assert check["checks"]["logit_gap"]["value"] > check["checks"]["logit_gap"]["limit"]


def test_burst_traffic_starts_cooled_and_is_correct():
    out = _run(traffic="chat_burst")
    run = out["run"]
    assert out["check"]["correct"], out["check"]
    assert run.counters["start"]["boots"] == run.counters["start"]["cooldowns"] >= 1
    assert any(s.timeline.t_boot_wall > 0 for s in run.window)
