"""chip_smoke.py's contract off the chip: every check can fail it, and it
prints no result when it cannot reach a TPU or runs outside a checkout."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402

GOOD = {
    "platform": "tpu", "impl": "pallas", "tpu_custom_call": True,
    "aot_verified": True, "split_serve": True, "decode_aot_verified": True,
    "decode_pools_in_place": True,
    "prefill_rel_delta": 0.01, "step_rel_delta": 0.01, "request_errors": [],
}
BAD = [
    ("platform", "cpu"), ("impl", "ref"), ("impl", "interpret"),
    ("tpu_custom_call", False), ("aot_verified", False), ("split_serve", False),
    ("decode_aot_verified", False), ("aot_verified", None),
    ("decode_pools_in_place", False),
    ("prefill_rel_delta", 0.5), ("step_rel_delta", 0.5),
    ("prefill_rel_delta", float("nan")), ("step_rel_delta", float("inf")),
    ("step_rel_delta", None), ("request_errors", ["decode request 2: boom"]),
]


def test_all_checks_pass():
    assert chip_smoke.failures(GOOD) == []


@pytest.mark.parametrize("key,value", BAD)
def test_each_check_fails_the_smoke(key, value):
    assert chip_smoke.failures({**GOOD, key: value})


def test_missing_facts_fail_the_smoke():
    for key in GOOD:
        facts = dict(GOOD)
        del facts[key]
        assert chip_smoke.failures(facts), key


def _run(cwd: Path, script: Path) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, str(script)], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def _printed_ok(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if json.loads(line).get("ok"):
                return True
        except (ValueError, AttributeError):
            continue
    return False


def test_alone_without_the_program_fails(tmp_path):
    alone = tmp_path / "chip_smoke.py"
    shutil.copy(ROOT / "chip_smoke.py", alone)
    out = _run(tmp_path, alone)
    assert out.returncode != 0
    assert not _printed_ok(out.stdout)


def test_without_a_tpu_fails():
    out = _run(ROOT, ROOT / "chip_smoke.py")
    assert out.returncode != 0, out.stdout
    assert not _printed_ok(out.stdout)
    assert '"platform": "cpu"' in out.stdout
