"""The readers of the program's own records (requests' stamps, the compile
log), on hand-made runs."""
import types

import pytest

from chipbench import harness
from chipbench.harness import load_reader
from chipbench.tests.small import small_cell
from chipbench.traffic import Request


def _run(window=(), t0=0.0, seconds=10.0):
    return harness.Run(cell=small_cell(), seconds=seconds, t0=t0, setup_s=1.0,
                       window=list(window), lead_in=[], hbm_bytes=[], counters={},
                       slots=4, device_kind="TPU v5 lite")


def _served(t_dispatch, t_ttfr, t_boot_wall=0.0):
    tl = types.SimpleNamespace(t_dispatch=t_dispatch, t_ttfr=t_ttfr, t_boot_wall=t_boot_wall)
    return harness.Served(Request(0.0, 4, 0), 0.0, 0.0, None, timeline=tl)


def test_admit_wall_ms_leaves_out_the_admissions_that_boot():
    window = [_served(1.0, 1.025), _served(2.0, 2.027),
              _served(3.0, 12.0, t_boot_wall=8.9),             # booted the executor
              harness.Served(Request(0.0, 4, 1), 0.0, 0.0, None)]   # failed: no stamps
    assert load_reader("admit_wall_ms")(_run(window)) == pytest.approx(26.0)
    assert load_reader("admit_wall_ms")(_run([_served(3.0, 12.0, 8.9)])) is None
    assert load_reader("admit_wall_ms")(_run()) is None


def test_window_compiles_counts_the_compiles_logged_inside_the_window():
    import jax
    from repro.core.metrics import now
    read = load_reader("window_compiles")
    t0 = now()
    jax.jit(lambda x: x * 7 + 1)(jax.numpy.ones((3, 17))).block_until_ready()
    assert read(_run(t0=t0, seconds=now() - t0)) >= 1
    assert read(_run(t0=now(), seconds=10.0)) == 0
