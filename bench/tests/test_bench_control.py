"""The control and the half-batch fault of ``bench/control.py`` at a size the
CPU holds: each reads far above what two reference runs with different
random draws read, on the numbers that catch it on the chip, and each comes
out not correct under the cells' committed limits."""
import functools

import pytest

from bench import control, run

from test_bench_faults import tiny_spec

CELLS = ["train.qwen2-0.5b.b8x128", "train.minicpm3-4b-l4.b8x128"]


def test_control_and_fault_separate_from_the_draws():
    s = tiny_spec()
    r = control.readings(s["config"], s["traffic"], 7, ["twin", "fp8", "half"],
                         s["limits"])
    twin, fp8, half = r["twin"], r["fp8"], r["half"]
    assert fp8["loss_gap"] > 100 * twin["loss_gap"]
    assert fp8["pulses1_gap"] > 10 * twin["pulses1_gap"]
    assert half["loss_gap"] > 100 * twin["loss_gap"]
    assert half["pulses1_gap"] > 100 * twin["pulses1_gap"]


@functools.lru_cache(maxsize=None)
def _readings(cell):
    s = tiny_spec()
    return control.readings(s["config"], s["traffic"], 2**33 + 9,
                            ["fp8", "half"], run.spec(cell)["limits"])


@pytest.mark.parametrize("cell", CELLS)
def test_control_and_fault_fail_the_committed_limits(cell):
    r = _readings(cell)
    assert r["fp8"]["correct"] is False
    assert {"loss_gap", "grad1_gap"} & set(r["fp8"]["over"])
    assert r["half"]["correct"] is False
    assert "loss_gap" in r["half"]["over"]
