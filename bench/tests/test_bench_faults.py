"""A whole run of a training cell, at a size the CPU holds, with the look
for a chip skipped: a sound run comes out correct, and each fault the cell
can have, planted in the timed path, comes out not correct.

The limits here are for this size (2 layers of width 64): the cells' own
limits are set from chip runs at their sizes (PERF.md)."""
import json
import os

import jax
import pytest

from bench import run

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# (sound run at this size reads ~1e-5, 2e-5, 5e-4, 0.3 and 0.3)
LIMITS = {"loss_gap": 1e-3, "grad1_gap": 1e-3, "pulses1_gap": 3e-2,
          "update1_gap": 10.0, "change3_gap": 0.7}


def tiny_spec():
    s = run.spec("train.qwen2-0.5b.b8x128")
    m = s["config"]["model"]
    m.update(n_layers=2, n_periods=2, d_model=64, n_heads=4, n_kv=2,
             head_dim=16, d_ff=128, vocab=512)
    s["traffic"].update(batch=4, seq=16, trace_steps=2)
    s["limits"] = dict(LIMITS)
    with open(os.path.join(BENCH, "peaks.json")) as f:
        s["peaks_row"] = next(iter(json.load(f)["devices"].values()))
    return s


@pytest.mark.parametrize("fault,correct", [
    (None, True), ("frozen", False), ("half_batch", False)])
def test_planted_faults_are_not_correct(fault, correct):
    line = run.execute(tiny_spec(), seed=2**33 + 5, seconds=0.5, trace=False,
                       devs=jax.devices(), fault=fault)
    assert line["correct"] is correct, line["checks"]
    assert line["metrics"]["train_tokens_per_s"]["value"] > 0
    assert list(line)[-1] == "checks"
