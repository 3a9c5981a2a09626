"""``test_bench_control``'s check that the control and the half-batch fault
fail a cell's committed limits, for the training cells of BENCHMARK.json
that its own list does not name (CPU)."""
import pytest

import test_bench_control as base
from bench import run

LATER = [w["name"] for w in run.load_json(run.ROOT, "BENCHMARK.json")["workloads"]
         if run.spec(w["name"])["traffic"]["kind"] == "train"
         and w["name"] not in base.CELLS]


def test_every_later_cell_is_listed():
    assert "train.qwen2-0.5b.b8x2048" in LATER


@pytest.mark.parametrize("cell", LATER)
def test_control_and_fault_fail_the_committed_limits(cell):
    base.test_control_and_fault_fail_the_committed_limits(cell)
