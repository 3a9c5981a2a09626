"""``test_bench_control``'s check that the control and the half-batch fault
fail a cell's committed limits, for the training cells of BENCHMARK.json
that its own list does not name (CPU). A cell whose configuration states a
mesh is checked on that mesh: ``test_bench_mesh.four_devices`` runs a tiny
copy of the cell and the control over four CPU devices, as the chips
would."""
import pytest

import test_bench_control as base
import test_bench_mesh as mesh_tests
from bench import run

LATER = [w["name"] for w in run.load_json(run.ROOT, "BENCHMARK.json")["workloads"]
         if run.spec(w["name"])["traffic"]["kind"] == "train"
         and w["name"] not in base.CELLS]
MESH = [c for c in LATER if run.spec(c)["config"].get("mesh")]


def test_every_later_cell_is_listed():
    assert "train.qwen2-0.5b.b8x2048" in LATER


@pytest.mark.parametrize("cell", LATER)
def test_control_and_fault_fail_the_committed_limits(cell):
    if cell not in MESH:
        base.test_control_and_fault_fail_the_committed_limits(cell)
        return
    mesh_tests.control_fails_the_committed_limits(
        mesh_tests.four_devices(cell, run.spec(cell)["config"]["mesh"]))
