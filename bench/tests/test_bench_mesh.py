"""A cell on several chips and against the reference its configuration names
(CPU only): the check of chips against the configuration's mesh, the peak
of the fullest device, a whole run on a (data 2, model 2) mesh of four CPU
devices with its control and its faults, a reference module brought by
files alone, and the readers of a one-device and a four-device record."""
import functools
import importlib.util
import json
import os
import shutil
import subprocess
import sys
import tempfile
import types

import pytest

import jax

from bench import counts, run
from bench import trace as tr
from bench.drivers import train
from bench.reference import lm
from test_bench_faults import tiny_spec

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
DATA = os.path.join(BENCH, "tests", "data")
TESTS = os.path.join(BENCH, "tests")


def _reader(name):
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.mark.parametrize("mesh,chips,ok", [
    (None, 1, True), (None, 4, False), ({"data": 2, "model": 2}, 4, True),
    ({"data": 2, "model": 2}, 1, False), ({"data": 1, "model": 4}, 4, True)])
def test_a_cells_chips_are_its_configurations_mesh(monkeypatch, mesh, chips, ok):
    load = run.load_json

    def changed(*parts):
        out = load(*parts)
        if parts[-1] == "BENCHMARK.json":
            out["workloads"][0]["chips"] = chips
        elif parts[-2:] == ("configs", "qwen2-0.5b.json"):
            out.pop("mesh", None)
            if mesh:
                out["mesh"] = mesh
        return out

    monkeypatch.setattr(run, "load_json", changed)
    if ok:
        assert run.spec("train.qwen2-0.5b.b8x128")["cell"]["chips"] == chips
    else:
        with pytest.raises(SystemExit, match="mesh holds"):
            run.spec("train.qwen2-0.5b.b8x128")


def test_peak_is_the_fullest_devices():
    def dev(stats):
        return types.SimpleNamespace(memory_stats=lambda: stats)

    devs = [dev({"peak_bytes_in_use": 5}), dev({"peak_bytes_in_use": 9}),
            dev({"bytes_in_use": 1}), dev(None)]
    assert train.peak_bytes(devs) == 9
    assert train.peak_bytes(devs[2:]) == 0


# a cell at 2 layers of width 64 (MLA sizes scaled alike) on a mesh of four
# CPU devices: a sound run, the tile state's placement, the run with the
# exchange between chips left out, and the control judged by this size's
# limits and by the cell's committed ones
MESH_RUN = r"""
import json, sys
sys.path[:0] = sys.argv[4:]
import jax
# one compilation cache for the runs and the control, whose programs repeat
jax.config.update("jax_compilation_cache_dir", sys.argv[3])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
from bench import control, run
from bench.drivers import train
from test_bench_faults import LIMITS, tiny_spec

committed = run.spec(sys.argv[1])
s = tiny_spec()
s.update({k: committed[k] for k in ("cell", "config", "reference")})
s["cell"] = dict(s["cell"], chips=4)
s["config"]["mesh"] = json.loads(sys.argv[2])
s["config"]["model"].update(
    n_layers=2, n_periods=2, d_model=64, n_heads=4, n_kv=4, head_dim=24,
    d_ff=128, vocab=512, q_lora=32, kv_lora=16, qk_nope=16, qk_rope=8,
    v_head_dim=16)
specs = set()
snapshot = train.Cell.snapshot


def placed(self, arrays=("W", "P")):
    specs.update(str(a.sharding.spec) for a in jax.tree.leaves(self.state["tiles"]))
    return snapshot(self, arrays)


train.Cell.snapshot = placed
line = run.execute(s, 2**33 + 5, 0.5, False, jax.devices())
peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in jax.devices()]
fault = run.execute(s, 2**33 + 5, 0.5, False, jax.devices(), fault="no_exchange")
mesh = train.cell_mesh(s["config"], jax.devices())
r = control.readings(s["config"], s["traffic"], 2**33 + 9, ["fp8", "half"],
                     LIMITS, s["reference"], mesh)
judged = {}
for v in ("fp8", "half"):
    checks, ok = train.verdict({k: r[v][k] for k in s["limits"]},
                               committed["limits"])
    judged[v] = {"correct": ok, "over": [k for k, c in checks.items()
                                         if c["value"] > c["limit"]]}
print(json.dumps({"line": line, "specs": sorted(specs), "peaks": peaks,
                  "no_exchange": fault, "control": r, "committed": judged}))
"""

# the MLA configuration laid over (data 2, model 2)
MESH_CELL, TWO_BY_TWO = "train.minicpm3-4b-l4.b8x128", {"data": 2, "model": 2}


@functools.lru_cache(maxsize=None)
def _four_devices(cell, mesh):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    with tempfile.TemporaryDirectory() as cache:
        out = subprocess.run(
            [sys.executable, "-c", MESH_RUN, cell, mesh, cache, TESTS,
             run.ROOT, os.path.join(run.ROOT, "src")],
            env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def four_devices(cell: str, mesh: dict) -> dict:
    """A tiny copy of ``cell`` laid over ``mesh`` on four CPU devices: its
    result line, the fault's and the control's readings (one subprocess
    for each cell and mesh)."""
    return _four_devices(cell, json.dumps(mesh, sort_keys=True))


def control_fails_the_committed_limits(r: dict) -> None:
    c = r["committed"]
    assert c["fp8"]["correct"] is False
    assert {"loss_gap", "grad1_gap"} & set(c["fp8"]["over"])
    assert c["half"]["correct"] is False
    assert "loss_gap" in c["half"]["over"]


def test_a_mesh_cell_runs_sharded_and_correct():
    """The run is correct on four devices, its tile stacks split over the
    data axis (ZeRO) and its peak the fullest device's."""
    r = four_devices(MESH_CELL, TWO_BY_TWO)
    line = r["line"]
    assert line["correct"] is True, line["checks"]
    assert line["device"]["count"] == 4
    assert line["device"]["memory_peak_bytes"] == max(r["peaks"])
    assert any("'data'" in spec for spec in r["specs"]), r["specs"]
    assert line["metrics"]["train_tokens_per_s"]["value"] > 0


def test_mesh_control_and_fault_are_not_correct():
    """The control (fp8) and the half-batch fault, the reference spread
    over the four devices, fail this size's limits as on one device."""
    r = four_devices(MESH_CELL, TWO_BY_TWO)["control"]
    assert r["fp8"]["correct"] is False
    assert r["half"]["correct"] is False
    assert "loss_gap" in r["half"]["over"]


def test_mesh_control_fails_the_committed_limits():
    control_fails_the_committed_limits(four_devices(MESH_CELL, TWO_BY_TWO))


def test_the_exchange_left_out_is_not_correct():
    """Each data replica updating what it holds from its own rows alone
    (no exchange between chips) comes out not correct: its digital leaves
    receive half a batch's gradient."""
    line = four_devices(MESH_CELL, TWO_BY_TWO)["no_exchange"]
    assert line["correct"] is False
    over = {k for k, c in line["checks"].items() if c["value"] > c["limit"]}
    assert "grad1_gap" in over, line["checks"]


def test_the_exchange_left_out_needs_a_mesh():
    with pytest.raises(ValueError, match="needs a mesh"):
        run.execute(tiny_spec(), 2**33 + 5, 0.5, False, jax.devices(),
                    fault="no_exchange")


# a reference for an architecture of its own: lm.py with the MLP's gate kept
# digital, and one matrix of the MLP in three multiplied per token
GATED = '''"""lm.py, with the MLP's gate on the digital optimizer and a token
multiplying one of the MLP's three matrices (a marked departure)."""
from bench.reference import lm
from bench.reference.lm import *  # noqa: F401,F403


def is_digital(path, ndim):
    return lm.is_digital(path, ndim) or path.endswith("mlp/wg")


def matmul_weights(m):
    d, ff, L = m["d_model"], m["d_ff"], m["n_layers"]
    return lm_matmul(m) - 2 * L * d * ff


def lm_matmul(m):
    from bench import counts
    return counts.matmul_weights(m, lm)
'''


def test_a_reference_module_is_added_by_files(tmp_path, monkeypatch):
    """A configuration names a reference module of its own; the harness
    loads it by that name, and counts, the reference and the driver's
    program check all take the architecture from it."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    b = tmp_path / "bench"
    (b / "reference" / "gated.py").write_text(GATED)
    cfg = json.loads((b / "configs" / "qwen2-0.5b.json").read_text())
    cfg["reference"] = "bench/reference/gated.py"
    (b / "configs" / "qwen2-0.5b-gated.json").write_text(json.dumps(cfg))
    (b / "limits" / "train.qwen2-0.5b-gated.b8x128.json").write_text(
        (b / "limits" / "train.qwen2-0.5b.b8x128.json").read_text())
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["configs"].append(dict(bench["configs"][0], name="qwen2-0.5b-gated",
                                 file="bench/configs/qwen2-0.5b-gated.json"))
    bench["workloads"].append(dict(bench["workloads"][0], config="qwen2-0.5b-gated",
                                   name="train.qwen2-0.5b-gated.b8x128"))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    spec = importlib.util.spec_from_file_location("gated_run", b / "run.py")
    copied = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(copied)
    s = copied.spec("train.qwen2-0.5b-gated.b8x128")
    ref, m = s["reference"], s["config"]["model"]
    assert ref.__file__ == str(b / "reference" / "gated.py")

    # counts: the module's own matmul_weights, its digital leaves
    d, ff, L = m["d_model"], m["d_ff"], m["n_layers"]
    assert counts.matmul_weights(m, ref) == counts.matmul_weights(m, lm) - 2 * L * d * ff
    assert counts.flops_per_token(m, 128, ref) == (
        counts.flops_per_token(m, 128, lm) - 12 * L * d * ff)
    assert counts.analog_weights(m, ref) == counts.analog_weights(m, lm) - L * d * ff

    # the reference (at a size the CPU holds) keeps the gate digital
    small = dict(s["config"], model=dict(m, n_layers=2, n_periods=2, d_model=64,
                                         n_heads=4, n_kv=2, head_dim=16,
                                         d_ff=128, vocab=512))
    plain = train.reference(small, s["traffic"], 7, ref)
    assert "stack/body/p0/mlp/wg" in plain.digital
    assert "stack/body/p0/mlp/wg" in train.reference(small, s["traffic"], 7, lm).analog

    # the driver's program check reads the layer kinds from the module
    train._program_config(small, ref)
    mla = types.SimpleNamespace(layer_kinds=lambda m: ("mla",) * m["n_layers"])
    with pytest.raises(ValueError, match="layer pattern"):
        train._program_config(small, mla)

    # and a run hands the driver that module
    seen = {}

    def driver_run(*args, **kw):
        seen.update(kw)
        raise StopIteration

    monkeypatch.setattr(train, "run", driver_run)
    with pytest.raises(StopIteration):
        copied.execute(s, 7, 1.0, False, devs=[])
    assert seen["ref"] is ref and seen["devs"] == []


def _recorded(**extra):
    red = tr.reduce(tr.load(os.path.join(DATA, "pulse_kernel.xplane.pb")))
    peaks = {"bf16_flops_per_s": 1.97e14, "hbm_bytes_per_s": 8.19e11}
    return dict({"trace": red, "steps": 2, "tokens": 2048, "flops_per_token": 3e9,
                 "update_bytes_per_step": 1e6, "peak_bytes": 2**30,
                 "peaks": peaks}, **extra)


# what each reader read on the recorded one-device trace before records
# carried their chips (the readers as they stood before the mesh)
ONE_DEVICE = {"train_mfu": 1485.12001118481,
              "pulse_kernel_ms.train": 0.0028770000000000002,
              "pulse_kernel_roofline.train": 42.44008415019885,
              "device_idle.train": 99.44338625346425,
              "peak_hbm_gib.train": 1.0}


@pytest.mark.parametrize("name", sorted(ONE_DEVICE))
def test_a_one_device_record_reads_as_before(name):
    assert _reader(name)(_recorded()) == ONE_DEVICE[name]
    assert _reader(name)(_recorded(chips=1)) == ONE_DEVICE[name]


# four devices, each as busy as the recorded one: the trace's reduction
# averages device time over them, so the kernel's time and the idle share
# are per device; the work (FLOPs, update bytes) is divided among them
FOUR_DEVICES = {"train_mfu": ONE_DEVICE["train_mfu"] / 4,
                "pulse_kernel_ms.train": ONE_DEVICE["pulse_kernel_ms.train"],
                "pulse_kernel_roofline.train": ONE_DEVICE["pulse_kernel_roofline.train"] / 4,
                "device_idle.train": ONE_DEVICE["device_idle.train"],
                "peak_hbm_gib.train": 1.0}


@pytest.mark.parametrize("name", sorted(FOUR_DEVICES))
def test_a_four_device_record_reads_per_device(name):
    t = tr.load(os.path.join(DATA, "pulse_kernel.xplane.pb"))
    plane = t["devices"]["/device:TPU:0"]
    t["devices"] = {f"/device:TPU:{i}": plane for i in range(4)}
    rec = _recorded(trace=tr.reduce(t), chips=4)
    assert _reader(name)(rec) == pytest.approx(FOUR_DEVICES[name], rel=1e-12)
