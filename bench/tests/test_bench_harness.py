"""The yardstick's arithmetic and its data-driven layout (CPU only)."""
import importlib.util
import json
import os
import shutil

import pytest

from bench import counts
from bench import trace as tr
from bench.reference import lm

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
DATA = os.path.join(BENCH, "tests", "data")


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def test_union_and_gaps():
    iv = [(0, 10), (5, 12), (20, 30), (25, 26)]
    assert tr.union_ns(iv) == 22
    assert tr.gaps(iv, -5, 40) == [(-5, 0), (12, 20), (30, 40)]


def test_reduction_of_a_recorded_trace():
    """Two launches of a program with the pulse-update kernel and a matmul,
    traced on one TPU v5e: the ops run back to back, so busy time is the sum
    of their durations, and the kernel is the tpu_custom_call."""
    t = tr.load(os.path.join(DATA, "pulse_kernel.xplane.pb"))
    red = tr.reduce(t)
    ops = t["devices"]["/device:TPU:0"][tr.OPS_LINE]
    assert len(ops) == 16
    assert red["busy_s"] == pytest.approx(sum(d for _, _, d in ops) * 1e-9)
    assert red["busy_s"] == pytest.approx(11689e-9)
    assert red["window_s"] == pytest.approx(2100020e-9)
    assert red["device_ops"][0][0] == "%branch_0_fun.1 tpu_custom_call bf16[256,512]"
    kern = _load(os.path.join(BENCH, "metrics", "pulse_kernel_ms.train.py"), "k")
    rec = {"trace": red, "steps": 2}
    assert kern.kernel_seconds(rec) == pytest.approx(2 * 2877e-9)
    assert kern.read(rec) == pytest.approx(2877e-6)
    idle = _load(os.path.join(BENCH, "metrics", "device_idle.train.py"), "i")
    assert idle.read(rec) == pytest.approx(100 * (1 - 11689 / 2100020))


def test_counts_match_hand_counts_for_qwen2():
    m = _config("qwen2-0.5b")["model"]
    per_layer = (896 * 896 * 2 + 896 * 128 * 2      # wq wo, wk wv
                 + 896 + 128 + 128                  # bq bk bv
                 + 896 * 2                          # ln1 ln2
                 + 896 * 4864 * 3)                  # wi wg wo
    assert per_layer == 14_912_384
    assert counts.analog_weights(m, lm) == 24 * per_layer == 357_897_216
    head = 151936 * 896
    assert counts.matmul_weights(m, lm) == 24 * 14_909_440 + head == 493_961_216
    attn = 3 * 24 * 14 * (64 + 64) * 129
    assert counts.flops_per_token(m, 128, lm) == 6 * 493_961_216 + attn == 2_980_411_392
    tile = _config("qwen2-0.5b")["tile"]
    # P and W pulsed: bf16 read + write, plus a bf16 increment, each
    assert counts.update_bytes_per_step(m, tile, lm) == 357_897_216 * 2 * 6


def test_reference_layout_is_the_configuration():
    m = _config("minicpm3-4b-l4")["model"]
    shapes = lm.leaf_paths(lm.param_shapes(m), is_leaf=lambda x: isinstance(x, tuple))
    assert shapes["head"] == (2560, 73448)
    assert not lm.is_digital("head", 2)          # the untied head is a tile
    assert lm.is_digital("embed", 2) and lm.is_digital("ln_f", 1)
    assert shapes["stack/body/p0/attn/wuq"] == (4, 768, 40 * 96)


def test_unknown_device_kind_is_an_error():
    with open(os.path.join(BENCH, "peaks.json")) as f:
        table = json.load(f)
    assert counts.peaks(table, "TPU v5 lite")["bf16_flops_per_s"] == 1.97e14
    with pytest.raises(KeyError, match="TPU v9"):
        counts.peaks(table, "TPU v9")


def test_cell_config_and_metric_are_found_by_adding_files(tmp_path):
    """A later change adds a cell, a configuration, a traffic mix and a
    per-layer metric by adding files and entries only (the new cell's name
    joins the list of cells of the end-to-end metric it reports)."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    b = tmp_path / "bench"
    cfg = _config("qwen2-0.5b")
    cfg["model"]["n_layers"] = cfg["model"]["n_periods"] = 12
    (b / "configs" / "qwen2-0.5b-l12.json").write_text(json.dumps(cfg))
    (b / "traffic" / "b4x512.json").write_text(json.dumps(
        {"kind": "train", "batch": 4, "seq": 512, "microbatch": 1,
         "trace_steps": 4, "recipe": {}}))
    (b / "limits" / "train.qwen2-0.5b-l12.b4x512.json").write_text(
        json.dumps({"loss_gap": 0.1}))
    (b / "metrics" / "steps_traced.train.py").write_text(
        "def read(rec):\n    return rec.get('steps')\n")
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["configs"].append(dict(bench["configs"][0], name="qwen2-0.5b-l12",
                                 file="bench/configs/qwen2-0.5b-l12.json"))
    bench["workloads"].append({"name": "train.qwen2-0.5b-l12.b4x512",
                               "config": "qwen2-0.5b-l12", "traffic": "b4x512",
                               "chips": 1, "why": "added by files"})
    bench["end_to_end"][0]["workloads"].append("train.qwen2-0.5b-l12.b4x512")
    bench["per_layer"].append({"name": "steps_traced.train", "unit": "steps",
                               "better": "higher", "source": "device_trace",
                               "layer": "device", "moves": "train_tokens_per_s"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    run = _load(str(b / "run.py"), "copied_run")
    s = run.spec("train.qwen2-0.5b-l12.b4x512")
    assert s["config"]["model"]["n_layers"] == 12
    assert s["traffic"]["seq"] == 512 and s["limits"] == {"loss_gap": 0.1}
    names = [m["name"] for m in run.metrics_for(s["benchmark"], s["cell"]["name"], True)]
    assert "steps_traced.train" in names and "train_mfu" in names
    assert run.reader("steps_traced.train")({"steps": 4}) == 4
    e2e = [m["name"] for m in run.metrics_for(s["benchmark"], s["cell"]["name"], False)]
    assert e2e == ["train_tokens_per_s", "setup_s"]
