"""CPU guards for ``chip_smoke.py``: its phases run end to end at smoke size
on the CPU, and its entry point refuses to report anything off a TPU."""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402


def _cpu_env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    env.pop("XLA_FLAGS", None)
    env.update(extra)
    return env


def test_kernel_parity_phase_interpreted():
    """The parity phase at a small ragged stack: the interpreted kernel
    agrees with the reference bit for bit, and no Mosaic kernel is built
    off the TPU."""
    r = chip_smoke.kernel_parity((2, 40, 200))
    assert r["max_ulps"] == 0.0, r
    assert r["moved"] > 0, r
    assert not r["kernel_in_hlo"], r


def test_seeded_parity_phase_interpreted():
    """The seeded-kernel phase at a small stack: the interpreted kernel,
    drawing devices and noise itself, agrees with the operand path bit for
    bit; and the two-halves uint32 -> f32 conversion equals XLA's here."""
    r = chip_smoke.seeded_parity((2, 40, 200))
    assert r["max_ulps"] == 0.0 and r["differing"] == 0, r
    assert r["moved"] > 0, r
    assert not r["kernel_in_hlo"], r
    assert chip_smoke.u32_conversion(1 << 16)["differing"] == 0


def test_bf16_ulps():
    a = np.array([1.0, 1.0, -2.0, 0.0], np.float32)
    b = np.array([1.0, 1.0 + 2 ** -7, -2.0 - 2 ** -6, 0.0], np.float32)
    np.testing.assert_allclose(chip_smoke.bf16_ulps(a, b), [0, 1, 1, 0])


def test_train_then_serve_phases_at_smoke_size(tmp_path):
    t = chip_smoke.train_phase(str(tmp_path / "ckpt"), smoke=True, steps=3)
    assert len(t["losses"]) == 3 and t["finite"], t
    assert len(t["step_s"]) == 2 and t["compile_s"] > 0
    assert t["tile_bytes"]["per_device"] == t["tile_bytes"]["total"] > 0
    s = chip_smoke.serve_phase(str(tmp_path / "ckpt"), str(tmp_path),
                               smoke=True, requests=2, prompt_len=12, gen=5)
    assert s["complete"], s
    assert s["continuous"]["generated_tokens"] == 10
    # the same weights, greedy, f32 at smoke size: the engines agree
    assert s["agreement"] == 1.0, s


def test_main_refuses_cpu(capsys):
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main([])
    assert exc.value.code != 0
    out = capsys.readouterr().out
    assert "platform=cpu" in out
    assert '"ok"' not in out


def test_script_alone_fails_without_result(tmp_path):
    """Copied out of the repo, the script cannot import the program: it
    exits non-zero and prints no result line."""
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    env = _cpu_env()
    env.pop("PYTHONPATH")
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_sharded_phase_on_four_virtual_devices():
    """The ``--chips 4`` comparison at smoke size on four CPU devices:
    one-device and (data 2, model 2) losses agree, tile state is spread."""
    code = ("import json, sys; sys.path.insert(0, %r)\n"
            "import chip_smoke\n"
            "r = chip_smoke.sharded_phase(smoke=True, steps=2)\n"
            "print('RESULT', json.dumps(r))\n" % ROOT)
    env = _cpu_env(XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [ln for ln in out.stdout.splitlines() if ln.startswith("RESULT")]
    r = json.loads(line[-1][len("RESULT "):])
    assert r["losses_agree"], r
    assert r["max_rel_diff"] < 1e-5, r  # f32 on the CPU: reordering only
    assert r["sharded"], r
