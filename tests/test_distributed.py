"""Distribution tests: sharding rules, compressed all-reduce, fault tooling,
plus an 8-device subprocess mini dry-run (devices can't be re-pinned inside
this pytest process)."""
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax.sharding import PartitionSpec as P

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


# ---------------------------------------------------------------------------
# sharding rules (pure logic; 1 device is fine for spec construction)
# ---------------------------------------------------------------------------


def test_param_spec_rules():
    from repro.distributed.sharding import param_spec
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh(1, 1)  # single device: divisibility forces replication
    spec = param_spec("stack/body/p0/mlp/wi", (24, 896, 4864), mesh)
    assert spec == P(None, None, None)


def test_param_spec_divisibility_fallback():
    """Dims not divisible by the mesh axis fall back to replication."""
    import repro.distributed.sharding as sh

    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 4, "model": 8}

    spec = sh.param_spec("attn/wq", (30, 64), FakeMesh())
    assert spec == P(None, "model")
    spec2 = sh.param_spec("attn/wq", (30, 20), FakeMesh())  # 20 % 8 != 0
    assert spec2 == P(None, None)
    spec3 = sh.param_spec("mlp/wi", (32, 64), FakeMesh(), zero=True)
    assert spec3 == P("data", "model")


def test_rule_template_tags():
    """Mesh-independent rule templates drive spec-aware tile grouping."""
    from repro.distributed.sharding import rule_template, template_tag

    assert template_tag(rule_template("l0/attn/wq", 2)) == "nM"
    assert template_tag(rule_template("l0/attn/wo", 2)) == "Mn"
    assert template_tag(rule_template("stack/body/p0/mlp/wi", 3)) == "nnM"
    assert template_tag(rule_template("unmatched", 2)) == "nn"
    assert template_tag(()) == "s"


def test_merge_specs():
    from repro.distributed.sharding import merge_specs

    assert merge_specs([P("data", None, "model"), P("data", "model", None)]) \
        == P("data", None, None)
    assert merge_specs([P("data", None, "model")]) == P("data", None, "model")


def test_grouped_tile_spec_multi_pod_stack():
    """Multi-pod ZeRO: the stack axis takes pod x data when divisible."""
    from repro.distributed.sharding import grouped_tile_spec

    class FakeMesh:
        axis_names = ("pod", "data", "model")
        shape = {"pod": 2, "data": 4, "model": 8}

    spec = grouped_tile_spec(("attn/wq",), (16, 32, 64), FakeMesh(),
                             zero=True)
    assert spec == P(("pod", "data"), None, "model")


def test_cache_spec_long_context():
    """batch=1 decode: sequence dim gets the data axes."""
    import repro.distributed.sharding as sh

    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}

    tree = {"kv": {"k": jax.ShapeDtypeStruct((1, 524288, 4, 256), jnp.bfloat16),
                   "pos": jax.ShapeDtypeStruct((524288,), jnp.int32)}}
    # cache_shardings needs a real Mesh for NamedSharding; use spec logic via
    # a real host mesh when >= 2 devices, else just smoke the function
    from repro.launch.mesh import make_host_mesh
    mesh = make_host_mesh(1, 1)
    sh.cache_shardings(tree, mesh)  # must not raise


# ---------------------------------------------------------------------------
# fault tolerance
# ---------------------------------------------------------------------------


def test_straggler_monitor_flags_slow_step():
    from repro.distributed.fault import StragglerMonitor

    m = StragglerMonitor(threshold=3.0, warmup=3)
    for _ in range(5):
        m.start()
        time.sleep(0.01)
        m.stop()
    m.start()
    time.sleep(0.2)
    assert m.stop() is True
    assert m.flagged == 1


def test_train_loop_feeds_the_straggler_monitor_synced_step_times(monkeypatch):
    """``launch.train`` dispatches each step asynchronously, so the time of
    the call alone is the dispatch's. The monitor is fed the per-step time
    of each log, taken after the loop waited for that step's metrics, and
    never brackets a dispatch."""
    from repro.distributed import fault
    from repro.launch import train

    fed = []

    class Recording(fault.StragglerMonitor):
        def start(self):
            raise AssertionError("the loop must not time the dispatch")

        def record(self, dt):
            fed.append(dt)
            return super().record(dt)

    monkeypatch.setattr(train, "StragglerMonitor", Recording)
    r = train.main(["--arch", "qwen2-0.5b", "--smoke", "--steps", "4",
                    "--batch", "2", "--seq", "16", "--log-every", "2"])
    assert fed == [m["step_s"] for m in r["history"]]
    assert len(fed) == 3 and all(dt > 0 for dt in fed)


def test_preemption_and_restart():
    from repro.distributed.fault import PreemptionHandler, RestartPolicy

    h = PreemptionHandler(install=False)
    assert not h.should_stop
    h.trigger()
    assert h.should_stop

    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise RuntimeError("boom")
        return "ok"

    rp = RestartPolicy(max_restarts=5, backoff_s=0.01)
    assert rp.run(flaky) == "ok"
    assert rp.restarts == 2


# ---------------------------------------------------------------------------
# subprocess multi-device tests
# ---------------------------------------------------------------------------


def _run_sub(code: str, devices: int = 8, timeout: int = 560) -> str:
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", code], env=env, timeout=timeout,
                         capture_output=True, text=True)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_compressed_allreduce_subprocess():
    out = _run_sub("""
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_host_mesh
from repro.distributed.compression import make_compressed_grad_fn
mesh = make_host_mesh(8, 1)
fn = make_compressed_grad_fn(mesh, "data")
g = jax.random.normal(jax.random.PRNGKey(0), (8, 4096))
err = jnp.zeros_like(g)
mean, new_err = fn(g, err)
true_mean = jnp.mean(g, axis=0, keepdims=True)
# every row of `mean` should equal the true mean within int8 quantization
diff = float(jnp.max(jnp.abs(mean - true_mean)))
scale = float(jnp.max(jnp.abs(g))) / 127
assert diff < 3 * scale, (diff, scale)
# error feedback accumulates the residual
assert float(jnp.max(jnp.abs(new_err))) <= scale * 1.01
print("COMPRESSION_OK")
""")
    assert "COMPRESSION_OK" in out


def test_mini_dryrun_subprocess():
    """8-device (2x2x2) multi-pod mini dry-run: train + decode cells lower,
    compile, and produce roofline JSONs."""
    out = _run_sub("""
import os
from repro.launch import dryrun
from repro.launch.mesh import make_mesh
def small_mesh(*, multi_pod=False):
    if multi_pod:
        return make_mesh((2,2,2), ("pod","data","model"))
    return make_mesh((2,4), ("data","model"))
dryrun.make_production_mesh = small_mesh
r1 = dryrun.run_cell("qwen2-0.5b", "decode_32k", multi_pod=True, out_dir="/tmp/dry_test", tag="pytest")
r2 = dryrun.run_cell("mamba2-2.7b", "long_500k", multi_pod=False, out_dir="/tmp/dry_test", tag="pytest")
assert r1["status"] == "ok", r1
assert r2["status"] == "ok", r2
assert r1["roofline"]["hlo_flops"] > 0
print("DRYRUN_OK")
""", timeout=560)
    assert "DRYRUN_OK" in out


def test_sharded_tile_bank_2x2_subprocess():
    """Acceptance criterion: on a 2x2 (data, model) mesh, same-shape tiles
    with different partition rules occupy distinct groups whose stacks carry
    the model axis, the stack dim takes the ZeRO/data axis, per-device
    tile-state bytes drop by ~the data size vs replicated, and the grouped
    train_step runs under the explicit specs."""
    out = _run_sub("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core.device import DeviceConfig
from repro.core.digital_opt import DigitalOptConfig, ScheduleConfig
from repro.core.tile import TileConfig
from repro.core.trainer import AnalogTrainer, TrainerConfig
from repro.distributed.sharding import state_shardings
from repro.launch.mesh import make_host_mesh

assert make_host_mesh(2, 1, pods=2).axis_names == ("pod", "data", "model")
mesh = make_host_mesh(2, 2)
dev = DeviceConfig(dw_min=0.01, sigma_pm=0.3, sigma_d2d=0.1, sigma_c2c=0.05)
cfg = TrainerConfig(
    tile=TileConfig(algorithm="erider", device_p=dev, device_w=dev),
    digital=DigitalOptConfig(kind="sgd"),
    schedule=ScheduleConfig(kind="constant", base_lr=0.1))
def loss(params, batch, rng):
    return sum(jnp.sum(v ** 2) for _, v in sorted(params.items())), {}
trainer = AnalogTrainer(loss, cfg, analog_filter=lambda p, l: True, mesh=mesh)
params = {}
for i in range(2):
    params[f"l{i}/attn/wq"] = 0.1 * jnp.ones((8, 8))
    params[f"l{i}/attn/wo"] = 0.1 * jnp.ones((8, 8))
state = trainer.init(jax.random.PRNGKey(0), params)
names = set(g for g, _ in state["tiles"].index)
assert names == {"g8x8_float32_nM", "g8x8_float32_Mn"}, names
cname = "g8x8_float32_Mn+g8x8_float32_nM"
assert [c for c, _ in state["tiles"].class_index] == [cname]
sh = state_shardings(state, mesh)
# class axis replicates (scan axis); stack axis takes ZeRO/data; member dims
# are the dim-wise agreement of nM and Mn rules (conflict -> replicate)
assert sh["tiles"].classes[cname]["W"].spec == P(None, "data", None, None)
assert sh["tiles"].classes[cname]["t"].spec == P(None, None)
state = jax.device_put(state, sh)
total = sum(l.nbytes for l in jax.tree.leaves(state["tiles"]))
per_dev = sum(l.addressable_shards[0].data.nbytes
              for l in jax.tree.leaves(state["tiles"]))
assert per_dev <= total / 2 + 1024, (per_dev, total)   # ~ZeRO/data factor
step = jax.jit(trainer.train_step, in_shardings=(sh, None), donate_argnums=(0,))
for _ in range(2):
    state, m = step(state, jnp.zeros(()))
w = state["tiles"].classes[cname]["W"]
wspec = tuple(w.sharding.spec) + (None,) * (w.ndim - len(w.sharding.spec))
assert wspec == (None, "data", None, None), w.sharding
assert np.isfinite(float(m["loss"]))
print("SHARDED_BANK_OK", per_dev, total)
""", devices=4)
    assert "SHARDED_BANK_OK" in out


def test_train_main_2x2_keeps_tile_state_sharded_subprocess():
    """``launch.train.main --data-parallel 2 --model-parallel 2`` places the
    state by the sharding rules and compiles the step for that layout: after
    training, the tile state is still spread over the four devices (the
    fullest holds at most half of it) and every W stack is sharded."""
    out = _run_sub("""
import jax, numpy as np
from repro.launch import train
r = train.main(["--arch", "qwen2-0.5b", "--smoke", "--steps", "2",
                "--batch", "4", "--seq", "16", "--log-every", "1",
                "--data-parallel", "2", "--model-parallel", "2"])
tb = r["tile_bytes"]
assert tb["per_device"] <= tb["total"] / 2 + 4096, tb
assert all(np.isfinite(h["loss"]) for h in r["history"]), r["history"]
print("TRAIN_2X2_OK", tb)
""", devices=4)
    assert "TRAIN_2X2_OK" in out


def test_elastic_restore_subprocess():
    """Checkpoint saved on one mesh restores onto a different mesh shape."""
    out = _run_sub("""
import jax, jax.numpy as jnp, numpy as np, tempfile
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.checkpoint import ckpt
from repro.launch.mesh import make_host_mesh
d = tempfile.mkdtemp()
mesh1 = make_host_mesh(4, 2)
x = jax.device_put(jnp.arange(64*32, dtype=jnp.float32).reshape(64, 32),
                   NamedSharding(mesh1, P("data", "model")))
ckpt.save({"x": x}, d, step=1)
mesh2 = make_host_mesh(2, 4)   # different factorization = elastic rescale
sh = {"x": NamedSharding(mesh2, P("data", "model"))}
restored = ckpt.restore({"x": x}, d, shardings=sh)
np.testing.assert_array_equal(np.asarray(restored["x"]), np.asarray(x))
assert restored["x"].sharding.spec == P("data", "model")
print("ELASTIC_OK")
""")
    assert "ELASTIC_OK" in out
