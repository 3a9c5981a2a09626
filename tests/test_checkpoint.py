"""Checkpoint tests: roundtrip, async, integrity, restart resume, and
forward-compat of the lifetime-era DeviceConfig fields (PR 6's
stored-keys-only policy compare + the cross-plan re-key path)."""
import json
import os
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import ckpt

# every DeviceConfig field added by the lifetime subsystem — a pre-drift
# checkpoint's stored policy JSON has none of them
LIFETIME_KEYS = ("drift_nu", "drift_nu_std", "drift_t0", "prog_noise",
                 "prog_noise_slope", "prog_rounds", "read_noise")


def _tree(key=0):
    k = jax.random.PRNGKey(key)
    return {
        "a": jax.random.normal(k, (17, 33)),
        "nested": {"b": jnp.arange(10, dtype=jnp.int32), "c": None,
                   "scalar": jnp.float32(3.5)},
    }


def test_roundtrip(tmp_path):
    t = _tree()
    ckpt.save(t, str(tmp_path), step=3)
    assert ckpt.latest_step(str(tmp_path)) == 3
    restored = ckpt.restore(_tree(99), str(tmp_path), verify=True)
    np.testing.assert_array_equal(np.asarray(t["a"]), np.asarray(restored["a"]))
    np.testing.assert_array_equal(np.asarray(t["nested"]["b"]),
                                  np.asarray(restored["nested"]["b"]))
    assert restored["nested"]["c"] is None


def test_bfloat16_roundtrip(tmp_path):
    """bf16 leaves (the full-width tile state) come back as bf16, bit for
    bit: np.savez writes them as raw 2-byte voids."""
    t = {"w": jax.random.normal(jax.random.PRNGKey(1), (8, 24)
                                ).astype(jnp.bfloat16)}
    ckpt.save(t, str(tmp_path), step=1)
    restored = ckpt.restore({"w": jnp.zeros((8, 24), jnp.bfloat16)},
                            str(tmp_path), verify=True)
    assert restored["w"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(t["w"], np.float32),
                                  np.asarray(restored["w"], np.float32))


def test_async_save_and_latest(tmp_path):
    t = _tree()
    th = ckpt.save(t, str(tmp_path), step=1, asynchronous=True)
    th.join(timeout=30)
    ckpt.save(t, str(tmp_path), step=2)
    assert ckpt.latest_step(str(tmp_path)) == 2
    assert os.path.islink(os.path.join(str(tmp_path), "latest"))


def test_restore_shape_mismatch_fails(tmp_path):
    ckpt.save(_tree(), str(tmp_path), step=1)
    bad = {"a": jnp.zeros((5, 5)), "nested": {"b": jnp.zeros(10, jnp.int32),
                                              "c": None, "scalar": jnp.float32(0)}}
    with pytest.raises(AssertionError):
        ckpt.restore(bad, str(tmp_path))


def _drift_trainer(plan=None):
    """AnalogTrainer over a drift-aware device preset (nonzero lifetime
    coefficients end up in every stored policy JSON)."""
    from repro.api import AnalogPlan, TilePolicy
    from repro.core.device import PRESETS
    from repro.core.digital_opt import DigitalOptConfig, ScheduleConfig
    from repro.core.tile import TileConfig
    from repro.core.trainer import AnalogTrainer, TrainerConfig

    dev = PRESETS["pcm_gst"]
    pol = TilePolicy(TileConfig(algorithm="erider", device_p=dev,
                                device_w=dev, lr_p=0.5, lr_w=0.5),
                     name="pcm")
    cfg = TrainerConfig(digital=DigitalOptConfig(kind="sgd"),
                        schedule=ScheduleConfig(kind="constant", base_lr=0.1))

    def loss_fn(params, batch, rng):
        return sum(jnp.sum(v ** 2) for v in params.values()), {}

    return AnalogTrainer(loss_fn, cfg,
                         plan=plan or AnalogPlan.of(("**", pol)))


def _strip_lifetime_keys(directory, step=1):
    """Rewrite a checkpoint manifest as a pre-drift writer would have:
    no lifetime keys in any stored device-config JSON."""
    path = os.path.join(directory, f"step_{step:09d}", "manifest.json")
    with open(path) as f:
        manifest = json.load(f)
    for rec in manifest.get("tile_groups", {}).values():
        pol = rec.get("policy") or {}
        for dev_key in ("device_p", "device_w"):
            dev = pol.get("tile", {}).get(dev_key)
            if dev:
                for k in LIFETIME_KEYS:
                    dev.pop(k, None)
    with open(path, "w") as f:
        json.dump(manifest, f)
    return manifest


def test_pre_drift_checkpoint_restores_silently(tmp_path):
    """Stored-keys-only policy compare: a checkpoint whose policies were
    written before DeviceConfig grew the lifetime fields restores into a
    drift-aware template without a policy-mismatch warning."""
    trainer = _drift_trainer()
    state = trainer.init(jax.random.PRNGKey(0),
                         {"w": jnp.ones((8, 8)), "v": jnp.ones((8, 8))})
    state, _ = trainer.jit_step(donate=False)(state, jnp.zeros(()))
    ckpt.save(state, str(tmp_path), step=1)
    _strip_lifetime_keys(str(tmp_path))

    with warnings.catch_warnings():
        warnings.simplefilter("error")
        restored = ckpt.restore(state, str(tmp_path))
    for p in ("w", "v"):
        np.testing.assert_array_equal(
            np.asarray(restored["tiles"][p]["W"]),
            np.asarray(state["tiles"][p]["W"]), err_msg=p)


def test_pre_drift_manifest_still_warns_on_real_mismatch(tmp_path):
    """Stripping the lifetime keys must not blind the compare: a stored
    key that genuinely differs (dw_min) still warns."""
    trainer = _drift_trainer()
    state = trainer.init(jax.random.PRNGKey(0), {"w": jnp.ones((8, 8))})
    ckpt.save(state, str(tmp_path), step=1)
    manifest = _strip_lifetime_keys(str(tmp_path))
    path = os.path.join(str(tmp_path), "step_000000001", "manifest.json")
    for rec in manifest["tile_groups"].values():
        rec["policy"]["tile"]["device_w"]["dw_min"] = 0.4999
    with open(path, "w") as f:
        json.dump(manifest, f)
    with pytest.warns(UserWarning, match="policy"):
        ckpt.restore(state, str(tmp_path))


def test_lifetime_fields_survive_rekey_both_directions(tmp_path):
    """Cross-plan re-key (single <-> mixed) with drift-aware policies:
    the policy JSON round-trips the lifetime fields and the re-keyed tile
    stacks are preserved in both directions."""
    from repro.api import AnalogPlan, TilePolicy
    from repro.core.device import PRESETS
    from repro.core.plan import policy_from_json, policy_to_json
    from repro.core.tile import TileConfig

    pcm = PRESETS["pcm_gst"]
    om = PRESETS["reram_om"]
    pol_pcm = TilePolicy(TileConfig(algorithm="erider", device_p=pcm,
                                    device_w=pcm, lr_p=0.5, lr_w=0.5),
                         name="pcm")
    pol_om = TilePolicy(TileConfig(algorithm="erider", device_p=om,
                                   device_w=om, lr_p=0.5, lr_w=0.5),
                        name="om")
    # the serializer keeps every lifetime coefficient
    for pol in (pol_pcm, pol_om):
        blob = policy_to_json(pol)
        assert blob["tile"]["device_w"]["drift_nu"] == pol.tile.device_w.drift_nu
        assert policy_from_json(blob) == pol

    params = {"w": jnp.ones((8, 8)), "v": jnp.ones((8, 8))}
    single = _drift_trainer(AnalogPlan.of(("**", pol_pcm)))
    mixed = _drift_trainer(AnalogPlan.of(("w", pol_pcm), ("**", pol_om)))

    # direction 1: single-policy checkpoint -> mixed-plan template
    s_single = single.init(jax.random.PRNGKey(1), params)
    s_single, _ = single.jit_step(donate=False)(s_single, jnp.zeros(()))
    ckpt.save(s_single, str(tmp_path / "a"), step=1)
    template = mixed.init(jax.random.PRNGKey(1), params)
    with pytest.warns(UserWarning, match="om"):   # v really changed policy
        restored = ckpt.restore(template, str(tmp_path / "a"))
    for p in params:
        np.testing.assert_array_equal(
            np.asarray(restored["tiles"][p]["W"]),
            np.asarray(s_single["tiles"][p]["W"]), err_msg=p)

    # direction 2: mixed-plan checkpoint -> single-policy template
    s_mixed = mixed.init(jax.random.PRNGKey(2), params)
    s_mixed, _ = mixed.jit_step(donate=False)(s_mixed, jnp.zeros(()))
    ckpt.save(s_mixed, str(tmp_path / "b"), step=1)
    template = single.init(jax.random.PRNGKey(2), params)
    with pytest.warns(UserWarning, match="pcm"):  # v changes policy back
        restored = ckpt.restore(template, str(tmp_path / "b"))
    for p in params:
        np.testing.assert_array_equal(
            np.asarray(restored["tiles"][p]["W"]),
            np.asarray(s_mixed["tiles"][p]["W"]), err_msg=p)


def test_trainer_state_roundtrip(tmp_path):
    """Full TrainState (tiles, opt, rng) survives save/restore and resumes."""
    from repro.core.device import DeviceConfig
    from repro.core.digital_opt import DigitalOptConfig, ScheduleConfig
    from repro.core.tile import TileConfig
    from repro.core.trainer import AnalogTrainer, TrainerConfig

    def loss_fn(params, batch, rng):
        return jnp.sum(params["w"] ** 2), {}

    dev = DeviceConfig(dw_min=0.01, sigma_pm=0.3)
    cfg = TrainerConfig(tile=TileConfig(algorithm="erider", device_p=dev, device_w=dev),
                        digital=DigitalOptConfig(kind="sgdm"),
                        schedule=ScheduleConfig(base_lr=0.1))
    trainer = AnalogTrainer(loss_fn, cfg, analog_filter=lambda p, l: True)
    state = trainer.init(jax.random.PRNGKey(0), {"w": jnp.ones((8, 8))})
    step = trainer.jit_step(donate=False)
    state, _ = step(state, jnp.zeros(()))
    ckpt.save(state, str(tmp_path), step=1)
    restored = ckpt.restore(state, str(tmp_path))
    s2a, _ = step(state, jnp.zeros(()))
    s2b, _ = step(restored, jnp.zeros(()))
    np.testing.assert_allclose(np.asarray(s2a["tiles"]["w"]["W"]),
                               np.asarray(s2b["tiles"]["w"]["W"]))
