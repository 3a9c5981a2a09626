"""Batched (shape-grouped) tile engine tests.

Covers the TileBank layout, the O(distinct-shapes) program-instancing
guarantee of the grouped train_step, equivalence with the legacy looped
engine, on-the-fly upgrade of legacy per-tile checkpoints, and the stacked
sharding specs.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.device import DeviceConfig
from repro.core.digital_opt import DigitalOptConfig, ScheduleConfig
from repro.core.tile import TileBank, TileConfig, group_name, parse_group_name
from repro.core.trainer import AnalogTrainer, TrainerConfig, merge_effective

DEV = DeviceConfig(dw_min=0.01, sigma_pm=0.3, sigma_d2d=0.1, sigma_c2c=0.05)


def _loss_fn(params, batch, rng):
    return sum(jnp.sum(v ** 2) for _, v in sorted(params.items())), {}


def _trainer(engine: str, algorithm: str = "erider") -> AnalogTrainer:
    cfg = TrainerConfig(
        tile=TileConfig(algorithm=algorithm, device_p=DEV, device_w=DEV,
                        lr_p=0.5, lr_w=0.5, gamma=0.1, eta=0.1, chopper_p=0.1),
        digital=DigitalOptConfig(kind="sgd"),
        schedule=ScheduleConfig(kind="constant", base_lr=0.1),
        engine=engine,
    )
    return AnalogTrainer(_loss_fn, cfg, analog_filter=lambda p, l: True)


def _params(n_square: int = 8, shape=(16, 16)):
    p = {f"l{i}": 0.1 * jnp.ones(shape) for i in range(n_square)}
    p["odd"] = 0.1 * jnp.ones((4, 24))
    return p


def test_group_name_roundtrip():
    assert parse_group_name(group_name((64, 128), jnp.float32, "nM")) \
        == ((64, 128), "float32", "nM", "")
    assert parse_group_name(group_name((4, 8, 16), jnp.bfloat16, "Mnn")) \
        == ((4, 8, 16), "bfloat16", "Mnn", "")
    # policy-tagged keys (mixed AnalogPlan) round-trip the 4th component
    assert parse_group_name(group_name((64, 128), jnp.float32, "nM", "rider")) \
        == ((64, 128), "float32", "nM", "rider")
    assert parse_group_name("g8x8_float32_Mn_ppola") \
        == ((8, 8), "float32", "Mn", "pola")
    # legacy (shape, dtype)-only keys parse with empty tags
    assert parse_group_name(group_name((64, 128), jnp.float32)) \
        == ((64, 128), "float32", "", "")
    # tag charset is a subset of dtype charset: the dtype must not eat it
    assert parse_group_name("g8x8_float32_nn") == ((8, 8), "float32", "nn", "")
    assert parse_group_name("not_a_group/W") is None


def test_spec_aware_grouping_splits_rule_families():
    """Same-shape tiles whose owning weights shard differently (wq's
    (None, "M") vs wo's ("M", None)) must land in distinct groups so their
    stacks can carry the model axis."""
    from repro.core.tile import group_tiles

    shapes = {}
    for i in range(3):
        shapes[f"l{i}/attn/wq"] = (16, 16)
        shapes[f"l{i}/attn/wk"] = (16, 16)
        shapes[f"l{i}/attn/wo"] = (16, 16)
    index = dict(group_tiles(shapes, TileConfig()))
    assert set(index) == {"g16x16_float32_nM", "g16x16_float32_Mn"}
    assert index["g16x16_float32_nM"] == tuple(sorted(
        p for p in shapes if p.endswith(("wq", "wk"))))
    assert index["g16x16_float32_Mn"] == tuple(sorted(
        p for p in shapes if p.endswith("wo")))


def test_scan_groups_bit_identical_to_unroll():
    """Acceptance criterion: the scanned grouped engine (same-structure
    group classes under one lax.scan) is bit-identical to the unrolled
    grouped engine — the per-group CRC-folded keys are the same. Tile
    STATE must match bitwise; the mean-based telemetry scalars are only
    checked to float32 ULP precision, because XLA is free to tile the
    (value-irrelevant) metric reductions differently inside a scan body
    than in an unrolled vmap."""

    def run(scan):
        cfg = TrainerConfig(
            tile=TileConfig(algorithm="erider", device_p=DEV, device_w=DEV,
                            lr_p=0.5, lr_w=0.5, gamma=0.1, eta=0.1,
                            chopper_p=0.1),
            digital=DigitalOptConfig(kind="sgd"),
            schedule=ScheduleConfig(kind="constant", base_lr=0.1),
            scan_groups=scan,
        )
        tr = AnalogTrainer(_loss_fn, cfg, analog_filter=lambda p, l: True)
        params = {}
        for i in range(3):  # wq/wk -> nM group, wo -> Mn group: 2-group class
            params[f"l{i}/attn/wq"] = 0.1 * jnp.ones((8, 8))
            params[f"l{i}/attn/wo"] = 0.1 * jnp.ones((8, 8))
        state = tr.init(jax.random.PRNGKey(5), params)
        step = tr.jit_step(donate=False)
        for _ in range(5):
            state, m = step(state, jnp.zeros(()))
        return state, m

    s_scan, m_scan = run(True)
    s_unroll, m_unroll = run(False)
    assert len(s_scan["tiles"].groups) == 2
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)),
        s_scan["tiles"], s_unroll["tiles"])
    for k in m_scan:
        np.testing.assert_allclose(np.asarray(m_scan[k]),
                                   np.asarray(m_unroll[k]),
                                   rtol=1e-6, err_msg=k)


def test_class_name_roundtrip_and_partition():
    """Scan classes: same-structure groups share one pre-stacked pytree.
    The class key is the sorted '+'-join of its member group names, and the
    partition signature is (treedef, leaf shapes/dtypes, policy) — so the
    nM and Mn groups of one transformer block co-scan while a different
    shape or policy splits off."""
    from repro.core.tile import (TileBank, class_name, class_partition,
                                 group_name, init_tile, parse_class_name)

    assert class_name(("a", "b")) == "a+b"
    assert parse_class_name("a+b") == ("a", "b")
    assert parse_class_name("solo") == ("solo",)

    cfg = TileConfig(algorithm="erider", device_p=DEV, device_w=DEV)
    key = jax.random.PRNGKey(0)

    def stack(n, shape):
        per = [init_tile(jax.random.fold_in(key, i), 0.1 * jnp.ones(shape), cfg)
               for i in range(n)]
        return jax.tree.map(lambda *ls: jnp.stack(ls), *per)

    nm, mn = group_name((8, 8), jnp.float32, "nM"), \
        group_name((8, 8), jnp.float32, "Mn")
    odd = group_name((4, 24), jnp.float32, "nM")
    groups = {nm: stack(3, (8, 8)), mn: stack(3, (8, 8)),
              odd: stack(1, (4, 24))}
    index = tuple((g, tuple(f"{g}/p{i}" for i in range(3 if g != odd else 1)))
                  for g in (nm, mn, odd))
    cidx = class_partition(groups, index)
    assert dict(cidx) == {class_name((nm, mn)): (nm, mn), odd: (odd,)}

    bank = TileBank(groups, index)
    assert [c for c, _ in bank.class_index] == sorted(
        [class_name((nm, mn)), odd])
    # class leaves are (C, n, *member); the per-group view slices them back
    assert bank.classes[class_name((nm, mn))]["W"].shape == (2, 3, 8, 8)
    for g in (nm, mn, odd):
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)), bank.groups[g], groups[g])


def test_fused_backend_bit_identical_to_vmap_hash():
    """Acceptance criterion: the fused batched pulse-update backend (one
    flattened update over each class stack, fastrng noise) is bit-identical
    to the vmap reference running rng='hash' — the per-tile hash streams
    are position-independent, so flattening (C, n) -> (C*n) changes no
    bits."""

    def run(backend):
        cfg = TrainerConfig(
            tile=TileConfig(algorithm="erider", device_p=DEV, device_w=DEV,
                            lr_p=0.5, lr_w=0.5, gamma=0.1, eta=0.1,
                            chopper_p=0.1, rng="hash",
                            update_backend=backend),
            digital=DigitalOptConfig(kind="sgd"),
            schedule=ScheduleConfig(kind="constant", base_lr=0.1),
        )
        tr = AnalogTrainer(_loss_fn, cfg, analog_filter=lambda p, l: True)
        params = {}
        for i in range(3):  # 2-group (nM + Mn) class plus an odd singleton
            params[f"l{i}/attn/wq"] = 0.1 * jnp.ones((8, 8))
            params[f"l{i}/attn/wo"] = 0.1 * jnp.ones((8, 8))
        params["odd"] = 0.1 * jnp.ones((4, 24))
        state = tr.init(jax.random.PRNGKey(7), params)
        step = tr.jit_step(donate=False)
        for _ in range(5):
            state, m = step(state, jnp.zeros(()))
        return state, m

    s_f, m_f = run("fused")
    s_v, m_v = run("vmap")
    # the two banks' aux policies differ (update_backend), so compare the
    # class-keyed storage leaves directly
    assert set(s_f["tiles"].classes) == set(s_v["tiles"].classes)
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)),
        dict(s_f["tiles"].classes), dict(s_v["tiles"].classes))
    assert set(m_f) == set(m_v)
    for k in m_f:
        np.testing.assert_allclose(np.asarray(m_f[k]), np.asarray(m_v[k]),
                                   rtol=1e-6, err_msg=k)


def test_init_groups_by_shape_and_matches_looped_init():
    """Grouped init is a pure re-layout: every per-path view must be bitwise
    identical to the legacy looped init (same per-tile fold_in seeds)."""
    params = _params()
    bank = _trainer("grouped").init(jax.random.PRNGKey(0), params)["tiles"]
    looped = _trainer("looped").init(jax.random.PRNGKey(0), params)["tiles"]
    assert isinstance(bank, TileBank)
    assert len(bank) == len(params) == len(looped)
    assert len(bank.groups) == 2  # (16,16) stack of 8 + (4,24) stack of 1
    for p, ts in looped.items():
        view = bank[p]
        assert jax.tree_util.tree_structure(view) \
            == jax.tree_util.tree_structure(ts), p
        jax.tree.map(
            lambda a, b: np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b), err_msg=p), view, ts)


def test_grouped_step_one_pulse_update_instance_per_shape_group():
    """Acceptance criterion: with >= 8 same-shape analog layers the jitted
    train_step contains ONE vmapped pulse-update instance per shape group,
    not per tile — the lowered program of the 8-layer model has exactly as
    many pulse-update instances as the 1-layer model, while the looped
    engine scales them O(tiles).

    An instance is counted by its stochastic-rounding ``floor``: every
    analog update rounds its pulse count once (E-RIDER updates P and W, so
    two per tile), and nothing else in the step floors. The count does not
    depend on how the RNG lowers."""

    def lowered_text(engine, n):
        tr = _trainer(engine)
        params = {f"l{i}": 0.1 * jnp.ones((16, 16)) for i in range(n)}
        state = tr.init(jax.random.PRNGKey(0), params)
        return jax.jit(tr.train_step).lower(state, jnp.zeros(())).as_text()

    floors_grouped_1 = lowered_text("grouped", 1).count("stablehlo.floor")
    text_grouped_8 = lowered_text("grouped", 8)
    floors_grouped_8 = text_grouped_8.count("stablehlo.floor")
    text_looped_8 = lowered_text("looped", 8)
    floors_looped_8 = text_looped_8.count("stablehlo.floor")

    assert floors_grouped_1 == 2, floors_grouped_1  # P and W of one group
    assert floors_grouped_8 == floors_grouped_1, (
        floors_grouped_8, floors_grouped_1)
    assert floors_looped_8 == 8 * floors_grouped_1, (
        floors_looped_8, floors_grouped_1)
    # the program itself must stop scaling with layer count
    assert len(text_grouped_8) < 0.6 * len(text_looped_8)


@pytest.mark.parametrize("algorithm", ["sgd", "ttv2", "agad", "rider", "erider"])
def test_grouped_trains_like_looped(algorithm):
    """Both engines reduce the quadratic loss to a comparable level (exact
    bits differ: the grouped engine uses split-once-per-group keys)."""

    def run(engine):
        tr = _trainer(engine, algorithm)
        state = tr.init(jax.random.PRNGKey(3), _params(4))
        step = tr.jit_step(donate=False)
        m = {}
        for _ in range(60):
            state, m = step(state, jnp.zeros(()))
        return state, {k: float(v) for k, v in m.items()}

    s_g, m_g = run("grouped")
    s_l, m_l = run("looped")
    initial = float(_loss_fn(_params(4), None, None)[0])
    # engine parity is the claim here (convergence quality per algorithm is
    # test_algorithms'); agad's thresholded transfer barely moves in 60 steps
    assert m_g["loss"] < initial, (algorithm, m_g["loss"], initial)
    assert abs(m_g["loss"] - m_l["loss"]) < 0.25 * max(m_l["loss"], 1e-3), \
        (algorithm, m_g["loss"], m_l["loss"])
    # same metric names out of both engines
    assert set(m_g) == set(m_l)


def test_grouped_metrics_aggregate_over_all_tiles():
    tr = _trainer("grouped")
    state = tr.init(jax.random.PRNGKey(0), _params())
    _, m = tr.jit_step(donate=False)(state, jnp.zeros(()))
    for k in ("tile/pulses", "tile/gp_sq", "tile/sp_err", "tile/prog_events"):
        assert np.isfinite(float(m[k])), k


def test_abstract_state_matches_init_structure():
    """Dry-run lowering depends on abstract_state agreeing with init."""
    tr = _trainer("grouped")
    params = _params()
    concrete = tr.init(jax.random.PRNGKey(0), params)
    abstract = tr.abstract_state(
        jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params))
    cflat = jax.tree_util.tree_flatten_with_path(concrete)[0]
    aflat = jax.tree_util.tree_flatten_with_path(abstract)[0]
    assert len(cflat) == len(aflat)
    for (ckp, cleaf), (akp, aleaf) in zip(cflat, aflat):
        assert ckp == akp
        assert tuple(cleaf.shape) == tuple(aleaf.shape), (ckp, cleaf.shape, aleaf.shape)
        assert cleaf.dtype == aleaf.dtype, (ckp, cleaf.dtype, aleaf.dtype)


def test_legacy_per_tile_checkpoint_restores_into_grouped(tmp_path):
    """A checkpoint written by the legacy looped engine (per-tile layout)
    restores into the grouped TileBank template by stacking member tiles."""
    from repro.checkpoint import ckpt

    params = _params(3)
    looped = _trainer("looped")
    state_l = looped.init(jax.random.PRNGKey(0), params)
    state_l, _ = looped.jit_step(donate=False)(state_l, jnp.zeros(()))
    ckpt.save(state_l, str(tmp_path), step=1)

    grouped = _trainer("grouped")
    template = grouped.init(jax.random.PRNGKey(0), params)
    restored = ckpt.restore(template, str(tmp_path))
    assert isinstance(restored["tiles"], TileBank)
    for p in state_l["tiles"]:
        np.testing.assert_array_equal(
            np.asarray(restored["tiles"][p]["W"]),
            np.asarray(state_l["tiles"][p]["W"]), err_msg=p)
        np.testing.assert_array_equal(
            np.asarray(restored["tiles"][p]["Qd"]),
            np.asarray(state_l["tiles"][p]["Qd"]), err_msg=p)
    # effective weights agree between the two layouts
    eff_l = merge_effective(state_l["params"], state_l["tiles"], looped.cfg.tile)
    eff_g = merge_effective(restored["params"], restored["tiles"], grouped.cfg.tile)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b)), eff_l, eff_g)
    # and the restored grouped state steps
    restored2, m = grouped.jit_step(donate=False)(restored, jnp.zeros(()))
    assert np.isfinite(float(m["loss"]))
    assert int(restored2["step"]) == 2


def test_legacy_shape_dtype_checkpoint_rekeys_into_spec_groups(tmp_path):
    """A checkpoint written with (shape, dtype)-only group keys (one stack
    mixing wq and wo) restores into the spec-aware template: each new group
    gathers its member rows out of the old combined stack."""
    from repro.checkpoint import ckpt
    from repro.core.tile import group_name

    params = {}
    for i in range(2):
        params[f"l{i}/attn/wq"] = 0.1 * jnp.ones((8, 8))
        params[f"l{i}/attn/wo"] = 0.1 * jnp.ones((8, 8))
    tr = _trainer("grouped")
    state = tr.init(jax.random.PRNGKey(1), params)
    state, _ = tr.jit_step(donate=False)(state, jnp.zeros(()))

    # rebuild the bank in the PR-1 layout: one (shape, dtype) stack holding
    # ALL tiles sorted by path (exactly what the old group_tiles produced)
    bank = state["tiles"]
    union = sorted(bank.paths())
    legacy_name = group_name((8, 8), jnp.float32)
    legacy_stack = jax.tree.map(
        lambda *leaves: jnp.stack(leaves), *(bank[p] for p in union))
    legacy_bank = TileBank({legacy_name: legacy_stack},
                           ((legacy_name, tuple(union)),))
    legacy_state = dict(state)
    legacy_state["tiles"] = legacy_bank
    ckpt.save(legacy_state, str(tmp_path), step=1)

    restored = ckpt.restore(state, str(tmp_path))
    assert set(g for g, _ in restored["tiles"].index) \
        == {"g8x8_float32_nM", "g8x8_float32_Mn"}
    for p in union:
        jax.tree.map(
            lambda a, b: np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b), err_msg=p),
            restored["tiles"][p], bank[p])
    # the re-keyed state steps
    restored2, m = tr.jit_step(donate=False)(restored, jnp.zeros(()))
    assert np.isfinite(float(m["loss"]))


def test_v3_pergroup_checkpoint_restores_into_v4_bit_identical(tmp_path):
    """Acceptance criterion: a layout-v3 checkpoint (per-GROUP stacks, no
    ``tile_classes`` manifest) restores into the class-keyed v4 storage
    bit-identically, and the restored state trains bit-identically to the
    state the checkpoint was taken from. The v3 fixture is built by
    down-converting a v4 save: each (C, n, *member) class array is split
    into its C per-group (n, *member) arrays, exactly what the v3 writer
    produced."""
    import json
    import zlib

    from repro.checkpoint import ckpt

    tr = _trainer("grouped")
    params = {}
    for i in range(3):  # wq -> nM, wo -> Mn: one 2-group class, plus odd
        params[f"l{i}/attn/wq"] = 0.1 * jnp.ones((8, 8))
        params[f"l{i}/attn/wo"] = 0.1 * jnp.ones((8, 8))
    params["odd"] = 0.1 * jnp.ones((4, 24))
    state = tr.init(jax.random.PRNGKey(2), params)
    step = tr.jit_step(donate=False)
    state, _ = step(state, jnp.zeros(()))
    assert any(len(gs) > 1 for _, gs in state["tiles"].class_index)
    ckpt.save(state, str(tmp_path), step=1)

    # ---- down-convert the written step to layout v3 ----
    d = tmp_path / "step_000000001"
    with open(d / "manifest.json") as f:
        manifest = json.load(f)
    classes = manifest.pop("tile_classes")
    arrays = {}
    for fname in sorted({m["file"] for m in manifest["arrays"].values()}):
        with np.load(d / fname) as z:
            arrays.update({k: z[k] for k in z.files})
    new_arrays, new_meta = {}, {}
    for key, meta in manifest["arrays"].items():
        arr = arrays[meta["npz_key"]]
        parts = key.split("/")
        if len(parts) == 3 and parts[0] == "tiles" and parts[1] in classes:
            for ci, g in enumerate(classes[parts[1]]["groups"]):
                gkey = f"tiles/{g}/{parts[2]}"
                garr = arr[ci]
                safe = gkey.replace("/", "__")
                new_arrays[safe] = garr
                new_meta[gkey] = {"shape": list(garr.shape),
                                  "dtype": meta["dtype"],
                                  "file": "arrays_000.npz", "npz_key": safe,
                                  "crc32": zlib.crc32(garr.tobytes())}
        else:
            new_arrays[meta["npz_key"]] = arr
            new_meta[key] = {**meta, "file": "arrays_000.npz"}
    for fname in {m["file"] for m in manifest["arrays"].values()}:
        (d / fname).unlink()
    np.savez(d / "arrays_000.npz", **new_arrays)
    manifest["arrays"] = new_meta
    manifest["layout"] = 3
    with open(d / "manifest.json", "w") as f:
        json.dump(manifest, f)

    restored = ckpt.restore(state, str(tmp_path), verify=True)
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)),
        restored, state)
    s2a, _ = step(state, jnp.zeros(()))
    s2b, _ = step(restored, jnp.zeros(()))
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)),
        s2a["tiles"], s2b["tiles"])


def test_grouped_checkpoint_roundtrip(tmp_path):
    from repro.checkpoint import ckpt

    tr = _trainer("grouped")
    state = tr.init(jax.random.PRNGKey(0), _params(3))
    step = tr.jit_step(donate=False)
    state, _ = step(state, jnp.zeros(()))
    ckpt.save(state, str(tmp_path), step=1)
    restored = ckpt.restore(state, str(tmp_path), verify=True)
    s2a, _ = step(state, jnp.zeros(()))
    s2b, _ = step(restored, jnp.zeros(()))
    for g, _paths in state["tiles"].index:
        np.testing.assert_allclose(
            np.asarray(s2a["tiles"].groups[g]["W"]),
            np.asarray(s2b["tiles"].groups[g]["W"]))


def test_grouped_tile_spec_stack_axis():
    """The stack axis is the ZeRO axis when the group size divides the data
    axes; otherwise ZeRO falls back into the member dims."""
    from repro.distributed.sharding import grouped_tile_spec
    from jax.sharding import PartitionSpec as P

    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 4, "model": 8}

    spec = grouped_tile_spec(("attn/wq",), (8, 30, 64), FakeMesh(), zero=True)
    assert spec == P("data", None, "model")
    spec2 = grouped_tile_spec(("attn/wq",), (3, 32, 64), FakeMesh(), zero=True)
    assert spec2 == P(None, "data", "model")
    spec3 = grouped_tile_spec(("attn/wq",), (3, 30, 64), FakeMesh(), zero=False)
    assert spec3 == P(None, None, "model")
    # same-shape members with conflicting rules (wq: (None,M), wo: (M,None))
    # must not silently transpose half the stack — member dims replicate,
    # with a one-time warning naming the offending paths
    with pytest.warns(UserWarning, match=r"attn/wo.*attn/wq"):
        spec4 = grouped_tile_spec(("attn/wo", "attn/wq"), (8, 64, 64),
                                  FakeMesh(), zero=False)
    assert spec4 == P(None, None, None)
    # ... and only once per offending stack
    import warnings as _warnings
    with _warnings.catch_warnings():
        _warnings.simplefilter("error")
        grouped_tile_spec(("attn/wo", "attn/wq"), (8, 64, 64),
                          FakeMesh(), zero=False)
    spec5 = grouped_tile_spec(("attn/wq", "mlp/wi"), (8, 30, 64),
                              FakeMesh(), zero=True)
    assert spec5 == P("data", None, "model")  # rules agree -> keep model axis


def test_state_shardings_grouped_smoke():
    """state_shardings over a grouped TrainState must produce a spec for
    every leaf (host mesh: everything replicates on 1 device)."""
    from repro.distributed.sharding import state_shardings
    from repro.launch.mesh import make_host_mesh

    tr = _trainer("grouped")
    state = tr.init(jax.random.PRNGKey(0), _params(2))
    sh = state_shardings(state, make_host_mesh(1, 1))
    n_specs = len(jax.tree.leaves(sh))
    assert n_specs == len(jax.tree.leaves(state))


def test_seeded_kernel_train_step_bit_identical_to_operand_path():
    """One E-RIDER step of the qwen2 smoke model with the full-width tile
    configuration (seeded devices, hash noise, bf16 state, every metric):
    the Pallas backend, whose seeded kernel draws devices and noise in
    VMEM, gives the jnp path's losses, P, W and ``tile/*`` bit for bit."""
    import dataclasses

    from repro import api
    from repro.configs import get_config
    from repro.kernels import ops
    from repro.launch.train import make_tile_cfg
    from repro.models.lm import LM

    model = LM(get_config("qwen2-0.5b", smoke=True))
    plan = api.plan_from_spec("erider", lambda a: make_tile_cfg(a, smoke=False))
    tcfg = plan.policy_for("blocks/l0/mlp/wi").tile
    assert not tcfg.store_device and tcfg.rng == "hash"
    tr = AnalogTrainer(model.loss, TrainerConfig(
        digital=DigitalOptConfig(kind="sgdm", clip_norm=1.0)), plan=plan)
    toks = jax.random.randint(jax.random.PRNGKey(3), (2, 16), 0, 512)
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, axis=1)}

    def run(backend):
        ops.set_backend(backend)
        try:
            state = tr.init(jax.random.PRNGKey(1),
                            model.init(jax.random.PRNGKey(0)))
            return tr.jit_step(donate=False)(state, batch)
        finally:
            ops.set_backend(None)

    (s_p, m_p), (s_r, m_r) = run("pallas"), run("ref")
    assert set(m_p) == set(m_r) and any(k.startswith("tile/") for k in m_p)
    for k in m_p:
        np.testing.assert_array_equal(np.asarray(m_p[k]), np.asarray(m_r[k]),
                                      err_msg=k)
    for c in s_r["tiles"].classes:
        for arr in ("P", "W"):
            a, b = (np.asarray(s["tiles"].classes[c][arr], np.float32)
                    for s in (s_p, s_r))
            np.testing.assert_array_equal(a, b, err_msg=f"{c}/{arr}")
    moved = [c for c in s_r["tiles"].classes
             if np.any(np.asarray(s_r["tiles"].classes[c]["P"]) != 0)]
    assert moved
