"""Training cells: the program's jitted, donated ``AnalogTrainer.train_step``
as ``launch/train.py`` builds it, fed from a ring of seeded device batches.

A configuration that states ``"mesh": {"data": D, "model": M}`` runs on a
(data D, model M) mesh of the cell's D x M chips, laid out as
``launch/train.py`` lays it out: the program's sharding rules, the trainer
given the mesh, the state placed by ``state_shardings`` (tile stacks ZeRO-
split over data), batches by ``batch_shardings``, and the step compiled
for exactly that layout. Without ``mesh`` a cell runs on one chip.

Set-up builds the step and its state once, runs the first three steps
through the same call and feed as the window, and keeps what the check
needs of them: each step's loss, what each leaf's optimizer received in
step 1 (digital: the momentum buffer; analog: P, which starts at zero), the
step's expected pulse count, and the weights after step 3. The window then
continues that same state. Once the window has closed and the peak memory
has been read, the program's state is freed and the plain reference
(``bench/reference/erider.py`` over the model module the configuration's
``reference`` names) repeats the three steps from the same seed, its
arrays spread over the cell's devices.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import gc
import math
import shutil
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from .. import counts, scopes
from .. import trace as tr
from ..reference import erider
from ..traffic import bigram

RING = 16          # distinct batches the feed cycles through
CHECKED_STEPS = 3  # steps the reference repeats


def _flat(tree) -> dict:
    flat = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda x: x is None)[0]
    return {"/".join(str(k.key) for k in kp): v for kp, v in flat
            if v is not None}


def _tile_arrays(tiles, name: str) -> dict:
    """{analog path: array ``name`` (W or P)} out of the program's class-
    stacked tile storage."""
    where = {p: (g, i) for g, ps in tiles.index for i, p in enumerate(ps)}
    cls = {g: (c, ci) for c, gs in tiles.class_index for ci, g in enumerate(gs)}
    out = {}
    for p, (g, i) in where.items():
        c, ci = cls[g]
        out[p] = tiles.classes[c][name][ci, i]
    return out


def _norm(x):
    x = x.astype(jnp.float32)
    return jnp.sqrt(jnp.sum(x * x))


def _program_config(config: dict, ref):
    """The program's ModelConfig: its registry entry with every size of the
    configuration file applied, its layers of the kinds ``ref`` builds."""
    from repro.configs import get_config
    from repro.configs.base import ModelConfig

    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    m = config["model"]
    cfg = dataclasses.replace(get_config(config["arch"]),
                              **{k: v for k, v in m.items() if k in fields})
    if jnp.dtype(cfg.dtype).name != m["dtype"]:
        raise ValueError(f"program dtype {cfg.dtype} is not the stated {m['dtype']}")
    if cfg.layer_kinds != ref.layer_kinds(m):
        raise ValueError("layer pattern differs from the configuration")
    return cfg


def _check_tile_config(tc, stated: dict) -> None:
    """The program's TileConfig must carry every number the configuration
    states; a run on anything else would not be the configuration."""
    bad = []
    for k, v in stated.items():
        have = getattr(tc, k)
        if isinstance(v, dict):
            bad += [f"{k}.{kk}" for kk, vv in v.items() if getattr(have, kk) != vv]
        elif k == "state_dtype":
            if jnp.dtype(have).name != v:
                bad.append(k)
        elif have != v:
            bad.append(k)
    if bad:
        raise ValueError(f"tile configuration differs from the stated one: {bad}")


def cell_mesh(config: dict, devs):
    """The (data, model) mesh the configuration states over the cell's
    devices, with the program's sharding rules installed, as
    ``launch/train.py`` sets them; None for one chip."""
    shape = config.get("mesh")
    if not shape:
        return None
    from repro.distributed import sharding
    from repro.launch.mesh import make_host_mesh
    from repro.models.common import set_shard_rules

    mesh = make_host_mesh(shape["data"], shape["model"])
    if set(mesh.devices.flat) != set(devs):
        raise ValueError(f"mesh {mesh.devices.tolist()} is not the cell's devices {devs}")
    set_shard_rules(sharding.logical_rules(mesh))
    return mesh


def batches(traffic: dict, seed: int, vocab: int, mesh=None) -> list:
    """The ring of seeded batches on the device, or placed over the mesh by
    the program's ``batch_shardings``."""
    data = bigram.batches(seed, vocab, RING, traffic["batch"], traffic["seq"])
    sh = dict.fromkeys(data)  # one chip: the default device
    if mesh is not None:
        from repro.distributed import sharding

        sh = sharding.batch_shardings({k: v[0] for k, v in data.items()}, mesh)
    return [{k: jax.device_put(v[i], sh[k]) for k, v in data.items()}
            for i in range(RING)]


class Cell:
    """The program's step and state for one training cell, on one chip or
    laid out over ``mesh``."""

    def __init__(self, config: dict, traffic: dict, seed: int, ref, mesh=None):
        from repro.core.digital_opt import DigitalOptConfig, ScheduleConfig
        from repro.core.trainer import AnalogTrainer, TrainerConfig
        from repro.distributed import sharding
        from repro.launch.train import make_plan
        from repro.models.lm import LM

        self.m = config["model"]
        r = traffic["recipe"]
        t = time.perf_counter()
        model = LM(_program_config(config, ref))
        plan = make_plan(config["algorithm"], smoke=False)
        shapes = ref.leaf_paths(ref.param_shapes(self.m),
                                is_leaf=lambda x: isinstance(x, tuple))
        for p, s in shapes.items():
            if not ref.is_digital(p, len(s)):
                _check_tile_config(plan.policy_for(p).tile, config["tile"])
        self.trainer = AnalogTrainer(model.loss, TrainerConfig(
            digital=DigitalOptConfig(kind="sgdm", momentum=r["momentum"],
                                     clip_norm=r["clip_norm"]),
            schedule=ScheduleConfig(kind="cosine", base_lr=r["base_lr"],
                                    warmup_steps=r["warmup_steps"],
                                    total_steps=r["total_steps"],
                                    min_ratio=r["min_ratio"]),
            microbatch=traffic["microbatch"]), plan=plan, mesh=mesh)
        self.timing = {"program_s": time.perf_counter() - t}

        t = time.perf_counter()
        init_w, key_w = functools.partial(ref.init_params, self.m), ref.seed_key(seed)
        key = jax.random.fold_in(jax.random.PRNGKey(seed % 2**32), seed >> 32)
        # on a mesh every array is made where it lives (weights by the
        # program's parameter rules, state by its ZeRO rules, batches by its
        # batch rules) and the step is compiled for that layout; on one chip
        # the calls take no layout
        w_at, state_at, step_at = {}, {}, {}
        if mesh is not None:
            aw = jax.eval_shape(init_w, key_w)
            state_sh = sharding.state_shardings(
                jax.eval_shape(self.trainer.init, key, aw), mesh)
            w_at = {"out_shardings": sharding.params_shardings(aw, mesh)}
            state_at = {"out_shardings": state_sh}
        w0 = jax.jit(init_w, **w_at)(key_w)
        want = {p: (tuple(s.shape), jnp.dtype(s.dtype).name)
                for p, s in _flat(jax.eval_shape(model.init,
                                                 jax.random.PRNGKey(0))).items()}
        have = {p: (tuple(a.shape), a.dtype.name) for p, a in _flat(w0).items()}
        if want != have:
            raise ValueError(f"seeded weights do not match the program's "
                             f"parameter layout: {sorted(set(want.items()) ^ set(have.items()))}")
        self.state = jax.jit(self.trainer.init, donate_argnums=1, **state_at)(key, w0)
        del w0
        jax.block_until_ready(self.state)
        self.timing["init_s"] = time.perf_counter() - t
        t = time.perf_counter()
        self.ring = batches(traffic, seed, self.m["vocab"], mesh)
        self.timing["batches_s"] = time.perf_counter() - t
        t = time.perf_counter()
        if mesh is not None:
            step_at = {"in_shardings": (state_sh, jax.tree.map(
                lambda a: a.sharding, self.ring[0])),
                       "out_shardings": (state_sh, None)}
        self.step = self.trainer.jit_step(**step_at).lower(
            self.state, self.ring[0]).compile()
        self.timing["compile_s"] = time.perf_counter() - t
        self.n = 0

        def received(state):
            out = {p: _norm(v) for p, v in _flat(state["opt"]["mu"]).items()}
            out.update({p: _norm(v) for p, v in
                        _tile_arrays(state["tiles"], "P").items()})
            return out

        self._received = jax.jit(received)

    def run(self):
        """One step on the next batch of the ring; returns its metrics."""
        self.state, met = self.step(self.state, self.ring[self.n % RING])
        self.n += 1
        return met

    def snapshot(self, arrays=("W", "P")) -> dict:
        """Host copies of the digital weights and of the tile ``arrays``."""
        out = {p: np.asarray(v) for p, v in _flat(self.state["params"]).items()}
        for name in arrays:
            for p, v in _tile_arrays(self.state["tiles"], name).items():
                out[f"{p}#{name}"] = np.asarray(v)
        return out


def _gap(prog: dict, ref: dict, leaves) -> tuple[float, str]:
    """Worst leaf: |prog - ref| over the larger of the reference's value
    for that leaf and the median over ``leaves``."""
    med = float(np.median([ref[p] for p in leaves]))
    worst, at = 0.0, ""
    for p in leaves:
        g = abs(prog[p] - ref[p]) / max(ref[p], med, 1e-30)
        if g >= worst:
            worst, at = g, p
    return worst, at


def kept_leaves(ref_steps: list) -> list:
    """Leaves whose step-1 reference gradient is at least a thousandth of
    the median leaf's; the others move by round-off alone."""
    gn = ref_steps[0]["grad_norm"]
    med = float(np.median(list(gn.values())))
    return [p for p in gn if gn[p] >= 1e-3 * med]


def compare(prog: dict, ref_steps: list, ref_change: dict,
            prog_change: dict, ref_w: dict, prog_w: dict) -> dict:
    """The numbers the check compares (see PERF.md, "How correct is
    decided"), the leaf-wise ones over ``kept_leaves``."""
    kept = kept_leaves(ref_steps)
    rec = ref_steps[0]["received"]
    digital = [p for p in kept if p in prog["digital"]]
    analog = [p for p in kept if p not in prog["digital"]]
    out = {
        "loss_gap": max(abs(a - b["loss"]) / abs(b["loss"])
                        for a, b in zip(prog["loss"], ref_steps)),
        "grad1_gap": _gap(prog["received"], rec, digital),
        "update1_gap": _gap(prog["received"], rec, analog),
        "pulses1_gap": abs(prog["pulses"] - ref_steps[0]["pulses"])
        / ref_steps[0]["pulses"],
    }
    ch_d = _gap(prog_change, ref_change, digital)
    ch_a = _gap(prog_change, ref_change, analog)
    out["change3_gap"] = max(ch_d, ch_a)
    # W alone, all tiles together: over the larger of the two norms, since
    # W moves in few elements in three steps and may not move at all
    out["wchange3_gap"] = (abs(prog_w["norm"] - ref_w["norm"])
                           / max(prog_w["norm"], ref_w["norm"], 1e-30))
    return out


def verdict(nums: dict, limits: dict, failed: int = 0) -> tuple[dict, bool]:
    """Each limited number beside its limit, and whether the run is
    correct: no failed step and every number at or under its limit."""
    checks = {}
    for name, lim in limits.items():
        v = nums[name]
        checks[name] = {"value": v[0] if isinstance(v, tuple) else v,
                        "limit": lim}
        if isinstance(v, tuple):
            checks[name]["leaf"] = v[1]
    return checks, failed == 0 and all(
        c["value"] <= c["limit"] for c in checks.values())


def _program_change(start: dict, end: dict) -> dict:
    """{leaf: norm of its change}: digital weights, and for a tile W and P
    together (P starts at zero)."""
    def norm(a, b=None):
        d = a.astype(np.float32) if b is None else (
            a.astype(np.float32) - b.astype(np.float32))
        return float(np.sqrt(np.sum(d.astype(np.float64) ** 2)))

    out = {}
    for k in start:
        p, _, arr = k.partition("#")
        if not arr:
            out[p] = norm(end[k], start[k])
        else:
            dp = norm(end[f"{p}#P"])
            out[p] = math.sqrt(norm(end[k], start[k]) ** 2 + dp * dp)
    return out


def _program_w_change(start: dict, end: dict) -> dict:
    """W alone, over every tile: the norm of its change over the checked
    steps and the number of its elements that changed."""
    sq, moved = 0.0, 0
    for k in start:
        if k.endswith("#W"):
            d = end[k].astype(np.float32) - start[k].astype(np.float32)
            sq += float(np.sum(d.astype(np.float64) ** 2))
            moved += int(np.count_nonzero(d))
    return {"norm": math.sqrt(sq), "moved": moved}


def reference_rows(traffic: dict) -> int:
    """Batch rows per reference gradient block: at most 2,048 tokens."""
    return max(1, min(traffic["batch"], 2048 // traffic["seq"]))


def _spread(mesh, shape):
    """A sharding over every device of ``mesh``: the last dimension that
    divides among them is split, an array with none is replicated."""
    from jax.sharding import NamedSharding, PartitionSpec

    for d in reversed(range(len(shape))):
        if shape[d] % mesh.size == 0:
            spec = [None] * len(shape)
            spec[d] = tuple(mesh.axis_names)
            return NamedSharding(mesh, PartitionSpec(*spec))
    return NamedSharding(mesh, PartitionSpec())


def reference(config: dict, traffic: dict, seed: int, ref, mesh=None,
              **variant) -> erider.Reference:
    """The plain reference of a training cell from ``seed``: the weights of
    ``ref`` (the model module the configuration names) made on the device,
    or spread over the devices of ``mesh`` and with them every array of the
    reference's state; ``variant`` as ``erider.Reference`` takes it."""
    init, key = functools.partial(ref.init_params, config["model"]), ref.seed_key(seed)
    placed = {} if mesh is None else {"out_shardings": jax.tree.map(
        lambda a: _spread(mesh, a.shape), jax.eval_shape(init, key))}
    w0 = ref.leaf_paths(jax.jit(init, **placed)(key))
    return erider.Reference(config["model"], config["tile"], traffic["recipe"],
                            w0, seed, ref=ref, rows=reference_rows(traffic),
                            remat=True, **variant)


def _without_exchange(step, mesh):
    """``step`` with the data axis's exchange left out (a planted fault):
    each data replica steps the state on its own rows alone and keeps what
    it holds of the result, so its ZeRO shard is updated from its local
    gradient and its copy of a replicated leaf drifts from the others'. The
    metrics are the replicas' mean: step 1's loss stays the batch's."""
    D = mesh.shape["data"]
    replica = {d: i for i, row in enumerate(mesh.devices) for d in row.flat}

    take = jax.jit(lambda b, i: jax.tree.map(
        lambda x: jnp.concatenate([jnp.split(x, D)[i]] * D), b), static_argnums=1)

    def own_rows(batch, i):  # replica i's rows in every replica's place
        return jax.device_put(take(batch, i),
                              jax.tree.map(lambda a: a.sharding, batch))

    def held(*xs):
        shards = [{s.device: s.data for s in x.addressable_shards} for x in xs]
        return jax.make_array_from_single_device_arrays(
            xs[0].shape, xs[0].sharding,
            [shards[replica[d]][d] for d in shards[0]])

    def faulty(state, batch):
        outs = [step(jax.tree.map(jnp.copy, state), own_rows(batch, i))
                for i in range(D)]
        return (jax.tree.map(held, *[s for s, _ in outs]),
                jax.tree.map(lambda *v: sum(v) / D, *[m for _, m in outs]))

    return faulty


def peak_bytes(devs) -> int:
    """``peak_bytes_in_use`` of the fullest of ``devs``; 0 where the
    backend keeps no such count."""
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devs)


def run(config: dict, traffic: dict, seed: int, seconds: float, trace: bool,
        limits: dict, *, ref, devs, fault: str | None = None,
        t0: float | None = None) -> dict:
    """One run of a training cell on ``devs`` (one chip, or the mesh the
    configuration states), checked against ``ref``, the model module the
    configuration names; set-up is counted from ``t0`` (the process's
    start), or from here. ``fault`` plants a fault in the timed path for
    the harness's own tests: "frozen" (the step returns its state
    unchanged), "half_batch" (the step sees half of each batch) or, on a
    mesh, "no_exchange" (``_without_exchange``)."""
    t0 = time.perf_counter() if t0 is None else t0
    mesh = cell_mesh(config, devs)
    if fault == "no_exchange" and mesh is None:
        raise ValueError("no_exchange needs a mesh: one chip exchanges nothing")
    cell = Cell(config, traffic, seed, ref, mesh)
    if fault == "no_exchange":
        cell.step = _without_exchange(cell.step, mesh)
    elif fault == "frozen":
        step = cell.step
        cell.step = lambda s, b: (s, step(jax.tree.map(jnp.copy, s), b)[1])
    elif fault == "half_batch":
        step = cell.step
        half = jax.jit(lambda b: jax.tree.map(
            lambda x: jnp.concatenate([x[: x.shape[0] // 2]] * 2), b))
        cell.step = lambda s, b: step(s, half(b))
    prog = {"loss": [], "digital": set(_flat(cell.state["params"]))}
    ts = time.perf_counter()
    start = cell.snapshot(("W",))
    aside = time.perf_counter() - ts
    for k in range(CHECKED_STEPS):
        met = cell.run()
        prog["loss"].append(float(met["loss"]))
        if k == 0:
            prog["pulses"] = float(met["tile/pulses"])
            prog["received"] = {p: float(v) for p, v in
                                cell._received(cell.state).items()}
    ts = time.perf_counter()
    end = cell.snapshot()
    aside += time.perf_counter() - ts
    jax.block_until_ready(cell.state)
    setup_s = time.perf_counter() - t0 - aside

    B, S = traffic["batch"], traffic["seq"]
    failed = 0
    if not trace:
        pending = collections.deque()
        t_start = time.perf_counter()
        while True:
            pending.append(cell.run()["loss"])
            if len(pending) > 2 and not math.isfinite(float(pending.popleft())):
                failed += 1
            if time.perf_counter() - t_start >= seconds:
                break
        failed += sum(not math.isfinite(float(x)) for x in pending)
        jax.block_until_ready(cell.state)
        window = time.perf_counter() - t_start
        steps = cell.n - CHECKED_STEPS
        e2e = {"train_tokens_per_s": steps * B * S / window, "setup_s": setup_s}
        red = None
    else:
        k = traffic["trace_steps"]
        tdir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(tdir)
        with jax.profiler.TraceAnnotation(tr.WINDOW):
            for _ in range(k):
                met = cell.run()
            jax.block_until_ready(cell.state)
        jax.profiler.stop_trace()
        failed += not math.isfinite(float(met["loss"]))
        steps = k
        red = tr.reduce(tr.load(tr.find(tdir)))
        shutil.rmtree(tdir, ignore_errors=True)
        e2e = None
    peak = peak_bytes(devs)
    # the compiled step's instruction -> scope names, for the scope readers
    op_scopes = scopes.op_scopes(cell.step.as_text()) if trace else None

    # the check: free the program, run the reference from the same seed
    ring, cell_timing = cell.ring, cell.timing
    del cell, met
    gc.collect()
    t_ref = time.perf_counter()
    plain = reference(config, traffic, seed, ref, mesh)
    ref_steps = [plain.step(ring[k]) for k in range(CHECKED_STEPS)]
    w_moved = {"program": _program_w_change(start, end), "reference": plain.w_change()}
    nums = compare(prog, ref_steps, plain.change(), _program_change(start, end),
                   w_moved["reference"], w_moved["program"])
    del start, end
    timing = {"setup_s": setup_s, **cell_timing, "check_copies_s": aside,
              "reference_s": time.perf_counter() - t_ref}
    checks, correct = verdict(nums, limits, failed)
    rec = None
    if trace:
        m = config["model"]
        rec = {"trace": red, "steps": steps, "tokens": steps * B * S,
               "chips": len(devs),
               "flops_per_token": counts.flops_per_token(m, S, ref),
               "update_bytes_per_step": counts.update_bytes_per_step(
                   m, config["tile"], ref),
               "peak_bytes": peak, "op_scopes": op_scopes}
    return {"correct": correct, "attempted": steps, "failed": failed,
            "e2e": e2e, "record": rec, "peak_bytes": peak, "checks": checks,
            "left_out": sorted(set(ref_steps[0]["grad_norm"])
                               - set(kept_leaves(ref_steps))),
            "w_moved": w_moved, "timing": timing}
