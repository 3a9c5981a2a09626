"""Training cells: the program's jitted, donated ``AnalogTrainer.train_step``
as ``launch/train.py`` builds it, fed from a ring of seeded device batches.

Set-up builds the step and its state once, runs the first three steps
through the same call and feed as the window, and keeps what the check
needs of them: each step's loss, what each leaf's optimizer received in
step 1 (digital: the momentum buffer; analog: P, which starts at zero), the
step's expected pulse count, and the weights after step 3. The window then
continues that same state. Once the window has closed and the peak memory
has been read, the program's state is freed and the plain reference
(``bench/reference``) repeats the three steps from the same seed.
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

from .. import counts
from .. import trace as tr
from ..reference import erider, lm
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


def _program_config(config: dict):
    """The program's ModelConfig: its registry entry with every size of the
    configuration file applied."""
    from repro.configs import get_config
    from repro.configs.base import ModelConfig

    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    m = config["model"]
    cfg = dataclasses.replace(get_config(config["arch"]),
                              **{k: v for k, v in m.items() if k in fields})
    if jnp.dtype(cfg.dtype).name != m["dtype"]:
        raise ValueError(f"program dtype {cfg.dtype} is not the stated {m['dtype']}")
    kinds = {"gqa": ("attn",), "mla": ("mla",)}[m["attention"]]
    if cfg.pattern != kinds or cfg.n_periods != m["n_layers"] or cfg.tail:
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


class Cell:
    """The program's step and state for one training cell."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        from repro.core.digital_opt import DigitalOptConfig, ScheduleConfig
        from repro.core.trainer import AnalogTrainer, TrainerConfig
        from repro.launch.train import make_plan
        from repro.models.lm import LM

        self.m = config["model"]
        r = traffic["recipe"]
        t = time.perf_counter()
        model = LM(_program_config(config))
        plan = make_plan(config["algorithm"], smoke=False)
        probe = next(p for p in lm.leaf_paths(
            lm.param_shapes(self.m), is_leaf=lambda x: isinstance(x, tuple))
            if p.endswith("mlp/wi"))
        _check_tile_config(plan.policy_for(probe).tile, config["tile"])
        self.trainer = AnalogTrainer(model.loss, TrainerConfig(
            digital=DigitalOptConfig(kind="sgdm", momentum=r["momentum"],
                                     clip_norm=r["clip_norm"]),
            schedule=ScheduleConfig(kind="cosine", base_lr=r["base_lr"],
                                    warmup_steps=r["warmup_steps"],
                                    total_steps=r["total_steps"],
                                    min_ratio=r["min_ratio"]),
            microbatch=traffic["microbatch"]), plan=plan)
        self.timing = {"program_s": time.perf_counter() - t}

        t = time.perf_counter()
        w0 = jax.jit(functools.partial(lm.init_params, self.m))(lm.seed_key(seed))
        want = {p: (tuple(s.shape), jnp.dtype(s.dtype).name)
                for p, s in _flat(jax.eval_shape(model.init,
                                                 jax.random.PRNGKey(0))).items()}
        have = {p: (tuple(a.shape), a.dtype.name) for p, a in _flat(w0).items()}
        if want != have:
            raise ValueError(f"seeded weights do not match the program's "
                             f"parameter layout: {sorted(set(want.items()) ^ set(have.items()))}")
        key = jax.random.fold_in(jax.random.PRNGKey(seed % 2**32), seed >> 32)
        self.state = jax.jit(self.trainer.init, donate_argnums=1)(key, w0)
        del w0
        jax.block_until_ready(self.state)
        self.timing["init_s"] = time.perf_counter() - t
        t = time.perf_counter()
        B, S = traffic["batch"], traffic["seq"]
        data = bigram.batches(seed, self.m["vocab"], RING, B, S)
        self.ring = [{k: jax.device_put(v[i]) for k, v in data.items()}
                     for i in range(RING)]
        self.timing["batches_s"] = time.perf_counter() - t
        t = time.perf_counter()
        self.step = self.trainer.jit_step().lower(self.state, self.ring[0]).compile()
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


def run(config: dict, traffic: dict, seed: int, seconds: float, trace: bool,
        limits: dict, fault: str | None = None, t0: float | None = None) -> dict:
    """One run of a training cell; set-up is counted from ``t0`` (the
    process's start), or from here. ``fault`` plants a fault in the timed
    path for the harness's own tests: "frozen" (the step returns its state
    unchanged) or "half_batch" (the step sees half of each batch)."""
    t0 = time.perf_counter() if t0 is None else t0
    cell = Cell(config, traffic, seed)
    if fault == "frozen":
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
    dev = jax.devices()[0]
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)

    # the check: free the program, run the reference from the same seed
    ring, cell_timing = cell.ring, cell.timing
    del cell, met
    gc.collect()
    t_ref = time.perf_counter()
    w0 = lm.leaf_paths(jax.jit(functools.partial(lm.init_params, config["model"]))(
        lm.seed_key(seed)))
    ref = erider.Reference(config["model"], config["tile"], traffic["recipe"],
                           w0, seed, rows=reference_rows(traffic),
                           remat=True)
    del w0
    ref_steps = [ref.step(ring[k]) for k in range(CHECKED_STEPS)]
    w_moved = {"program": _program_w_change(start, end), "reference": ref.w_change()}
    nums = compare(prog, ref_steps, ref.change(), _program_change(start, end),
                   w_moved["reference"], w_moved["program"])
    del start, end
    timing = {"setup_s": setup_s, **cell_timing, "check_copies_s": aside,
              "reference_s": time.perf_counter() - t_ref}
    checks, correct = verdict(nums, limits, failed)
    rec = None
    if trace:
        m = config["model"]
        rec = {"trace": red, "steps": steps, "tokens": steps * B * S,
               "flops_per_token": counts.flops_per_token(m, S),
               "update_bytes_per_step": counts.update_bytes_per_step(m, config["tile"]),
               "peak_bytes": peak}
    return {"correct": correct, "attempted": steps, "failed": failed,
            "e2e": e2e, "record": rec, "peak_bytes": peak, "checks": checks,
            "left_out": sorted(set(ref_steps[0]["grad_norm"])
                               - set(kept_leaves(ref_steps))),
            "w_moved": w_moved, "timing": timing}
