"""AnalogTrainer: wires any JAX model to the analog tile algorithms.

Given a loss function over a parameter pytree and an ``AnalogPlan``
(ordered path rules -> TilePolicy; see core/plan.py) deciding which leaves
live on which analog tile stacks, builds pure jit-able ``init`` /
``train_step`` functions:

  1. ``begin_step`` phase (chopper draw / Q-tilde sync, Alg.3 l.3-6)
  2. forward/backward on the *effective* parameter tree
     (analog leaves -> scale * W̄, paper's mixed weight)
  3. digital leaves -> SGD/Adam; analog leaves -> pulse-based tile update

Tiles are stored shape-grouped (TileBank): all tiles of one (shape, dtype,
sharding-rule template, policy) stack along a leading axis and phases 1/3b
run as ONE vmapped instance per group — each group's graph built with its
own policy's static TileConfig, so one train_step mixes algorithms and
device presets freely; groups with identical stacked structure AND policy
(same member shape/count/dtype, e.g. the wq-family and wo-family of a
uniform transformer) additionally share one ``jax.lax.scan``'ed graph, so
the jitted train_step stays O(distinct structures) — O(1) in depth — not
O(layers). ``TrainerConfig(engine="looped")`` keeps the legacy per-tile
dict layout and Python loop as a reference baseline;
``TrainerConfig(scan_groups=False)`` unrolls the groups (bit-identical to
the scanned path — same per-group keys).

Per-tile/per-group RNG keys fold in a CRC of the tile path (init, looped
engine) or of the group's member-path tuple (grouped engine) — NOT an
enumeration index — so a model trained under a mixed plan is bit-identical
to the same tiles trained side by side in separate single-policy trainers.

The same train_step is used single-host and under GSPMD (the dry-run lowers
it with sharded in/out specs; gradients reduce over the data axes before
pulse quantization, so Assumption 3.4 applies to the global gradient).
Passing ``mesh=`` pins the grouped update path to explicit in/out specs —
the stack dim on the ZeRO/data axes, member dims on the model axis — and
runs its phases under shard_map (see distributed/sharding.py).
"""
from __future__ import annotations

import dataclasses
import logging
import zlib
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import algorithms as alg
from .digital_opt import DigitalOptConfig, ScheduleConfig, apply_opt, init_opt, lr_at
from .paths import path_str
from .plan import AnalogPlan, TilePolicy, legacy_plan, plan_partition
from .tile import (TileBank, TileConfig, _class_member, abstract_tile,
                   abstract_tile_group, group_policies, group_tiles,
                   init_tile, stack_tiles)

logger = logging.getLogger("repro.plan")


def _crc_fold(key, name: str):
    """Fold a stable CRC of ``name`` into ``key`` — path-content keyed RNG
    (independent of enumeration order / co-trained tiles)."""
    return jax.random.fold_in(key, np.uint32(zlib.crc32(name.encode())))

# Names of the train step's phases, as ``jax.named_scope`` writes them into
# the op_name metadata of the compiled program: the five phases of
# ``train_step`` in order, then the parts of the pulse update inside
# ``tile_update`` (core/algorithms.py, kernels/ops.py). Metadata only: the
# scopes add no operation. The benchmark attributes device time to them.
TRACE_SCOPES = ("tile_begin", "tile_effective", "fwd_bwd", "digital_opt",
                "tile_update", "pulse_operands", "pulse_kernel",
                "tile_metrics")

PathPredicate = Callable[[str, Any], bool]
LossFn = Callable[[Any, Any, jax.Array], Tuple[jax.Array, Dict[str, jax.Array]]]


@dataclasses.dataclass(frozen=True)
class TrainerConfig:
    tile: TileConfig = TileConfig()
    digital: DigitalOptConfig = DigitalOptConfig()
    schedule: ScheduleConfig = ScheduleConfig()
    # gradient accumulation: split the batch into `microbatch` slices and
    # accumulate grads before the (single) pulse update — required to fit
    # activations at LM scale (and keeps Assumption 3.4 applied to the
    # full-batch gradient, as in the single-device math).
    microbatch: int = 1
    accum_dtype: Any = jnp.float32
    # Tile engine. "grouped" (default) stacks tiles by (shape, dtype, rule
    # template) into a TileBank and runs one vmapped begin_step/update per
    # *group*, so the jitted train_step contains O(distinct shapes) copies
    # of the pulse-update graph instead of O(layers). "looped" keeps the
    # legacy per-tile dict layout and Python loop (reference/benchmark
    # baseline; also the layout of pre-TileBank checkpoints).
    engine: str = "grouped"
    # Scan same-structure group classes with jax.lax.scan instead of
    # unrolling one vmapped instance per group: program size stays O(1) in
    # the number of rule-split groups. False unrolls (bit-identical).
    scan_groups: bool = True

    def __post_init__(self):
        assert self.engine in ("grouped", "looped"), self.engine


def default_analog_filter(path: str, leaf) -> bool:
    """Analog-tile every >=2-D weight except embeddings/heads (kept digital,
    as in the paper's setups; see DESIGN.md §5)."""
    if getattr(leaf, "ndim", 0) < 2:
        return False
    lowered = path.lower()
    return not any(s in lowered for s in ("embed", "vocab", "lm_head", "pos"))


def partition_params(params, analog_filter: PathPredicate):
    """Split a param tree into (digital tree w/ None at analog slots,
    {path: leaf} analog dict)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    analog = {}
    dig_leaves = []
    for kp, leaf in flat:
        p = path_str(kp)
        if analog_filter(p, leaf):
            analog[p] = leaf
            dig_leaves.append(None)
        else:
            dig_leaves.append(leaf)
    digital = jax.tree_util.tree_unflatten(treedef, dig_leaves)
    return digital, analog


def _group_tile_cfg(bank: TileBank, group: str, default: TileConfig) -> TileConfig:
    pol = bank.policy(group)
    return pol.tile if (pol is not None and pol.tile is not None) else default


def effective_weights(tiles, tcfg: TileConfig, policies=None) -> Dict[str, jax.Array]:
    """{path: model-space effective weight} for a TileBank (one doubly-
    vmapped effective_weight per class stack, read in place, then static
    ``eff[ci, i]`` slices per member path) or a legacy per-tile dict
    (``policies``: optional {path: TileConfig})."""
    if isinstance(tiles, TileBank):
        out = {}
        pidx = dict(tiles.index)
        for cname, gnames in tiles.class_index:
            gcfg = _group_tile_cfg(tiles, gnames[0], tcfg)
            eff = jax.vmap(jax.vmap(
                lambda ts: alg.effective_weight(ts, gcfg)))(
                    tiles.classes[cname])
            for ci, g in enumerate(gnames):
                for i, p in enumerate(pidx[g]):
                    out[p] = eff[ci, i]
        return out
    policies = policies or {}
    return {p: alg.effective_weight(ts, policies.get(p, tcfg))
            for p, ts in tiles.items()}


def merge_effective(digital, tiles, tcfg: TileConfig, policies=None):
    """Rebuild the full parameter tree with analog leaves replaced by
    their effective (model-space) weights. ``tiles`` is a TileBank (whose
    per-group policies win over ``tcfg``) or a legacy {path: TileState}
    dict."""
    eff = effective_weights(tiles, tcfg, policies)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        digital, is_leaf=lambda x: x is None
    )
    out = []
    for kp, leaf in flat:
        p = path_str(kp)
        if leaf is None and p in eff:
            out.append(eff[p])
        else:
            out.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, out)


def extract_analog_grads(grads, tiles):
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    agrads = {}
    for kp, leaf in flat:
        p = path_str(kp)
        if p in tiles:
            agrads[p] = leaf
    return agrads


def mask_digital_grads(grads, tiles):
    flat, treedef = jax.tree_util.tree_flatten_with_path(grads)
    out = []
    for kp, leaf in flat:
        out.append(None if path_str(kp) in tiles else leaf)
    return jax.tree_util.tree_unflatten(treedef, out)


class TrainState(dict):
    """Pytree: step, key, params (digital; None at analog), tiles, opt."""


jax.tree_util.register_pytree_with_keys(
    TrainState,
    lambda d: (tuple((jax.tree_util.DictKey(k), d[k]) for k in sorted(d)),
               tuple(sorted(d))),
    lambda keys, vals: TrainState(zip(keys, vals)),
)


def _vmap_tile(fn):
    """Lift a per-tile ``fn(tile_state, key, *extras)`` to one group stack:
    vmap over the member axis, wrapping each tile's raw (2,) key data."""
    return jax.vmap(
        lambda ts, kr, *ex: fn(ts, jax.random.wrap_key_data(kr), *ex))


def _stack_rows(results):
    """Restack per-group results into a class-shaped (C, ...) tree (the
    unrolled reference path; singletons use a free expand_dims)."""
    if len(results) == 1:
        return jax.tree.map(lambda l: jnp.expand_dims(l, 0), results[0])
    return jax.tree.map(lambda *ls: jnp.stack(ls), *results)


class AnalogTrainer:
    def __init__(
        self,
        loss_fn: LossFn,
        cfg: TrainerConfig,
        analog_filter: Optional[PathPredicate] = None,
        mesh=None,
        *,
        plan: Optional[AnalogPlan] = None,
    ):
        """``plan``: an AnalogPlan mapping parameter paths to TilePolicies
        (heterogeneous devices/algorithms per path; see core/plan.py and
        the ``repro.api`` facade). When omitted, the deprecated
        ``(cfg.tile, analog_filter)`` pair is mapped onto a one-rule plan
        behind a one-time DeprecationWarning.

        ``mesh``: optional jax.sharding.Mesh. When set, the grouped tile
        phases run under explicit in/out specs (stack dim on the ZeRO/data
        axes, member dims on the model axis per the owning weight's rule);
        when None, layout is left to GSPMD propagation from the caller's
        in_shardings."""
        self.loss_fn = loss_fn
        self.cfg = cfg
        if plan is None:
            plan = legacy_plan(cfg.tile, analog_filter or default_analog_filter)
        elif analog_filter is not None:
            raise ValueError("pass either plan= or analog_filter=, not both")
        self.plan = plan
        self.analog_filter = analog_filter
        self.mesh = mesh
        # {path: TileConfig} resolved against real leaves by init /
        # abstract_state — the looped engine's policy source (the grouped
        # engine carries policies in the TileBank treedef instead)
        self._path_tile_cfgs: Dict[str, TileConfig] = {}

    def _remember_path_cfgs(self, analog, policies) -> None:
        self._path_tile_cfgs.update(
            {p: (policies[p].tile or self.cfg.tile) for p in analog})

    def _tile_cfg_of(self, path: str) -> TileConfig:
        """Static TileConfig of one analog path (looped engine): resolved
        with the leaf at init/abstract_state time when possible (rank
        guards and legacy predicates need the leaf). Paths never seen by
        init — e.g. a restored state stepped without one — re-resolve
        leafless; a rule the plan cannot evaluate without a leaf falls
        back to the trainer default, which is exactly the legacy
        single-policy behavior."""
        cfg = self._path_tile_cfgs.get(path)
        if cfg is not None:
            return cfg
        try:
            pol = self.plan.policy_for(path)
        except Exception:  # leaf-dependent legacy predicate
            return self.cfg.tile
        return pol.tile if (pol is not None and pol.tile is not None) \
            else self.cfg.tile

    def describe_plan(self, params) -> str:
        """One-line plan summary: ``N analog paths -> K groups, algorithms
        {...}, M digital leaves``. Works on abstract params."""
        digital, analog, policies = plan_partition(params, self.plan)
        index = group_tiles({p: analog[p].shape for p in analog},
                            self.cfg.tile, policies)
        pols = group_policies(index, policies) or {}
        algos: Dict[str, int] = {}
        for g, paths in index:
            pol = pols.get(g)
            a = pol.tile.algorithm if pol is not None else self.cfg.tile.algorithm
            algos[a] = algos.get(a, 0) + len(paths)
        n_dig = sum(leaf is not None for leaf in jax.tree.leaves(
            digital, is_leaf=lambda x: x is None))
        algos_s = "{" + ", ".join(f"{a}: {n}" for a, n in sorted(algos.items())) + "}"
        return (f"plan: {len(analog)} analog paths -> {len(index)} groups, "
                f"algorithms {algos_s}, {n_dig} digital leaves")

    def _constrain(self, tree, member_paths, prefix: int = 0):
        if self.mesh is None:
            return tree
        from repro.distributed import sharding as shd

        return shd.constrain_stacked(tree, member_paths, self.mesh,
                                     prefix=prefix)

    def _stacked_call(self, fn, n: int, *args, axis: int = 0):
        """``fn(*args)`` over stacks of ``n`` tiles; with a mesh, as a
        manual map over the stack axis (``shard_stacked_call``)."""
        if self.mesh is None:
            return fn(*args)
        from repro.distributed import sharding as shd

        return shd.shard_stacked_call(fn, self.mesh, n, *args, axis=axis)

    def _grouped_apply(self, bank: TileBank, make_vfn, key, extras=()):
        """Apply one stack-level function per scan class, in place.

        ``make_vfn(tcfg)`` returns a *stack-level* function
        ``vfn(group_state, keys_raw, *extra)`` over one (n, *member) group
        stack, specialized to the class's static TileConfig (its TilePolicy
        under a mixed plan, the trainer default otherwise) — usually
        ``_vmap_tile`` of a per-tile function, or ``alg.update_batched``
        for the fused backend. The bank's class storage already carries the
        (C, n, *member) layout ``lax.scan`` wants, so the scanned path
        consumes ``bank.classes`` directly: zero ``jnp.stack`` on entry,
        zero ``leaf[ci]`` gather on exit (the acceptance HLO check counts
        restack concatenates). Per-group keys fold a CRC of the group's
        member-path tuple — identical between the scanned and unrolled
        engines (bit-identical results) and independent of which other
        groups co-train. With a mesh, stacks are pinned to explicit specs
        and every phase runs as a manual map over the stack axis
        (``shard_stacked_call``), so the Pallas kernel is never left to
        GSPMD.

        extras: {class-name: (C, n, ...) stacked array} pytrees of
        per-class inputs (analog gradients). Returns {class-name: vfn
        output with a leading class axis} — singleton classes get a free
        ``expand_dims``; ``scan_groups=False`` unrolls per group and
        restacks (the PR-5-equivalent data-movement reference path).

        Classes under a ``update_backend='fused'`` policy skip the scan
        entirely: the class stack IS the batch of a hand-batched update, so
        the (C, n) axes flatten to one (C*n, *member) stack — a free
        reshape on class-keyed storage — and every phase runs as a single
        fused program with no per-iteration slice/scatter. Per-tile key
        streams are position-independent, so this is bit-identical to the
        scanned and unrolled paths.
        """
        index = dict(bank.index)

        def keys_raw(paths):
            kg = _crc_fold(key, "|".join(paths))
            return jax.random.key_data(jax.random.split(kg, len(paths)))

        out = {}
        for cname, gnames in bank.class_index:
            tcfg = _group_tile_cfg(bank, gnames[0], self.cfg.tile)
            vfn = make_vfn(tcfg)
            cstate = bank.classes[cname]
            if tcfg.update_backend == "fused":
                n_c = len(gnames)
                paths_list = tuple(index[g] for g in gnames)
                flat_n = sum(len(ps) for ps in paths_list)
                kr = (jnp.concatenate([keys_raw(ps) for ps in paths_list])
                      if n_c > 1 else keys_raw(paths_list[0]))

                def flat(t):
                    return jax.tree.map(
                        lambda l: l.reshape((-1,) + l.shape[2:]), t)

                args = (self._constrain(flat(cstate), paths_list),
                        kr) + tuple(
                            self._constrain(flat(e[cname]), paths_list)
                            for e in extras)
                res = self._stacked_call(vfn, flat_n, *args)
                out[cname] = self._constrain(
                    jax.tree.map(
                        lambda l: l.reshape((n_c, flat_n // n_c)
                                            + l.shape[1:]), res),
                    paths_list, prefix=1)
            elif len(gnames) > 1 and self.cfg.scan_groups:
                paths_list = tuple(index[g] for g in gnames)
                kr = jnp.stack([keys_raw(index[g]) for g in gnames])
                cstate = self._constrain(cstate, paths_list, prefix=1)
                ex = tuple(self._constrain(e[cname], paths_list, prefix=1)
                           for e in extras)

                def body(carry, xs):
                    return carry, vfn(*xs)

                def scanned(*xs):
                    return jax.lax.scan(body, (), xs)[1]

                res = self._stacked_call(scanned, len(paths_list[0]),
                                         cstate, kr, *ex, axis=1)
                out[cname] = self._constrain(res, paths_list, prefix=1)
            else:
                results = []
                for ci, g in enumerate(gnames):
                    paths = index[g]
                    args = (self._constrain(_class_member(cstate, ci),
                                            paths),
                            keys_raw(paths)) + tuple(
                                self._constrain(
                                    _class_member(e[cname], ci),
                                    paths)
                                for e in extras)
                    res = self._stacked_call(vfn, len(paths), *args)
                    results.append(self._constrain(res, paths))
                out[cname] = _stack_rows(results)
        return out

    # -- state ------------------------------------------------------------
    def init(self, key, params, sp_estimates: Optional[Dict[str, Any]] = None) -> TrainState:
        digital, analog, policies = plan_partition(params, self.plan)
        self._remember_path_cfgs(analog, policies)
        logger.info(self.describe_plan(params))
        per_tile = {}
        for p, w0 in sorted(analog.items()):
            sp = (sp_estimates or {}).get(p)
            per_tile[p] = init_tile(_crc_fold(key, p), w0,
                                    policies[p].tile or self.cfg.tile, sp)
        if self.cfg.engine == "grouped":
            index = group_tiles({p: w.shape for p, w in analog.items()},
                                self.cfg.tile, policies)
            tiles = stack_tiles(per_tile, index,
                                group_policies(index, policies))
        else:
            tiles = per_tile
        return TrainState(
            step=jnp.zeros((), jnp.int32),
            key=jax.random.key_data(key).astype(jnp.uint32),
            params=digital,
            tiles=tiles,
            opt=init_opt(digital, self.cfg.digital),
        )

    def abstract_state(self, params_shapes) -> TrainState:
        """ShapeDtypeStruct state (dry-run lowering; no allocation)."""
        digital, analog, policies = plan_partition(params_shapes, self.plan)
        self._remember_path_cfgs(analog, policies)
        if self.cfg.engine == "grouped":
            index = group_tiles({p: w.shape for p, w in analog.items()},
                                self.cfg.tile, policies)
            pols = group_policies(index, policies)
            tiles = TileBank(
                {g: abstract_tile_group(
                    analog[paths[0]].shape, len(paths),
                    (pols or {}).get(g, TilePolicy(self.cfg.tile)).tile)
                 for g, paths in index},
                index,
                pols,
            )
        else:
            tiles = {p: abstract_tile(w.shape, policies[p].tile or self.cfg.tile)
                     for p, w in sorted(analog.items())}
        opt = init_opt(
            jax.tree.map(lambda s: None if s is None else jax.ShapeDtypeStruct(s.shape, jnp.float32),
                         digital, is_leaf=lambda x: x is None),
            self.cfg.digital,
        )
        return TrainState(
            step=jax.ShapeDtypeStruct((), jnp.int32),
            key=jax.ShapeDtypeStruct((2,), jnp.uint32),
            params=digital,
            tiles=tiles,
            opt=opt,
        )

    # -- step -------------------------------------------------------------
    def train_step(self, state: TrainState, batch) -> Tuple[TrainState, Dict[str, jax.Array]]:
        tcfg = self.cfg.tile
        key = jax.random.wrap_key_data(state["key"])
        key, k_begin, k_model, k_upd = jax.random.split(key, 4)
        grouped = isinstance(state["tiles"], TileBank)

        # phase 1: chopper / Q-tilde sync — one vmapped begin_step per
        # group under the group's policy TileConfig, scanned per
        # same-structure same-policy class (grouped engine), or one per
        # tile (legacy looped engine)
        with jax.named_scope("tile_begin"):
            if grouped:
                bank: TileBank = state["tiles"]
                begun = self._grouped_apply(
                    bank,
                    lambda gcfg: _vmap_tile(
                        lambda ts, k: alg.begin_step(ts, k, gcfg)),
                    k_begin)
                tiles = TileBank.from_classes(begun, bank.index, bank.class_index,
                                              bank.policies)
                path_cfgs = None
            else:
                path_cfgs = {p: self._tile_cfg_of(p) for p in state["tiles"]}
                tiles = {
                    p: alg.begin_step(ts, _crc_fold(k_begin, p), path_cfgs[p])
                    for p, ts in sorted(state["tiles"].items())
                }

        # phase 2: fwd/bwd on effective weights (with grad accumulation)
        with jax.named_scope("tile_effective"):
            eff = merge_effective(state["params"], tiles, tcfg, path_cfgs)
        with jax.named_scope("fwd_bwd"):
            mb = self.cfg.microbatch
            if mb <= 1:
                (loss, aux), grads = jax.value_and_grad(self.loss_fn, has_aux=True)(
                    eff, batch, k_model
                )
            else:
                def slice_batch(i):
                    return jax.tree.map(
                        lambda x: x.reshape(mb, x.shape[0] // mb, *x.shape[1:])[i]
                        if getattr(x, "ndim", 0) >= 1 else x,
                        batch,
                    )

                def mb_step(carry, i):
                    g_acc, l_acc, a_acc = carry
                    (l, a), g = jax.value_and_grad(self.loss_fn, has_aux=True)(
                        eff, slice_batch(i), jax.random.fold_in(k_model, i)
                    )
                    g_acc = jax.tree.map(
                        lambda acc, gi: acc + gi.astype(self.cfg.accum_dtype), g_acc, g
                    )
                    a_acc = jax.tree.map(lambda x, y: x + y, a_acc, a)
                    return (g_acc, l_acc + l, a_acc), None

                # first microbatch outside the scan (defines aux structure)
                (l0, a0), g0_ = jax.value_and_grad(self.loss_fn, has_aux=True)(
                    eff, slice_batch(0), jax.random.fold_in(k_model, 0)
                )
                g0 = jax.tree.map(lambda g: g.astype(self.cfg.accum_dtype), g0_)
                (grads, loss, aux), _ = jax.lax.scan(
                    mb_step, (g0, l0.astype(jnp.float32), a0), jnp.arange(1, mb)
                )
                inv = 1.0 / mb
                grads = jax.tree.map(lambda g: g * inv, grads)
                loss = loss * inv
                aux = jax.tree.map(lambda x: x * inv, aux)

        lr = lr_at(state["step"], self.cfg.schedule)

        # phase 3a: digital branch
        with jax.named_scope("digital_opt"):
            dgrads = mask_digital_grads(grads, tiles)
            new_params, new_opt, gnorm = apply_opt(
                state["params"], dgrads, state["opt"], state["step"], lr,
                self.cfg.digital)

        # phase 3b: analog branch (pulse updates) — grouped engine runs ONE
        # vmapped pulse-update per group over the stacked state (scanned per
        # same-structure class), with a single split-once-per-group key;
        # looped engine is the legacy O(tiles) unrolled reference.
        with jax.named_scope("tile_update"):
            agrads = extract_analog_grads(grads, tiles)
            tile_metrics = []  # per-class (C*n,) metric vectors / per-tile scalars
            if grouped:
                # One flat stack + free reshape per class, laid out by the
                # static class index — grads enter the scan in storage order
                # with a single rank-(member+1) concatenate (no per-group
                # restack, no per-step dict re-walk).
                pidx = dict(tiles.index)
                stacked_grads = {}
                for cname, gnames in tiles.class_index:
                    flat = [agrads[p] for g in gnames for p in pidx[g]]
                    cdims = tiles.classes[cname]["W"].shape[:2]
                    arr = (jnp.stack(flat) if len(flat) > 1
                           else jnp.expand_dims(flat[0], 0))
                    stacked_grads[cname] = arr.reshape(cdims + flat[0].shape)

                def make_update_vfn(gcfg):
                    if gcfg.update_backend == "fused":
                        return lambda ts, kr, grd: alg.update_batched(
                            ts, grd, kr, gcfg, lr)
                    return _vmap_tile(
                        lambda ts, k, grd: alg.update(ts, grd, k, gcfg, lr))

                res = self._grouped_apply(
                    tiles, make_update_vfn, k_upd, extras=(stacked_grads,))
                new_tiles = TileBank.from_classes(
                    {c: res[c][0] for c, _ in tiles.class_index},
                    tiles.index, tiles.class_index, tiles.policies)
                tile_metrics = [
                    jax.tree.map(lambda v: v.reshape(-1), res[c][1])
                    for c, _ in tiles.class_index]
            else:
                new_tiles = {}
                for p, ts in sorted(tiles.items()):
                    ts2, m = alg.update(ts, agrads[p], _crc_fold(k_upd, p),
                                        path_cfgs[p], lr)
                    new_tiles[p] = ts2
                    tile_metrics.append(m)

            # mixed plans: metric key sets differ per algorithm — aggregate
            # the union over whichever groups emit each key
            tile_agg = {}
            with jax.named_scope("tile_metrics"):
                for k in sorted({k for m in tile_metrics for k in m}):
                    vals = jnp.concatenate(
                        [jnp.atleast_1d(m[k]) for m in tile_metrics if k in m])
                    tile_agg[f"tile/{k}"] = (
                        jnp.sum(vals) if k in ("pulses", "prog_events")
                        else jnp.mean(vals))

        metrics = {"loss": loss, "lr": lr, "grad_norm": gnorm, **aux, **tile_agg}

        new_state = TrainState(
            step=state["step"] + 1,
            key=jax.random.key_data(key).astype(jnp.uint32),
            params=new_params,
            tiles=new_tiles,
            opt=new_opt,
        )
        return new_state, metrics

    def jit_step(self, donate: bool = True, **jit_kwargs):
        return jax.jit(
            self.train_step,
            donate_argnums=(0,) if donate else (),
            **jit_kwargs,
        )
