"""Sharding rules: parameter-path patterns -> PartitionSpecs.

MaxText-style logical rules keyed on the stable parameter names produced by
the model zoo. Highlights:

* model axis ("model"): attention heads / MLA up-projections / FFN hidden /
  expert ffn dim / RG-LRU blocks / SSD heads / vocab.
* data axes ("pod","data"): batch; ZeRO-style sharding of analog tile state
  and digital optimizer moments (legal because analog updates are
  element-local — DESIGN.md §3).
* scan-stacked body params (path contains "/body/") get a leading None for
  the period axis.
* decode caches: batch dim on data axes when divisible, otherwise the
  sequence dim (long_500k batch=1 -> ring/sequence sharding).

All choices are divisibility-checked against the actual leaf shapes; a dim
that doesn't divide falls back to replication (GSPMD would pad, but uneven
pads on 512 ways waste memory).
"""
from __future__ import annotations

import re
import warnings
from typing import Any, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.paths import path_str


def mesh_axis_sizes(mesh: Mesh):
    data_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    model_ax = "model" if "model" in mesh.axis_names else None
    dsize = 1
    for a in data_axes:
        dsize *= mesh.shape[a]
    msize = mesh.shape[model_ax] if model_ax else 1
    return data_axes, dsize, model_ax, msize


# (regex, spec template) — templates use "M" for model, "D" for data axes,
# None for replicated; matched against the *trailing* dims of the leaf.
PARAM_RULES: Tuple[Tuple[str, Tuple], ...] = (
    (r"embed$", ("M", None)),
    (r"head$", (None, "M")),
    (r"(wq|wk|wv|wuq|wuk|wuv)$", (None, "M")),
    (r"(bq|bk|bv)$", ("M",)),
    (r"attn/wo$", ("M", None)),
    (r"cross/wo$", ("M", None)),
    (r"(wdq|wdkv|wkr)$", (None, None)),
    (r"(qln|kvln|qn|kn|ln1|ln2|lnx|ln_f|norm)$", (None,)),
    (r"mlp/(wi|wg)$", (None, "M")),
    (r"mlp/wo$", ("M", None)),
    (r"moe/router$", (None, None)),
    (r"moe/(wi|wg)$", (None, None, "M")),
    (r"moe/wo$", (None, "M", None)),
    (r"moe/(swi|swg)$", (None, "M")),
    (r"moe/swo$", ("M", None)),
    (r"mix/(wx|wy|wz|wb|wc|wdt)$", (None, "M")),
    (r"mix/(war|wai)$", ("M", None, None)),
    (r"mix/lam$", ("M",)),
    (r"mix/(conv|conv_x|conv_b|conv_c)$", (None, "M")),
    (r"mix/(a_log|dt_bias|d_skip)$", ("M",)),
    (r"mix/wout$", ("M", None)),
    (r"wout$", ("M", None)),
    (r"(conv1|conv2)/w$", (None, None, None, None)),
    (r"/b$", (None,)),
    (r"/w$", (None, "M")),  # convnet fc fallback
)


def _resolve(template, shape, data_axes, dsize, model_ax, msize, zero_dim: Optional[int]):
    """Template -> PartitionSpec with divisibility checks. ``zero_dim`` marks
    the first replicated dim to ZeRO-shard over the data axes (or None)."""
    offset = len(shape) - len(template)
    spec: list = [None] * len(shape)
    for i, t in enumerate(template):
        dim = offset + i
        if t == "M" and model_ax and msize > 1 and shape[dim] % msize == 0 \
                and shape[dim] > 0:
            spec[dim] = model_ax
    if zero_dim is not None and data_axes and dsize > 1:
        for dim in range(len(shape)):
            if spec[dim] is None and shape[dim] % dsize == 0 and shape[dim] >= dsize:
                spec[dim] = data_axes if len(data_axes) > 1 else data_axes[0]
                break
    return P(*spec)


def rule_template(path: str, ndim: int) -> Tuple:
    """Mesh-independent spec template of a parameter path, normalized to
    ``ndim`` dims (leading dims pad with None; body-scan params gain a
    leading None for the period axis). This is the rule identity used for
    spec-aware tile grouping: two paths with equal templates shard
    identically on every mesh, so their tiles may share a stack."""
    template = None
    for pat, tmpl in PARAM_RULES:
        if re.search(pat, path):
            template = tmpl
            break
    if template is None:
        template = (None,) * ndim
    if "/body/" in path and ndim > len(template):
        template = (None,) + tuple(template)
    while len(template) < ndim:
        template = (None,) + tuple(template)
    return tuple(template[-ndim:]) if ndim else ()


def template_tag(template) -> str:
    """Short stable name of a rule template, used inside tile-group keys:
    (None, "M") -> "nM", ("M", None, None) -> "Mnn", () -> "s" (scalar)."""
    if not template:
        return "s"
    return "".join({"M": "M", "D": "D"}.get(t, "n") for t in template)


def param_spec(path: str, shape, mesh: Mesh, zero: bool = False) -> P:
    data_axes, dsize, model_ax, msize = mesh_axis_sizes(mesh)
    template = rule_template(path, len(shape))
    return _resolve(template, shape, data_axes, dsize, model_ax, msize,
                    0 if zero else None)


_TILE_SLOTS = r"(W|P|Qd|Qt|H|dev_p/(gamma|rho)|dev_w/(gamma|rho))"

# group signatures already warned about (one warning per offending stack)
_MIXED_RULE_WARNED: set = set()


def grouped_tile_spec(member_paths, shape, mesh: Mesh,
                      zero: bool = True) -> P:
    """PartitionSpec for a stacked tile-group array (n, *member-shape).

    Member dims inherit the owning weights' model-axis spec. Groups key on
    (shape, dtype, rule template) — see ``repro.core.tile.group_tiles`` — so
    every member of a stack resolves to the same spec and the member dims
    can always carry the model axis. A stack that nonetheless mixes rules
    (hand-built banks, or pre-spec-aware legacy groups) replicates its
    member dims rather than silently transposing half its tiles' layout,
    and warns once naming the offending paths. The leading stack axis is
    the natural ZeRO/scan axis (element-local updates, DESIGN.md §3) and
    takes the data axes when the group size divides, falling back to the
    first divisible replicated member dim otherwise.
    """
    if isinstance(member_paths, str):
        member_paths = (member_paths,)
    data_axes, dsize, model_ax, msize = mesh_axis_sizes(mesh)
    per_path = {p: param_spec(p, shape[1:], mesh) for p in member_paths}
    specs = set(per_path.values())
    if len(specs) == 1:
        inner = specs.pop()
    else:
        inner = P(*([None] * (len(shape) - 1)))
        sig = tuple(sorted(member_paths))
        if sig not in _MIXED_RULE_WARNED:
            _MIXED_RULE_WARNED.add(sig)
            warnings.warn(
                "tile group mixes partition rules; model axis dropped "
                "(member dims replicate) for stack of "
                + ", ".join(f"{p}->{per_path[p]}" for p in sig)
                + " — re-group with spec-aware keys (core.tile.group_tiles)",
                stacklevel=2)
    spec = [None] + list(inner) + [None] * (len(shape) - 1 - len(inner))
    if zero and data_axes and dsize > 1:
        daxes = data_axes if len(data_axes) > 1 else data_axes[0]
        if shape[0] % dsize == 0 and shape[0] >= dsize:
            spec[0] = daxes
        else:
            for dim in range(1, len(shape)):
                if spec[dim] is None and shape[dim] % dsize == 0 \
                        and shape[dim] >= dsize:
                    spec[dim] = daxes
                    break
    return P(*spec)


def merge_specs(specs):
    """Dim-wise agreement of PartitionSpecs: keep an axis only where every
    spec places it; disagreeing dims replicate. Used to constrain a scan
    stack of same-structure groups whose member rules differ."""
    specs = [tuple(s) for s in specs]
    n = max((len(s) for s in specs), default=0)
    specs = [s + (None,) * (n - len(s)) for s in specs]
    return P(*[s0 if all(s[d] == s0 for s in specs) else None
               for d, s0 in enumerate(specs[0])]) if specs else P()


def constrain_stacked(tree, member_paths, mesh: Mesh, zero: bool = True,
                      prefix: int = 0):
    """with_sharding_constraint over every stacked tile-state leaf of
    ``tree`` (a stacked TileState, a stacked gradient array, or any pytree
    of (n, *member-shape) arrays).

    Leaves of rank >= prefix + 3 (``prefix`` extra leading axes — the scan
    class axis — then stack axis + a >=2-D member weight) get the group
    spec from ``grouped_tile_spec``; per-tile scalars (n,) and seeds (n, 2)
    pin to replicated, matching ``state_shardings`` so a donated train_step
    round-trips without resharding. ``member_paths`` may be a tuple of path
    tuples, one per scanned group — the constraint is then the dim-wise
    agreement of the groups' specs (merge_specs).
    """
    paths_list = [member_paths] if member_paths and isinstance(
        member_paths[0], str) else list(member_paths)

    def c(leaf):
        nd = getattr(leaf, "ndim", 0)
        if nd < prefix + 3:
            spec = P(*([None] * nd))
        else:
            inner = merge_specs([
                grouped_tile_spec(ps, leaf.shape[prefix:], mesh, zero=zero)
                for ps in paths_list])
            spec = P(*([None] * prefix + list(inner)))
        return jax.lax.with_sharding_constraint(
            leaf, NamedSharding(mesh, spec))

    return jax.tree.map(c, tree)


# (stack size, data size) pairs already warned about by shard_stacked_call
_UNSPLIT_WARNED: set = set()


def shard_stacked_call(fn, mesh: Mesh, n: int, *args, axis: int = 0):
    """Run ``fn(*args)`` as a manual map (``jax.shard_map``) over ``mesh``.

    Every argument and output carries the stack axis (length ``n``) at
    position ``axis``. ``fn`` must be element-local over that axis — true of
    every stacked tile phase (begin_step / update vmapped over the stack):
    tile updates touch only their own members, so the map needs no
    collectives and is bit-identical to the global call. The stack axis is
    split over the data axes when ``n`` divides their size. Otherwise every
    device runs the whole stack: exact, at the price of redundant work, and
    announced by a warning once per stack size.

    The tile phases hold the Pallas pulse-update kernel, and GSPMD cannot
    partition a Mosaic custom call, so with a mesh they always run here,
    never under automatic partitioning.
    """
    data_axes, dsize, _, _ = mesh_axis_sizes(mesh)
    split = dsize > 1 and n % dsize == 0
    if dsize > 1 and not split and (n, dsize) not in _UNSPLIT_WARNED:
        _UNSPLIT_WARNED.add((n, dsize))
        warnings.warn(
            f"tile stack of {n} does not divide the data axes ({dsize}); "
            "every device updates the whole stack", stacklevel=2)
    daxes = (data_axes if len(data_axes) > 1 else data_axes[0]) \
        if split else None

    def spec_of(leaf):
        nd = getattr(leaf, "ndim", axis + 1)
        return P(*([None] * axis + [daxes] + [None] * (nd - axis - 1)))

    in_specs = jax.tree.map(spec_of, args)
    out_specs = jax.tree.map(spec_of, jax.eval_shape(fn, *args))
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)(*args)


def state_shardings(state_tree, mesh: Mesh, zero_states: bool = True):
    """NamedShardings for an AnalogTrainer TrainState (abstract or concrete).

    Tile/optimizer arrays inherit the owning weight's spec plus ZeRO over the
    data axes; scalars replicate. Class-keyed (TileBank) states carry
    (C, n, *member) leaves: the class axis replicates (it is the scan axis),
    the stack axis takes the ZeRO/data axes and the member dims the
    dim-wise agreement of the member groups' model-axis specs — exactly the
    spec ``constrain_stacked(prefix=1)`` pins inside the step, so a donated
    train_step round-trips without resharding. Legacy per-tile states keep
    the old behaviour.
    """
    from repro.core.tile import TileBank

    bank = state_tree.get("tiles") if hasattr(state_tree, "get") else None
    members = dict(bank.index) if isinstance(bank, TileBank) else {}
    class_groups = dict(bank.class_index) if isinstance(bank, TileBank) else {}

    def spec_of(kp, leaf):
        path = path_str(kp)
        shape = leaf.shape
        if len(shape) == 0:
            return P()
        # class-keyed layout: tiles/<class>/<slot>, (C, n, *member) leaves
        m = re.match(rf"tiles/([^/]+)/{_TILE_SLOTS}$", path)
        if m and m.group(1) in class_groups:
            inner = merge_specs([
                grouped_tile_spec(members[g], shape[1:], mesh,
                                  zero=zero_states)
                for g in class_groups[m.group(1)]])
            return P(None, *inner)
        # per-group layout (hand-built (n, *member) stacks): stack axis leads
        if m and m.group(1) in members:
            return grouped_tile_spec(members[m.group(1)], shape, mesh,
                                     zero=zero_states)
        # stacked per-tile scalars (C, n) / seeds (C, n, 2): replicate
        m = re.match(r"tiles/([^/]+)/(t|c|scale|prog|seed_w|seed_p)$", path)
        if m and (m.group(1) in class_groups or m.group(1) in members):
            return P(*([None] * len(shape)))
        # legacy per-tile layout: tiles/<weight-path>/<slot>
        m = re.match(rf"tiles/(.*)/{_TILE_SLOTS}$", path)
        if m:
            return param_spec(m.group(1), shape, mesh, zero=zero_states)
        if path.startswith("opt/"):
            sub = re.sub(r"^opt/(mu|nu)/", "", path)
            return param_spec(sub, shape, mesh, zero=zero_states)
        if path.startswith("params/"):
            return param_spec(path[len("params/"):], shape, mesh)
        return param_spec(path, shape, mesh)

    return jax.tree_util.tree_map_with_path(
        lambda kp, leaf: NamedSharding(mesh, spec_of(kp, leaf)), state_tree
    )


def params_shardings(params_tree, mesh: Mesh):
    return jax.tree_util.tree_map_with_path(
        lambda kp, leaf: NamedSharding(
            mesh,
            param_spec(path_str(kp),
                       leaf.shape, mesh),
        ),
        params_tree,
    )


# ---------------------------------------------------------------------------
# batch / cache shardings
# ---------------------------------------------------------------------------


def batch_shardings(batch_tree, mesh: Mesh):
    data_axes, dsize, model_ax, msize = mesh_axis_sizes(mesh)
    daxes = data_axes if len(data_axes) > 1 else (data_axes[0] if data_axes else None)

    def spec_of(kp, leaf):
        shape = leaf.shape
        if len(shape) == 0:
            return P()
        spec: list = [None] * len(shape)
        if shape[0] % dsize == 0 and dsize > 1:
            spec[0] = daxes
        return P(*spec)

    return jax.tree_util.tree_map_with_path(
        lambda kp, leaf: NamedSharding(mesh, spec_of(kp, leaf)), batch_tree
    )


def cache_shardings(cache_tree, mesh: Mesh):
    """Decode-cache shardings: batch on data axes when divisible, else the
    sequence dim (long-context batch=1); model axis on heads/head_dim/state
    dims when divisible."""
    data_axes, dsize, model_ax, msize = mesh_axis_sizes(mesh)
    daxes = data_axes if len(data_axes) > 1 else (data_axes[0] if data_axes else None)

    def spec_of(kp, leaf):
        path = path_str(kp)
        shape = leaf.shape
        name = path.split("/")[-1]
        spec: list = [None] * len(shape)
        if len(shape) == 0 or name == "pos":
            return P(*spec)
        # leading scan (period) axis for body caches
        bdim = 1 if "/body/" in path else 0
        if len(shape) <= bdim:
            return P(*spec)
        batch_ok = dsize > 1 and shape[bdim] % dsize == 0 and shape[bdim] >= dsize
        if batch_ok:
            spec[bdim] = daxes
        elif name in ("k", "v", "ckv", "kpe", "ck", "cv") and len(shape) > bdim + 1 \
                and dsize > 1 and shape[bdim + 1] % dsize == 0:
            spec[bdim + 1] = daxes  # shard cache sequence (long_500k)
        # model axis: try trailing dims (heads / head_dim / state dims)
        if model_ax:
            for dim in range(len(shape) - 1, bdim, -1):
                if spec[dim] is None and shape[dim] % msize == 0 and shape[dim] >= msize:
                    spec[dim] = model_ax
                    break
        return P(*spec)

    return jax.tree_util.tree_map_with_path(
        lambda kp, leaf: NamedSharding(mesh, spec_of(kp, leaf)), cache_tree
    )


def replicated(tree, mesh: Mesh):
    return jax.tree.map(lambda _: NamedSharding(mesh, P()), tree)


def logical_rules(mesh: Mesh):
    """Table consumed by models.common.constrain()."""
    data_axes, dsize, model_ax, msize = mesh_axis_sizes(mesh)
    daxes = data_axes if len(data_axes) > 1 else (data_axes[0] if data_axes else None)
    return mesh, {
        "batch": daxes,
        "embed": None,
        "heads": model_ax,
        "mlp": model_ax,
        "vocab": model_ax,
    }
