"""Sharded checkpointing with async save, integrity manifest and elastic
restore (resharding to a different mesh on load).

Format (layout v4): one directory per step:
  step_000123/
    manifest.json   — {path: {shape, dtype, file, crc32}}, step, timestamp;
                      "tile_groups" records, for every TileBank stack, its
                      member weight-paths in stacking order and the resolved
                      TilePolicy (devices + algorithm + hyper-parameters)
                      that trained it; "tile_classes" records each scan
                      class's member groups in class-stack order (with
                      their per-slot member paths) — so restore re-keys
                      stacks from the checkpoint's own layout instead of
                      reconstructing the order from the restore template,
                      and a checkpoint is self-describing about the plan
                      that produced it.
    arrays_000.npz  — leaf arrays keyed by their tree path (chunked ~512MB)

Layout v4 (class-keyed TileBank storage) writes tile leaves as
``tiles/<class>/<slot>`` with a (C, n, *member) shape — one array per scan
class, exactly the zero-copy form the grouped engine trains on. Restore
upgrades any older layout on the fly (see the re-key matrix in
docs/architecture.md): v3 per-group stacks, v2 coarser-keyed stacks and v1
per-tile checkpoints all assemble into v4 class stacks bit-identically, and
a v4 checkpoint restores into any differently-partitioned template
(replanned policies, v3-era per-group consumers) by slicing the class
stacks back apart.

Restore takes a *template* pytree (abstract or concrete) and returns arrays
device_put with the caller's shardings — so a checkpoint written on one mesh
restores onto any other mesh (elastic scaling), or on CPU for inspection.
Preemption-safe: writes go to a tmp dir and are atomically renamed; a
``latest`` symlink is updated last.
"""
from __future__ import annotations

import json
import os
import threading
import time
import warnings
import zlib
from typing import Any, Dict, Optional

import jax
import numpy as np

from repro.core.paths import npz_key, path_str

_CHUNK_BYTES = 512 * 1024 * 1024


def _flatten(tree) -> Dict[str, Any]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {
        path_str(kp): leaf for kp, leaf in flat
    }


def _tile_group_manifest(tree) -> Dict[str, Any]:
    """Per-group member paths + resolved policy of every TileBank in
    ``tree`` (manifest layout v3+). Member order IS the stacking order."""
    from repro.core.plan import policy_to_json
    from repro.core.tile import TileBank

    out: Dict[str, Any] = {}

    def visit(x):
        if isinstance(x, TileBank):
            for g, paths in x.index:
                pol = x.policy(g)
                out[g] = {
                    "members": list(paths),
                    "policy": policy_to_json(pol) if pol is not None else None,
                }
        return None

    jax.tree.map(visit, tree, is_leaf=lambda x: isinstance(x, TileBank))
    return out


def _tile_class_manifest(tree) -> Dict[str, Any]:
    """Per-class member groups (in class-stack order) and their member
    weight-paths for every TileBank in ``tree`` (manifest layout v4). Row
    ``ci`` of a class array is the stack of ``members[ci]``."""
    from repro.core.tile import TileBank

    out: Dict[str, Any] = {}

    def visit(x):
        if isinstance(x, TileBank):
            pidx = dict(x.index)
            for cname, gnames in x.class_index:
                out[cname] = {
                    "groups": list(gnames),
                    "members": [list(pidx[g]) for g in gnames],
                }
        return None

    jax.tree.map(visit, tree, is_leaf=lambda x: isinstance(x, TileBank))
    return out


def save(tree, directory: str, step: int, *, asynchronous: bool = False,
         extra: Optional[Dict[str, Any]] = None) -> Optional[threading.Thread]:
    """Write a checkpoint. With asynchronous=True the device->host copy
    happens immediately but file IO runs on a daemon thread.

    ``extra``: JSON-serializable keys merged into manifest.json (e.g. the
    ``gdc_signatures`` t0 weight signatures ``repro.lifetime`` compares
    against at serve time). Reserved layout keys cannot be overridden."""
    flat = _flatten(tree)
    host = {k: np.asarray(v) for k, v in flat.items() if v is not None}
    tile_groups = _tile_group_manifest(tree)
    tile_classes = _tile_class_manifest(tree)
    reserved = {"step", "time", "layout", "arrays", "tile_groups",
                "tile_classes"}
    extra = dict(extra or {})
    assert not (set(extra) & reserved), \
        f"extra manifest keys collide with layout keys: {set(extra) & reserved}"

    def _write():
        # unique tmp dir: an async save and a final sync save of the same
        # step must not collide
        tmp = os.path.join(
            directory, f".tmp_step_{step:09d}_{os.getpid()}_{threading.get_ident()}")
        final = os.path.join(directory, f"step_{step:09d}")
        os.makedirs(tmp, exist_ok=True)
        manifest: Dict[str, Any] = {"step": step, "time": time.time(),
                                    "layout": 4, "arrays": {}}
        if tile_groups:
            manifest["tile_groups"] = tile_groups
        if tile_classes:
            manifest["tile_classes"] = tile_classes
        manifest.update(extra)
        chunk_idx, chunk, chunk_bytes = 0, {}, 0

        def flush():
            nonlocal chunk_idx, chunk, chunk_bytes
            if not chunk:
                return
            fname = f"arrays_{chunk_idx:03d}.npz"
            np.savez(os.path.join(tmp, fname), **chunk)
            chunk_idx += 1
            chunk, chunk_bytes = {}, 0

        for key, arr in sorted(host.items()):
            safe = npz_key(key)
            manifest["arrays"][key] = {
                "shape": list(arr.shape),
                "dtype": str(arr.dtype),
                "file": f"arrays_{chunk_idx:03d}.npz",
                "npz_key": safe,
                "crc32": zlib.crc32(arr.tobytes()),
            }
            chunk[safe] = arr
            chunk_bytes += arr.nbytes
            if chunk_bytes >= _CHUNK_BYTES:
                flush()
        flush()
        with open(os.path.join(tmp, "manifest.json"), "w") as f:
            json.dump(manifest, f)
        if os.path.exists(final):
            old = final + f".old_{os.getpid()}_{threading.get_ident()}"
            os.rename(final, old)
        try:
            os.rename(tmp, final)
        except OSError:
            # another writer won the race for this step; ours is equivalent
            import shutil

            shutil.rmtree(tmp, ignore_errors=True)
        latest = os.path.join(directory, "latest")
        tmp_link = latest + ".tmp"
        if os.path.lexists(tmp_link):
            os.remove(tmp_link)
        os.symlink(os.path.basename(final), tmp_link)
        os.replace(tmp_link, latest)

    if asynchronous:
        t = threading.Thread(target=_write, daemon=True)
        t.start()
        return t
    _write()
    return None


def read_manifest(directory: str, step: Optional[int] = None) -> Dict[str, Any]:
    """Load manifest.json of ``step`` (default: latest) — the cheap way to
    read checkpoint metadata (stored plan, ``extra`` keys like the GDC t0
    signatures) without touching any array chunk."""
    if step is None:
        step = latest_step(directory)
        assert step is not None, f"no checkpoints in {directory}"
    with open(os.path.join(directory, f"step_{step:09d}", "manifest.json")) as f:
        return json.load(f)


def latest_step(directory: str) -> Optional[int]:
    if not os.path.isdir(directory):
        return None
    steps = [
        int(d.split("_")[1]) for d in os.listdir(directory)
        if d.startswith("step_") and ".old" not in d
    ]
    return max(steps) if steps else None


def _legacy_group_members(manifest, shape, dtype_name, tag=""):
    """Member weight-paths of one tile group in a legacy per-tile
    checkpoint — sorted, which is exactly the stacking order
    ``repro.core.tile.group_tiles`` uses. A non-empty ``tag`` keeps only
    paths whose sharding-rule template matches (spec-aware group keys)."""
    import re

    members = []
    for key, meta in manifest["arrays"].items():
        m = re.match(r"^tiles/(.+)/W$", key)
        if m and tuple(meta["shape"]) == tuple(shape) \
                and meta["dtype"] == dtype_name:
            members.append(m.group(1))
    if tag:
        from repro.distributed.sharding import rule_template, template_tag

        members = [p for p in members
                   if template_tag(rule_template(p, len(shape))) == tag]
    return sorted(members)


def _bank_member_index(template):
    """{group name: member weight-paths} of every TileBank in ``template``
    (the restore target). Member paths live in the bank's static index, not
    in its leaves, so the re-keying upgrade path reads them here."""
    from repro.core.tile import TileBank

    members = {}

    def visit(x):
        if isinstance(x, TileBank):
            for g, paths in x.index:
                members[g] = tuple(paths)
        return None

    jax.tree.map(visit, template,
                 is_leaf=lambda x: isinstance(x, TileBank))
    return members


def _legacy_grouped_arr(key, manifest, load_arr, bank_members):
    """Assemble a grouped-layout leaf ``tiles/<group>/<slot>`` missing from
    the manifest by upgrading any older layout:

    * per-tile (pre-TileBank) checkpoints: stack the group's member tiles
      in group order;
    * coarser-keyed grouped checkpoints — (shape, dtype)-only stacks
      (pre-spec-aware keys) or single-policy stacks without the policy tag
      (pre-AnalogPlan) — gather the rows belonging to this group's members
      out of the old combined stack. The old stacking order comes from the
      checkpoint's own ``tile_groups`` member manifest when present
      (layout v3); only manifests that predate it fall back to
      reconstructing the order from the restore template's union (which
      assumes the same model);
    * any other regrouping a v3 member manifest can describe — e.g. a
      mixed-plan checkpoint's policy-split stacks restoring into a
      coarser single-policy template — assembled member by member from
      each tile's stored (group, row).

    Returns None when ``key`` is not a grouped tile leaf.
    """
    import re

    from repro.core.tile import group_name, parse_group_name

    m = re.match(r"^tiles/([^/]+)/(.+)$", key)
    if not m:
        return None
    gname = m.group(1)
    parsed = parse_group_name(gname)
    if parsed is None:
        return None
    shape, dtype_name, tag, _ptag = parsed
    slot = m.group(2)
    manifest_groups = manifest.get("tile_groups", {})
    members = bank_members.get(gname) \
        or manifest_groups.get(gname, {}).get("members") \
        or _legacy_group_members(manifest, shape, dtype_name, tag)
    if not members:
        return None
    # 1) per-tile legacy layout
    if f"tiles/{members[0]}/{slot}" in manifest["arrays"]:
        return np.stack([load_arr(f"tiles/{p}/{slot}") for p in members])
    # 2) coarser-keyed grouped layouts: re-key the old stack. Candidates,
    # most specific first: same (shape, dtype, template) without the policy
    # tag (pre-AnalogPlan single-policy), then (shape, dtype) only (PR-1).
    candidates = []
    for cand in (group_name(shape, dtype_name, tag),
                 group_name(shape, dtype_name)):
        if cand != gname and cand not in candidates:
            candidates.append(cand)
    for src in candidates:
        if f"tiles/{src}/{slot}" not in manifest["arrays"]:
            continue
        old_members = manifest_groups.get(src, {}).get("members")
        if old_members is None:
            # pre-v3 manifest: the old member set is the union of the
            # template's groups that the old key covered (same model,
            # regrouped), sorted — the old stacking order.
            sshape, sdt, sttag, _ = parse_group_name(src)
            old_members = sorted(
                p for g, paths in bank_members.items()
                for p in paths
                if (lambda pg: pg is not None and pg[0] == sshape
                    and pg[1] == sdt
                    and (not sttag or pg[2] == sttag))(parse_group_name(g)))
        if not all(p in old_members for p in members):
            continue
        old = load_arr(f"tiles/{src}/{slot}")
        assert old.shape[0] == len(old_members), (
            f"legacy group {src} holds {old.shape[0]} tiles but its member "
            f"list names {len(old_members)}: {old_members}")
        return old[[old_members.index(p) for p in members]]
    # 3) cross-plan re-key via the layout-v3 member map: the checkpoint's
    # own tile_groups manifest names every tile's (group, row), so the
    # template group can be assembled member by member from ANY regrouping
    # — e.g. a mixed-plan checkpoint (policy-split stacks) restoring into
    # a coarser single-policy template merges the split stacks back.
    path_src: Dict[str, tuple] = {}
    for src, rec in manifest_groups.items():
        if f"tiles/{src}/{slot}" not in manifest["arrays"]:
            continue
        for row, p2 in enumerate(rec.get("members") or ()):
            path_src.setdefault(p2, (src, row))
    if not all(p in path_src for p in members):
        return None
    loaded: Dict[str, Any] = {}  # each source stack decompresses ONCE
    rows = []
    for p in members:
        src, row = path_src[p]
        if src not in loaded:
            loaded[src] = load_arr(f"tiles/{src}/{slot}")
        rows.append(loaded[src][row])
    return np.stack(rows)


def _group_view(manifest, load_arr):
    """Per-group view of a v4 class-keyed checkpoint: returns a
    ``(manifest', load_arr')`` pair in which every ``tiles/<group>/<slot>``
    of every class member exists as a virtual array (a static ``[ci]``
    slice of its class stack). All pre-v4 re-key strategies
    (``_legacy_grouped_arr``) then work against a v4 source unchanged —
    this is the v4 -> v3-partition fallback direction of the re-key
    matrix. Checkpoints without ``tile_classes`` pass through untouched."""
    import re

    classes = manifest.get("tile_classes")
    if not classes:
        return manifest, load_arr
    arrays = dict(manifest["arrays"])
    virtual: Dict[str, tuple] = {}
    for key, meta in manifest["arrays"].items():
        m = re.match(r"^tiles/([^/]+)/(.+)$", key)
        if not m or m.group(1) not in classes:
            continue
        cname, slot = m.group(1), m.group(2)
        for ci, g in enumerate(classes[cname]["groups"]):
            gkey = f"tiles/{g}/{slot}"
            # single-group classes (cname == g) are overridden too: the
            # group view always has the (n, *member) member shape
            virtual[gkey] = (key, ci)
            arrays[gkey] = {**meta, "shape": list(meta["shape"][1:])}
    man2 = dict(manifest)
    man2["arrays"] = arrays

    def load2(key):
        v = virtual.get(key)
        if v is None:
            return load_arr(key)
        return load_arr(v[0])[v[1]]

    return man2, load2


def _class_arr(key, manifest, load_arr, bank_members):
    """Assemble a v4 class-keyed leaf ``tiles/<class>/<slot>`` that is not
    stored under its own key, by stacking its member groups — each group
    coming from a same-name v3 stack, a re-keyed older layout
    (``_legacy_grouped_arr``), or a slice of a differently-partitioned v4
    class (``_group_view``). Returns None when ``key`` is not a class
    leaf or a member group cannot be assembled."""
    import re

    from repro.core.tile import parse_class_name, parse_group_name

    m = re.match(r"^tiles/([^/]+)/(.+)$", key)
    if not m:
        return None
    cname, slot = m.group(1), m.group(2)
    groups = parse_class_name(cname)
    if any(parse_group_name(g) is None for g in groups):
        return None
    gman, gload = _group_view(manifest, load_arr)
    parts = []
    for g in groups:
        gkey = f"tiles/{g}/{slot}"
        if gkey in gman["arrays"]:
            arr = gload(gkey)
        else:
            arr = _legacy_grouped_arr(gkey, gman, gload, bank_members)
        if arr is None:
            return None
        parts.append(arr)
    return np.stack(parts)


def _policy_json_matches(new, stored) -> bool:
    """Tolerant policy comparison: only keys the checkpoint actually
    recorded constrain the match, so TileConfig fields added after the
    checkpoint was written (e.g. ``update_backend``) compare as their
    defaults instead of flagging every old checkpoint as mismatched."""
    if isinstance(new, dict) and isinstance(stored, dict):
        return all(_policy_json_matches(new.get(k), v)
                   for k, v in stored.items())
    return new == stored


def _warn_policy_mismatch(template, manifest) -> None:
    """Emit ONE consolidated warning listing every template stack whose
    TilePolicy differs from the policy the checkpoint records for it
    (layout v3+ manifests only) — large mixed plans would otherwise spam
    one warning per stack. Groups absent from the manifest under their own
    name compare against the coarser legacy key they would re-key from
    (``_legacy_grouped_arr``'s candidate order), so retraining a
    single-policy checkpoint under a different mixed plan warns too."""
    from repro.core.plan import policy_to_json
    from repro.core.tile import TileBank, group_name, parse_group_name

    stored = manifest.get("tile_groups", {})
    if not stored:
        return

    def stored_policies(g):
        if g in stored:
            return [stored[g].get("policy")]
        parsed = parse_group_name(g)
        if parsed is None:
            return []
        shape, dtype_name, tag, _ptag = parsed
        # coarser source the re-key would read from ...
        for cand in (group_name(shape, dtype_name, tag),
                     group_name(shape, dtype_name)):
            if cand in stored:
                return [stored[cand].get("policy")]
        # ... or finer (policy-split) stacks covering the same structure
        return [rec.get("policy") for g2, rec in stored.items()
                if (parse_group_name(g2) or (None,) * 3)[:3]
                == (shape, dtype_name, tag)]

    mismatched = []

    def visit(x):
        if isinstance(x, TileBank):
            for g, _ in x.index:
                pol = x.policy(g)
                if pol is None:
                    continue
                for rec in stored_policies(g):
                    if rec is not None and not _policy_json_matches(
                            policy_to_json(pol), rec):
                        mismatched.append(
                            f"{g} ({rec.get('name') or rec.get('tag')}"
                            f" -> {pol.name or pol.tag})")
                        break
        return None

    jax.tree.map(visit, template, is_leaf=lambda x: isinstance(x, TileBank))
    if mismatched:
        warnings.warn(
            f"{len(mismatched)} tile stack(s) restore under a different "
            f"policy than the one they were trained with: "
            f"{'; '.join(mismatched)}",
            stacklevel=3)


def restore(template, directory: str, step: Optional[int] = None, *,
            shardings=None, verify: bool = False):
    """Load arrays into the structure of ``template``.

    shardings: optional matching pytree of NamedShardings (elastic restore —
    the stored full arrays are device_put with the *new* mesh's shardings).

    Class-keyed tile state (``tiles/<class>/...`` with (C, n, *member)
    leaves, layout v4) restores from any layout: same-layout checkpoints
    load directly; v3 per-group stacks assemble into class stacks group by
    group; legacy per-tile checkpoints are upgraded by stacking their
    member tiles in group order; coarser-keyed stacks — (shape,
    dtype)-only (pre-spec-aware keys) or untagged single-policy stacks
    (pre-AnalogPlan) — are re-keyed by gathering each group's member rows
    out of the old combined stack, using the checkpoint's own
    ``tile_groups`` member manifest when present; and a v4 checkpoint
    restores into a differently-partitioned template by slicing its class
    stacks back into per-group arrays (``_group_view``). Stored per-group
    policies that differ from the restore template's are reported in one
    consolidated warning (restoring a checkpoint into a different plan is
    legal but usually a mistake).
    """
    if step is None:
        step = latest_step(directory)
        assert step is not None, f"no checkpoints in {directory}"
    d = os.path.join(directory, f"step_{step:09d}")
    with open(os.path.join(d, "manifest.json")) as f:
        manifest = json.load(f)
    _warn_policy_mismatch(template, manifest)
    files: Dict[str, Any] = {}

    def load_arr(key):
        meta = manifest["arrays"][key]
        fname = meta["file"]
        if fname not in files:
            files[fname] = np.load(os.path.join(d, fname))
        arr = files[fname][meta["npz_key"]]
        want = jax.numpy.dtype(meta["dtype"])
        if arr.dtype != want:
            # np.savez stores extension dtypes (bfloat16) as raw void bytes
            arr = arr.view(want)
        if verify:
            assert zlib.crc32(arr.tobytes()) == meta["crc32"], f"corrupt leaf {key}"
        return arr

    bank_members = _bank_member_index(template)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        template, is_leaf=lambda x: x is None
    )
    shard_flat = None
    if shardings is not None:
        shard_flat = [s for _, s in jax.tree_util.tree_flatten_with_path(
            shardings, is_leaf=lambda x: x is None)[0]]
    out = []
    for i, (kp, leaf) in enumerate(flat):
        key = path_str(kp)
        if leaf is None:
            out.append(None)
            continue
        expect = tuple(leaf.shape)
        if key in manifest["arrays"] and \
                tuple(manifest["arrays"][key]["shape"]) == expect:
            arr = load_arr(key)
        else:
            arr = _class_arr(key, manifest, load_arr, bank_members)
            if arr is None:
                arr = _legacy_grouped_arr(key, manifest, load_arr,
                                          bank_members)
            if arr is None and key in manifest["arrays"]:
                arr = load_arr(key)  # let the shape assert report it
            assert arr is not None, f"checkpoint missing leaf {key}"
        assert tuple(arr.shape) == expect, (key, arr.shape, expect)
        if shard_flat is not None and shard_flat[i] is not None:
            out.append(jax.device_put(arr, shard_flat[i]))
        else:
            out.append(jax.numpy.asarray(arr))
    return jax.tree_util.tree_unflatten(treedef, out)
