"""Tree paths rendered as "a/b/c" strings.

Tree paths are the stable identifiers for every leaf in this codebase
(sharding rules, checkpoints, tile grouping). ``npz_key`` / ``npz_path``
are the matching on-disk encoding used by checkpoint manifests
(np.savez member names cannot contain "/").
"""
from __future__ import annotations

import jax


def path_str(kp) -> str:
    return jax.tree_util.keystr(kp, simple=True, separator="/")


def npz_key(path: str) -> str:
    """Tree path -> npz member name ("tiles/g8x8_float32_nM/W" ->
    "tiles|g8x8_float32_nM|W"). Stable across releases: checkpoint
    manifests persist these names."""
    return path.replace("/", "|")


def npz_path(key: str) -> str:
    """Inverse of ``npz_key``."""
    return key.replace("|", "/")
