"""Production mesh factory.

Defined as functions (never module-level constants) so importing this module
never touches jax device state — required because the dry-run pins the
device count via XLA_FLAGS before any jax initialization.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips single-pod, or 2x16x16 = 512 chips across 2 pods."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_mesh(shape, axes):
    """Elastic variant: any (pods, data, model) factorization of the fleet."""
    return _mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1, pods: int = 1):
    """Small mesh over whatever devices exist (tests / CPU smoke).

    ``pods > 1`` adds the leading "pod" axis so host-device tests exercise
    the multi-pod ZeRO path (tile stacks shard over pod x data) with the
    same axis names the production mesh uses.
    """
    n = len(jax.devices())
    assert pods * data * model <= n, (pods, data, model, n)
    if pods > 1:
        return _mesh((pods, data, model), ("pod", "data", "model"))
    return _mesh((data, model), ("data", "model"))
