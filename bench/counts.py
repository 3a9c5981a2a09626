"""Operations and bytes the benchmarked work needs, from the configuration's
shapes alone, so they read the same whatever implements the work.

- ``flops_per_token``: model FLOPs of one training token: 6 per weight of
  every matrix product (the output head included, embedding lookups and
  gains and biases excluded) plus causal attention's score and value
  products, 3 * n_heads * (qk_dim + v_dim) * (seq + 1) per layer (forward
  2 * H * (dk + dv) * (seq + 1) / 2 at the mean causal context, times 3 for
  forward and backward). Recomputation under remat is not counted.
- ``update_bytes_per_step``: HBM bytes E-RIDER's pulse updates need per
  step: every analog weight is pulsed twice, P by (18a) and W by (18b),
  and each pulse reads and writes the array at the tile storage type and
  reads one increment at the model type. Device parameters and noise
  count zero: the configuration regenerates them from seeds.
"""
from __future__ import annotations

import math

from .reference import lm

_ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def _paths(m: dict) -> dict:
    return lm.leaf_paths(lm.param_shapes(m), is_leaf=lambda x: isinstance(x, tuple))


def analog_weights(m: dict) -> int:
    """Weights held on analog tiles (every leaf the configuration does not
    keep digital)."""
    return sum(math.prod(s) for p, s in _paths(m).items()
               if not lm.is_digital(p, len(s)))


def matmul_weights(m: dict) -> int:
    """Weights that enter a matrix product per token: every matrix of the
    layers, plus the output head (the tied embedding when there is none)."""
    n = sum(math.prod(s) for p, s in _paths(m).items()
            if len(s) == 3 and p.startswith("stack/"))
    return n + m["vocab"] * m["d_model"]


def attention_flops_per_token(m: dict, seq: int) -> int:
    if m["attention"] == "gqa":
        dk = dv = m["head_dim"]
    else:
        dk, dv = m["qk_nope"] + m["qk_rope"], m["v_head_dim"]
    return 3 * m["n_layers"] * m["n_heads"] * (dk + dv) * (seq + 1)


def flops_per_token(m: dict, seq: int) -> int:
    return 6 * matmul_weights(m) + attention_flops_per_token(m, seq)


def update_bytes_per_step(m: dict, tile: dict) -> int:
    state = _ITEMSIZE[tile["state_dtype"]]
    incr = _ITEMSIZE[m["dtype"]]
    return analog_weights(m) * 2 * (2 * state + incr)


def peaks(table: dict, device_kind: str) -> dict:
    """The peak row for ``device_kind``; an unknown device is an error."""
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table['devices'])}")
    return table["devices"][device_kind]

