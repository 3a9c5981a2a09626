"""Where a Pallas kernel runs: compiled on the TPU, interpreted elsewhere.

The choice is made when a program is lowered, for the platform it is
lowered for (``jax.lax.platform_dependent``), not from the process's default
backend. A program compiled for a TPU therefore always contains the Mosaic
kernel, even when it is compiled on a CPU host for a described chip, and no
TPU program can reach the Pallas interpreter. Any other platform runs the
kernel in the interpreter, which is what the CPU tests compare against the
jnp oracles.
"""
from __future__ import annotations

import jax


def run(make_call, *operands):
    """``make_call(interpret)(*operands)`` with ``interpret`` chosen per
    lowering platform (False on TPU, True anywhere else)."""
    return jax.lax.platform_dependent(
        *operands, tpu=make_call(False), default=make_call(True))
