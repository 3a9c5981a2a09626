"""Chopped-EMA SP-tracking filter Pallas kernel (TPU target).

Implements the digital side of E-RIDER's tracking loop in one fused pass:
the first-order IIR low-pass filter Q <- (1-eta) Q + eta P (paper eq. 12,
Lemma 3.10) together with the two telemetry reductions the convergence
metric (14) needs: sum G_p(P)^2 and the SP tracking error sum (Q' - w_sp)^2.

Partial sums are emitted per grid row and reduced by the thin ops wrapper —
this keeps the kernel free of cross-block accumulation hazards on both the
TPU and interpret backends.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import platform

DEFAULT_BLOCK = (256, 512)


def _kernel(
    q_ref,
    p_ref,
    gamma_ref,
    rho_ref,
    qout_ref,
    gp_ref,   # (1, 1) partial sum of G_p(P)^2 for this block
    err_ref,  # (1, 1) partial sum of (Q' - w_sp)^2 for this block
    *,
    eta: float,
    tau_min: float,
    tau_max: float,
):
    q = q_ref[...].astype(jnp.float32)
    p = p_ref[...].astype(jnp.float32)
    gam = gamma_ref[...].astype(jnp.float32)
    rho = rho_ref[...].astype(jnp.float32)

    q_new = (1.0 - eta) * q + eta * p
    qout_ref[...] = q_new.astype(qout_ref.dtype)

    qp = (gam + rho) * (1.0 - p * (1.0 / tau_max))
    qm = (gam - rho) * (1.0 + p * (1.0 / tau_min))
    g = (qm - qp) * 0.5

    a_p = gam + rho
    a_m = gam - rho
    w_sp = (a_p - a_m) / (a_p * (1.0 / tau_max) + a_m * (1.0 / tau_min))

    gp_ref[0, 0] = jnp.sum(g * g)
    err_ref[0, 0] = jnp.sum((q_new - w_sp) ** 2)


def sp_filter_pallas(
    q,
    p,
    gamma,
    rho,
    *,
    eta: float,
    tau_min: float,
    tau_max: float,
    block=DEFAULT_BLOCK,
):
    """Returns (q_new, gp_sq_sum, err_sq_sum). 2-D inputs, identical shapes."""
    m, n = q.shape
    bm = min(block[0], m)
    bn = min(block[1], n)
    assert m % bm == 0 and n % bn == 0, "ops.py pads"
    gm, gn = m // bm, n // bn

    kern = functools.partial(
        _kernel, eta=float(eta), tau_min=float(tau_min), tau_max=float(tau_max)
    )
    spec = pl.BlockSpec((bm, bn), lambda i, j: (i, j))
    scalar_spec = pl.BlockSpec((1, 1), lambda i, j: (i, j))
    q_new, gp_parts, err_parts = platform.run(
        lambda interpret: pl.pallas_call(
            kern,
            out_shape=(
                jax.ShapeDtypeStruct((m, n), q.dtype),
                jax.ShapeDtypeStruct((gm, gn), jnp.float32),
                jax.ShapeDtypeStruct((gm, gn), jnp.float32),
            ),
            grid=(gm, gn),
            in_specs=[spec] * 4,
            out_specs=(spec, scalar_spec, scalar_spec),
            interpret=interpret,
        ),
        q, p, gamma, rho)
    return q_new, jnp.sum(gp_parts), jnp.sum(err_parts)
