"""Fused analog pulse-update Pallas kernel (compiled on TPU; platform.py).

The analog update (paper eq. 2) touches W, dW, per-element device params
(gamma, rho) and two noise streams — 6 weight-sized arrays — and is purely
element-wise: arithmetic intensity << 1 FLOP/byte, i.e. **memory bound**.
An unfused jnp implementation performs ~15 HBM round trips (one per jnp op);
this kernel performs exactly one read of each operand and one write of the
output per element, streamed through VMEM in (block_m, block_n) tiles.

The stochastic-rounding Bernoulli consumes pre-generated uint32 bits and the
aggregated cycle-to-cycle noise consumes a standard-normal operand; see
DESIGN.md §3 (TPU adaptation) for why RNG is an operand rather than
``pltpu.prng_*`` (no CPU-interpret rule; bit-exact testability).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import platform
from .ref import uniform_from_bits

# Default VMEM tile: 6 f32 operands + 1 output at (256, 512) = ~3.7 MiB,
# comfortably inside a 16 MiB VMEM budget; last dim is a multiple of 128
# (lane width) and second-to-last a multiple of 8 (sublane width).
DEFAULT_BLOCK = (256, 512)
# stable name of the kernel in compiled programs and device traces
KERNEL_NAME = "analog_pulse_update"


def _kernel(
    w_ref,
    dw_ref,
    gamma_ref,
    rho_ref,
    ubits_ref,
    zeta_ref,
    out_ref,
    *,
    dw_min: float,
    tau_min: float,
    tau_max: float,
    sigma_c2c: float,
    bl: int,
):
    w = w_ref[...].astype(jnp.float32)
    dw = dw_ref[...].astype(jnp.float32)
    gam = gamma_ref[...].astype(jnp.float32)
    rho = rho_ref[...].astype(jnp.float32)

    inv_dwmin = 1.0 / dw_min
    n_real = dw * inv_dwmin
    n_floor = jnp.floor(n_real)
    frac = n_real - n_floor
    u = uniform_from_bits(ubits_ref[...])
    n_q = n_floor + (u < frac).astype(jnp.float32)
    if bl and bl > 0:
        n_q = jnp.clip(n_q, -float(bl), float(bl))
    delta = n_q * dw_min

    qp = (gam + rho) * (1.0 - w * (1.0 / tau_max))
    qm = (gam - rho) * (1.0 + w * (1.0 / tau_min))
    f = (qm + qp) * 0.5
    g = (qm - qp) * 0.5
    upd = delta * f - jnp.abs(delta) * g

    q_dir = jnp.where(delta >= 0.0, qp, qm)
    noise = (dw_min * sigma_c2c) * jnp.sqrt(jnp.abs(n_q)) * q_dir * zeta_ref[...].astype(jnp.float32)

    w_new = jnp.clip(w + upd + noise, -tau_min, tau_max)
    out_ref[...] = w_new.astype(out_ref.dtype)


def analog_update_pallas(
    w,
    dw,
    gamma,
    rho,
    ubits,
    zeta,
    *,
    dw_min: float,
    tau_min: float,
    tau_max: float,
    sigma_c2c: float,
    bl: int = 0,
    block=DEFAULT_BLOCK,
):
    """Fused analog update on 2-D tiles or 3-D tile stacks.

    2-D ``(m, n)`` inputs tile over a ``(m//bm, n//bn)`` grid; 3-D
    ``(k, m, n)`` inputs (a TileBank class stack, member axis leading) add
    the stack axis as the outermost grid dimension so each member streams
    through VMEM independently — no flatten/restack on the host side.
    ``ops.analog_update`` handles reshaping/padding of arbitrary trees.
    Compiled on TPU, interpreted on any other platform (``platform.run``).
    """
    assert w.ndim in (2, 3), "kernel operates on 2-D tiles or 3-D stacks"
    m, n = w.shape[-2:]
    bm = min(block[0], m)
    bn = min(block[1], n)
    assert m % bm == 0 and n % bn == 0, "ops.py pads to block multiples"

    kern = functools.partial(
        _kernel,
        dw_min=float(dw_min),
        tau_min=float(tau_min),
        tau_max=float(tau_max),
        sigma_c2c=float(sigma_c2c),
        bl=int(bl),
    )
    if w.ndim == 2:
        grid = (m // bm, n // bn)
        spec = pl.BlockSpec((bm, bn), lambda i, j: (i, j))
    else:
        grid = (w.shape[0], m // bm, n // bn)
        spec = pl.BlockSpec((1, bm, bn), lambda k, i, j: (k, i, j))
    return platform.run(
        lambda interpret: pl.pallas_call(
            kern,
            out_shape=jax.ShapeDtypeStruct(w.shape, w.dtype),
            grid=grid,
            in_specs=[spec] * 6,
            out_specs=spec,
            interpret=interpret,
            name=KERNEL_NAME,
        ),
        w, dw, gamma, rho, ubits, zeta)
