"""Pure-jnp oracles for every Pallas kernel in this package.

These functions are the *single source of truth* for the analog math: the
Pallas kernels are asserted allclose against them in tests, and the rest of
the framework (``repro.core``) calls them through ``repro.kernels.ops`` which
dispatches to the fused kernels when profitable.

Math reference (paper eq. numbers):

  q+(w) = (gamma + rho) * (1 - w / tau_max)          (SoftBoundsReference)
  q-(w) = (gamma - rho) * (1 + w / tau_min)
  F(w)  = (q-(w) + q+(w)) / 2                        (6a)
  G(w)  = (q-(w) - q+(w)) / 2                        (6b)

  Analog Update (2):
    w' = w + dw * F(w) - |dw| * G(w) + b
  realized here as a stochastically-rounded pulse count
    n  = stochastic_round(dw / dw_min)               (b_k, Assumption 3.4)
  optionally capped at +-BL, with per-pulse cycle-to-cycle lognormal-ish
  multiplicative noise aggregated into a single Gaussian term.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import fastrng

# ---------------------------------------------------------------------------
# Response functions (element-wise; all args broadcastable arrays)
# ---------------------------------------------------------------------------


def q_plus(w, gamma, rho, tau_max):
    return (gamma + rho) * (1.0 - w / tau_max)


def q_minus(w, gamma, rho, tau_min):
    return (gamma - rho) * (1.0 + w / tau_min)


def response_fg(w, gamma, rho, tau_min, tau_max):
    """Return (F, G) of eq. (6) for the soft-bounds reference device."""
    qp = q_plus(w, gamma, rho, tau_max)
    qm = q_minus(w, gamma, rho, tau_min)
    return (qm + qp) * 0.5, (qm - qp) * 0.5


# ---------------------------------------------------------------------------
# Device-to-device sampling (paper App. F.1; core/device.py sample_device)
# ---------------------------------------------------------------------------

# fastrng salts of a device's three normal fields: slope, asymmetry, SP target
DEVICE_SALTS = (11, 13, 17)


def device_params(normal, shape, dev):
    """Per-element ``(gamma, rho)`` of a device from standard normals.

    ``normal(i)`` draws normal field ``i`` (0 slope, 1 asymmetry, 2 SP
    target) at ``shape``; only the fields ``dev`` uses are drawn. ``dev``
    is a ``core.device.DeviceConfig`` (read for ``sigma_d2d``,
    ``sigma_pm``, ``ref_mean``, ``ref_std``, ``tau_min``, ``tau_max``).
    """
    if dev.sigma_d2d > 0:
        gamma = jnp.exp(dev.sigma_d2d * normal(0))
    else:
        gamma = jnp.ones(shape, jnp.float32)
    # Def. 2.1 positive-definiteness: |rho| < gamma keeps both alpha+- > 0
    rho = jnp.clip(dev.sigma_pm * normal(1), -0.95 * gamma, 0.95 * gamma)

    if dev.ref_mean != 0.0 or dev.ref_std != 0.0:
        # Solve for rho that realizes a target SP w* ~ N(ref_mean, ref_std^2):
        #   w* = 2 rho / ((gamma+rho)/tmax + (gamma-rho)/tmin)
        # => rho = w* gamma (tmin + tmax) / (2 tmin tmax + w*(tmin - tmax))
        w_star = dev.ref_mean + dev.ref_std * normal(2)
        w_star = jnp.clip(w_star, -0.95 * dev.tau_min, 0.95 * dev.tau_max)
        num = w_star * gamma * (dev.tau_min + dev.tau_max)
        den = 2.0 * dev.tau_min * dev.tau_max + w_star * (dev.tau_min - dev.tau_max)
        rho = num / den
        # keep alpha+- positive (Def. 2.1 positive-definiteness)
        rho = jnp.clip(rho, -0.95 * gamma, 0.95 * gamma)
    return gamma, rho


def hash_device(seed0, seed1, idx, dev):
    """``device_params`` from the hash at linear indices ``idx``: what
    ``sample_device(method='hash')`` gives at those elements."""
    return device_params(
        lambda i: fastrng.normal_from_bits(
            fastrng.hash_at(seed0, seed1, idx, DEVICE_SALTS[i])),
        idx.shape, dev)


# ---------------------------------------------------------------------------
# Fused analog pulse update  (kernel: analog_update.py)
# ---------------------------------------------------------------------------


def uniform_from_bits(ubits):
    """uint32 random bits -> f32 uniform in [0, 1) from their top 24 bits.

    The one expression behind every stochastic-rounding draw: the Pallas
    kernel, this oracle and ``core.pulse`` all call it, so they stay
    bit-comparable. It goes through int32 because the TPU kernel compiler
    has no uint32 -> f32 cast; 24 bits convert to f32 exactly.
    """
    top = jax.lax.shift_right_logical(ubits, jnp.uint32(8))
    return top.astype(jnp.int32).astype(jnp.float32) * (1.0 / 16777216.0)


def analog_update_ref(
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
):
    """Apply the Analog Update (2) with stochastic pulse discretization.

    Args:
      w:      current weights (any float dtype; accumulated in f32).
      dw:     desired increment (e.g. ``-lr * grad``).
      gamma:  per-element common response slope (d2d sampled).
      rho:    per-element asymmetry.
      ubits:  uint32 random bits for the stochastic rounding Bernoulli.
      zeta:   standard-normal noise for the aggregated c2c term.
      dw_min: response granularity.
      bl:     max pulses per update (0 = uncapped).

    Returns:
      Updated weights, same dtype as ``w``.
    """
    wf = w.astype(jnp.float32)
    dwf = dw.astype(jnp.float32)
    gam = gamma.astype(jnp.float32)
    rh = rho.astype(jnp.float32)

    # -- pulse count: stochastic rounding of dw / dw_min -------------------
    n_real = dwf / dw_min
    n_floor = jnp.floor(n_real)
    frac = n_real - n_floor
    u = uniform_from_bits(ubits)
    n_q = n_floor + (u < frac).astype(jnp.float32)
    if bl and bl > 0:
        n_q = jnp.clip(n_q, -float(bl), float(bl))
    delta = n_q * dw_min  # realized target increment

    # -- response at current state -----------------------------------------
    f, g = response_fg(wf, gam, rh, tau_min, tau_max)
    upd = delta * f - jnp.abs(delta) * g

    # -- aggregated cycle-to-cycle noise ------------------------------------
    # each pulse has multiplicative noise sigma_c2c on its |dw_min * q| step;
    # over |n_q| pulses the aggregate std is dw_min * q_dir * sigma * sqrt(|n|).
    q_dir = jnp.where(delta >= 0.0, q_plus(wf, gam, rh, tau_max), q_minus(wf, gam, rh, tau_min))
    noise = dw_min * sigma_c2c * jnp.sqrt(jnp.abs(n_q)) * q_dir * zeta.astype(jnp.float32)

    w_new = wf + upd + noise
    w_new = jnp.clip(w_new, -tau_min, tau_max)
    return w_new.astype(w.dtype)


def analog_update_expected_ref(w, dw, gamma, rho, *, tau_min, tau_max):
    """Noise-free expectation of the Analog Update (used in theory tests)."""
    wf = w.astype(jnp.float32)
    f, g = response_fg(wf, gamma.astype(jnp.float32), rho.astype(jnp.float32), tau_min, tau_max)
    out = wf + dw.astype(jnp.float32) * f - jnp.abs(dw).astype(jnp.float32) * g
    return jnp.clip(out, -tau_min, tau_max).astype(w.dtype)


# ---------------------------------------------------------------------------
# IO-quantized analog MVM  (kernel: analog_matmul.py)
# ---------------------------------------------------------------------------


def analog_mvm_ref(
    x,
    w,
    noise,
    *,
    inp_res: float,
    inp_bound: float,
    out_res: float,
    out_bound: float,
    out_noise: float,
):
    """Analog crossbar MVM with DAC/ADC quantization (paper Table 7).

    Pipeline: ABS_MAX noise management -> input DAC quantization -> matmul ->
    additive output noise -> ADC clip + quantization -> rescale.

    Args:
      x: (..., K) activations.
      w: (K, N) analog weights.
      noise: standard normal, shape of the output (..., N).
    """
    xf = x.astype(jnp.float32)
    wf = w.astype(jnp.float32)
    # ABS_MAX noise management: scale rows into [-1, 1]
    s = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    s = jnp.maximum(s, 1e-12)
    xn = xf / s
    # input DAC (multiply by the Python-level reciprocal — bit-identical to
    # the Pallas kernel's constant; `x / res` rounds differently at .5 ULP)
    xq = jnp.clip(xn, -inp_bound, inp_bound)
    xq = jnp.round(xq * (1.0 / inp_res)) * inp_res
    # crossbar
    y = xq @ wf
    # output noise + ADC
    y = y + out_noise * noise.astype(jnp.float32)
    y = jnp.clip(y, -out_bound, out_bound)
    y = jnp.round(y * (1.0 / out_res)) * out_res
    return (y * s).astype(x.dtype)


# ---------------------------------------------------------------------------
# Chopped EMA SP filter  (kernel: sp_filter.py)
# ---------------------------------------------------------------------------


def sp_filter_ref(q, p, gamma_p, rho_p, *, eta: float, tau_min: float, tau_max: float):
    """One step of the digital SP-tracking filter (12) plus drift telemetry.

    Returns (q_new, gp_sq_sum, err_sq_sum) where
      q_new       = (1 - eta) * q + eta * p
      gp_sq_sum   = sum(G_p(p)^2)               (convergence metric of Thm 3.7)
      err_sq_sum  = sum((q_new - w_sp)^2)        (SP tracking error; w_sp from
                                                  the corrected eq. (110))
    """
    qf = q.astype(jnp.float32)
    pf = p.astype(jnp.float32)
    gam = gamma_p.astype(jnp.float32)
    rh = rho_p.astype(jnp.float32)
    q_new = (1.0 - eta) * qf + eta * pf
    _, g = response_fg(pf, gam, rh, tau_min, tau_max)
    a_p = gam + rh
    a_m = gam - rh
    w_sp = (a_p - a_m) / (a_p / tau_max + a_m / tau_min)
    gp_sq = jnp.sum(g * g)
    err_sq = jnp.sum((q_new - w_sp) ** 2)
    return q_new.astype(q.dtype), gp_sq, err_sq
