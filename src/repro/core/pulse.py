"""Pulse-update engine: the Analog Update (paper eq. 2/5) on device arrays.

Two fidelity modes:
  * ``fused`` (default): one aggregated update with a stochastically-rounded
    pulse count (exactly the b_k model of Assumption 3.4 — zero mean,
    Var = Theta(lr * dw_min); property-tested) + aggregated c2c noise.
    This is the TPU-native form (see DESIGN.md §3) and is served by the
    fused Pallas kernel / its jnp oracle.
  * ``train``: explicit BL-deep pulse train via lax.fori_loop, each pulse
    re-evaluating the response at the *current* weight (AIHWKit fidelity).
    Used by small-scale fidelity tests; O(BL)x more HBM traffic.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ops as kops
from repro.kernels import ref as kref

from .device import DeviceConfig, DeviceParams, fg, responses, sample_device


def analog_update(
    w,
    dw,
    dp: DeviceParams,
    cfg: DeviceConfig,
    key,
    *,
    bl: int = 0,
    mode: str = "fused",
    rng: str = "threefry",
    noise=None,
    with_device: bool = False,
):
    """Apply desired increment ``dw`` to analog array ``w`` via pulses.

    ``dp`` is the device's ``DeviceParams``, or the (2,) uint32 seed that
    ``sample_device(method=rng)`` draws them from: the fused soft-bounds
    update under the hash RNG then draws them, with its noise, element by
    element where it uses them (``kops.analog_update_seeded``); every other
    case samples them here. ``with_device`` returns ``(w_new,
    DeviceParams)``, the params the update used, for a caller that reads
    them too.
    ``noise`` optionally carries pre-drawn ``(ubits, zeta)`` (uint32 bits
    for the stochastic rounding + standard normal for c2c); the grouped
    engine's fused backend passes one batched stream for a whole stack.
    """
    fused = cfg.kind in ("softbounds", "linear") and mode == "fused"
    if not isinstance(dp, dict):  # a seed
        if fused and rng == "hash":
            out = kops.analog_update_seeded(w, dw, dp, key, device=cfg, bl=bl,
                                            with_device=with_device)
            if not with_device:
                return out
            return out[0], DeviceParams(gamma=out[1], rho=out[2])
        with jax.named_scope("pulse_operands"):
            dp = sample_device(jax.random.wrap_key_data(dp), w.shape, cfg,
                               method=rng)
    if fused:
        out = kops.analog_update(
            w, dw, dp["gamma"], dp["rho"], key,
            dw_min=cfg.dw_min, tau_min=cfg.tau_min, tau_max=cfg.tau_max,
            sigma_c2c=cfg.sigma_c2c, bl=bl, rng=rng, noise=noise,
        )
    elif mode == "fused":
        out = _fused_generic(w, dw, dp, cfg, key, bl=bl, noise=noise)
    elif mode == "train":
        out = _pulse_train(w, dw, dp, cfg, key, bl=max(bl, 1))
    else:
        raise ValueError(f"unknown pulse mode {mode}")
    return (out, dp) if with_device else out


def _stochastic_round(x, key):
    fl = jnp.floor(x)
    frac = x - fl
    u = jax.random.uniform(key, x.shape, jnp.float32)
    return fl + (u < frac).astype(jnp.float32)


def _fused_generic(w, dw, dp, cfg, key, *, bl, noise=None):
    """Fused update for any response family (jnp path; the kernels' oracle).

    With pre-drawn ``noise=(ubits, zeta)`` the rounding uniform is
    ``kref.uniform_from_bits(ubits)`` — the expression the Pallas kernel
    and the jnp ref use — so this path is bit-comparable against them.
    """
    wf = w.astype(jnp.float32)
    if noise is None:
        ku, kz = jax.random.split(key)
        n_q = _stochastic_round(dw.astype(jnp.float32) / cfg.dw_min, ku)
        zeta = jax.random.normal(kz, w.shape)
    else:
        ubits, zeta = noise
        x = dw.astype(jnp.float32) / cfg.dw_min
        fl = jnp.floor(x)
        u = kref.uniform_from_bits(ubits)
        n_q = fl + (u < x - fl).astype(jnp.float32)
    if bl:
        n_q = jnp.clip(n_q, -float(bl), float(bl))
    delta = n_q * cfg.dw_min
    f, g = fg(wf, dp, cfg)
    qp, qm = responses(wf, dp, cfg)
    q_dir = jnp.where(delta >= 0, qp, qm)
    amp = cfg.dw_min * cfg.sigma_c2c * jnp.sqrt(jnp.abs(n_q)) * q_dir
    out = wf + delta * f - jnp.abs(delta) * g + amp * zeta
    return jnp.clip(out, -cfg.tau_min, cfg.tau_max).astype(w.dtype)


def _pulse_train(w, dw, dp, cfg, key, *, bl):
    """Explicit sequential pulse train (response re-evaluated per pulse)."""
    ku, kz = jax.random.split(key)
    n_q = _stochastic_round(dw.astype(jnp.float32) / cfg.dw_min, ku)
    n_q = jnp.clip(n_q, -float(bl), float(bl))
    sign = jnp.sign(n_q)
    n_abs = jnp.abs(n_q)

    def body(i, carry):
        wf, k = carry
        k, kn = jax.random.split(k)
        live = (i < n_abs).astype(jnp.float32)
        eps = live * sign * cfg.dw_min
        qp, qm = responses(wf, dp, cfg)
        f = (qm + qp) * 0.5
        g = (qm - qp) * 0.5
        c2c = 1.0 + cfg.sigma_c2c * jax.random.normal(kn, wf.shape)
        step = (eps * f - jnp.abs(eps) * g) * c2c
        wf = jnp.clip(wf + step, -cfg.tau_min, cfg.tau_max)
        return wf, k

    wf, _ = jax.lax.fori_loop(0, bl, body, (w.astype(jnp.float32), kz))
    return wf.astype(w.dtype)


def zs_step(w, eps, dp: DeviceParams, cfg: DeviceConfig, key=None):
    """One zero-shifting pulse (paper eq. 7): w + eps*F(w) - |eps|*G(w).

    ``eps`` entries are +-dw_min. c2c noise applied when cfg.sigma_c2c > 0.
    """
    wf = w.astype(jnp.float32)
    f, g = fg(wf, dp, cfg)
    step = eps * f - jnp.abs(eps) * g
    if cfg.sigma_c2c > 0.0 and key is not None:
        step = step * (1.0 + cfg.sigma_c2c * jax.random.normal(key, wf.shape))
    return jnp.clip(wf + step, -cfg.tau_min, cfg.tau_max).astype(w.dtype)
