"""Analog resistive-device models (paper §4 "Device model" + Appendix F.1).

A *device* here is the per-cross-point physics of one analog tile: the pair
of response functions (q+, q-) that scale every up/down conductance pulse.
We implement the SoftBoundsReference family used by the paper (IBM AIHWKit
presets, Table 3) plus the broader training-friendly families of Def. 2.1 /
C.1 (linear-monotone, exponential) used by the theory tests.

Per-element device-to-device (d2d) sampling follows App. F.1:
    gamma_ij = exp(sigma_d2d * xi)      (common slope, lognormal)
    rho_ij   = sigma_pm * xi'           (up/down asymmetry, normal)
    alpha+ = gamma + rho,  alpha- = gamma - rho

Ground-truth symmetric point (G(w)=0), with the sign typo of paper eq. (110)
corrected (see DESIGN.md §1):
    w_sp = (alpha+ - alpha-) / (alpha+/tau_max + alpha-/tau_min)
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels import fastrng
from repro.kernels import ref as kref


@dataclasses.dataclass(frozen=True)
class DeviceConfig:
    """Static (non-pytree) description of a device family/preset."""

    kind: str = "softbounds"      # softbounds | linear | exp
    tau_min: float = 1.0          # lower bound is -tau_min (tau_min > 0)
    tau_max: float = 1.0
    dw_min: float = 0.001         # response granularity
    sigma_d2d: float = 0.0        # d2d slope variation (lognormal sigma)
    sigma_pm: float = 0.0         # d2d asymmetry variation
    sigma_c2c: float = 0.0        # cycle-to-cycle write noise
    # Optional nonzero-SP initialization for robustness studies (Tables 1-2):
    # rho is shifted so the per-element SP ~ N(ref_mean, ref_std^2).
    ref_mean: float = 0.0
    ref_std: float = 0.0
    # exp-family curvature (only for kind == "exp")
    exp_kappa: float = 0.5
    # --- lifetime (post-training) physics, consumed by repro.lifetime ---
    # Conductance drift W(t) = W(t0) * (t/t0)^-nu (Rasch et al. HWA
    # replications): nu is sampled per element ~ N(drift_nu, drift_nu_std^2),
    # clipped to >= 0; drift_t0 is the reference instant (seconds after
    # programming) the checkpointed state is defined at. All defaults are
    # no-op values so pre-lifetime checkpoints and presets behave
    # identically (the stored-keys-only policy compare relies on this).
    drift_nu: float = 0.0
    drift_nu_std: float = 0.0
    drift_t0: float = 1.0
    # Write-and-verify programming error: one write lands at
    # w + N(0, sigma_p(w)^2) with the state-dependent
    # sigma_p(w) = prog_noise + prog_noise_slope * |w|; each verify round
    # reads back (read_noise-corrupted) and applies a corrective write
    # whose own error is proportional to the correction magnitude.
    prog_noise: float = 0.0
    prog_noise_slope: float = 0.0
    prog_rounds: int = 1
    # Additive conductance read noise (weight units) on any post-t0 read.
    read_noise: float = 0.0

    @property
    def num_states(self) -> float:
        """Number of conductance states across the dynamic range."""
        return (self.tau_max + self.tau_min) / self.dw_min


# AIHWKit-style presets from paper Table 3, with per-preset lifetime
# coefficients (drift exponent, programming/read noise) in the units of the
# normalized weight range. ReRAM drift is weak relative to PCM (retention
# loss dominated by filament relaxation); the PCM preset carries the
# canonical nu ~ 0.06 of d-GST mushroom cells.
PRESETS = {
    # HfO2-based ReRAM (Gong et al., 2022b): very few states (~4-5)
    "reram_hfo2": DeviceConfig(
        kind="softbounds", tau_min=1.0, tau_max=1.0, dw_min=0.4622,
        sigma_d2d=0.1, sigma_pm=0.7125, sigma_c2c=0.2174,
        drift_nu=0.01, drift_nu_std=0.004, prog_noise=0.02,
        prog_noise_slope=0.05, read_noise=0.01,
    ),
    # ReRamArrayOMPresetDevice (Gong et al., 2022b)
    "reram_om": DeviceConfig(
        kind="softbounds", tau_min=1.0, tau_max=1.0, dw_min=0.0949,
        sigma_d2d=0.1, sigma_pm=0.7829, sigma_c2c=0.4158,
        drift_nu=0.01, drift_nu_std=0.004, prog_noise=0.01,
        prog_noise_slope=0.04, read_noise=0.005,
    ),
    # High-precision device used for the ZS complexity study (Fig. 1)
    "softbounds_2000": DeviceConfig(
        kind="softbounds", tau_min=1.0, tau_max=1.0, dw_min=0.001,
        sigma_d2d=0.1, sigma_pm=0.3, sigma_c2c=0.05,
        drift_nu=0.005, drift_nu_std=0.002, prog_noise=0.002,
        prog_noise_slope=0.01, read_noise=0.002,
    ),
    # ECRAM-style preset (AIHWKit EcRamPresetDevice analogue): ~1000 states,
    # milder asymmetry than the ReRAM presets but nonzero write noise —
    # the "good device" partner in mixed-device plans.
    "ecram": DeviceConfig(
        kind="softbounds", tau_min=1.0, tau_max=1.0, dw_min=0.002,
        sigma_d2d=0.1, sigma_pm=0.25, sigma_c2c=0.15,
        drift_nu=0.002, drift_nu_std=0.001, prog_noise=0.004,
        prog_noise_slope=0.02, read_noise=0.002,
    ),
    # Mushroom-cell d-GST PCM (Rasch et al. HWA replications, SNIPPETS.md
    # snippets 1 and 3): the canonical drifting device GDC was built for —
    # nu ~ 0.06 with wide d2d spread, strongly state-dependent programming
    # error, t0 ~ 20 s after program-and-verify.
    "pcm_gst": DeviceConfig(
        kind="softbounds", tau_min=1.0, tau_max=1.0, dw_min=0.005,
        sigma_d2d=0.1, sigma_pm=0.3, sigma_c2c=0.05,
        drift_nu=0.06, drift_nu_std=0.02, drift_t0=20.0,
        prog_noise=0.01, prog_noise_slope=0.07, prog_rounds=3,
        read_noise=0.005,
    ),
    # Idealized symmetric device (digital-like reference)
    "ideal": DeviceConfig(
        kind="softbounds", tau_min=10.0, tau_max=10.0, dw_min=1e-6,
        sigma_d2d=0.0, sigma_pm=0.0, sigma_c2c=0.0,
    ),
}


class DeviceParams(dict):
    """Pytree of per-element device parameters ({'gamma','rho'} arrays)."""


jax.tree_util.register_pytree_with_keys(
    DeviceParams,
    lambda d: (tuple((jax.tree_util.DictKey(k), d[k]) for k in sorted(d)),
               tuple(sorted(d))),
    lambda keys, vals: DeviceParams(zip(keys, vals)),
)


def sample_device(key, shape, cfg: DeviceConfig, method: str = "threefry") -> DeviceParams:
    """Sample per-element (gamma, rho) for a tile of `shape` (App. F.1).

    method='hash' draws from the fused stateless hash RNG (sharding-friendly
    regeneration path at LM scale; see kernels/fastrng.py).
    """
    if method == "hash":
        seed = fastrng.seed_from_key(key)
        gamma, rho = kref.hash_device(seed[0], seed[1],
                                      fastrng.linear_index(shape), cfg)
    else:
        keys = jax.random.split(key, 3)
        gamma, rho = kref.device_params(
            lambda i: jax.random.normal(keys[i], shape, jnp.float32), shape, cfg)
    return DeviceParams(gamma=gamma, rho=rho)


def abstract_device(shape, dtype=jnp.float32) -> DeviceParams:
    """ShapeDtypeStruct stand-in (for dry-run lowering)."""
    s = jax.ShapeDtypeStruct(shape, dtype)
    return DeviceParams(gamma=s, rho=s)


# ---------------------------------------------------------------------------
# Response functions
# ---------------------------------------------------------------------------


def responses(w, dp: DeviceParams, cfg: DeviceConfig) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(q_plus, q_minus) for the device family."""
    gamma, rho = dp["gamma"], dp["rho"]
    if cfg.kind in ("softbounds", "linear"):
        qp = kref.q_plus(w, gamma, rho, cfg.tau_max)
        qm = kref.q_minus(w, gamma, rho, cfg.tau_min)
    elif cfg.kind == "exp":
        # monotone exponential family (Def. C.1): q+ decreasing, q- increasing
        qp = (gamma + rho) * jnp.exp(-cfg.exp_kappa * w / cfg.tau_max)
        qm = (gamma - rho) * jnp.exp(cfg.exp_kappa * w / cfg.tau_min)
    else:
        raise ValueError(f"unknown device kind {cfg.kind}")
    # Def 2.1 positive-definiteness: clip away dead regions
    eps = 1e-4
    return jnp.maximum(qp, eps), jnp.maximum(qm, eps)


def fg(w, dp: DeviceParams, cfg: DeviceConfig):
    qp, qm = responses(w, dp, cfg)
    return (qm + qp) * 0.5, (qm - qp) * 0.5


def symmetric_point(dp: DeviceParams, cfg: DeviceConfig):
    """Ground-truth SP (G(w)=0). Closed form for softbounds; exp family has
    w_sp where (gamma-rho) e^{k w/tmin} = (gamma+rho) e^{-k w/tmax}."""
    gamma, rho = dp["gamma"], dp["rho"]
    a_p = gamma + rho
    a_m = gamma - rho
    if cfg.kind in ("softbounds", "linear"):
        return (a_p - a_m) / (a_p / cfg.tau_max + a_m / cfg.tau_min)
    if cfg.kind == "exp":
        k = cfg.exp_kappa
        return jnp.log(a_p / a_m) / (k / cfg.tau_min + k / cfg.tau_max)
    raise ValueError(cfg.kind)
