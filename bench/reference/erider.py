"""Plain reference of E-RIDER training (paper Alg. 3) over a decoder LM.

Independent of the program: the tile algorithm, the soft-bounds device and
the digital SGD with momentum are written here from the paper and from the
configuration's stated numbers, in float32 arithmetic on state held at the
configured storage type (bfloat16 tiles and digital weights). Random draws
(device-to-device variation, chopper flips, stochastic pulse rounding and
cycle-to-cycle noise) come from this module's own keys, so only statistics
of them are comparable with the program, never single draws.

One tile per analog leaf (a leaf stacked over layers is one tile):

  init    scale = max(max|w0|, min_weight_range) / (target_range * tau)
          W = w0 / scale, P = Qd = Qt = 0, c = +1
  begin   with probability chopper_p: c <- -c, Qt <- Qd          (17)
  weight  scale * (W + gamma * c * (P - Qt))
  update  P <- P (+) -alpha c grad*scale                         (18a)
          W <- W (+)  beta c (P - Qt)                            (18b)
          Qd <- (1 - eta) Qd + eta P                             (12)

where ``(+)`` is the analog pulse update on a soft-bounds device: the
increment is stochastically rounded to whole pulses of dw_min, each pulse
scaled by the device response, with aggregated cycle-to-cycle noise.
"""
from __future__ import annotations

import functools
import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np

from . import lm


def _fold(key, *names):
    for n in names:
        key = jax.random.fold_in(key, np.uint32(zlib.crc32(str(n).encode())))
    return key


def device_params(key, shape, d):
    """Per-element (gamma, rho) of a soft-bounds device (paper App. F.1):
    gamma = exp(sigma_d2d xi), rho = sigma_pm xi' clipped to |rho| < 0.95
    gamma; with a reference spread, rho is solved so that the symmetric
    point is N(ref_mean, ref_std^2)."""
    k1, k2, k3 = jax.random.split(key, 3)
    gamma = jnp.exp(d["sigma_d2d"] * jax.random.normal(k1, shape))
    rho = jnp.clip(d["sigma_pm"] * jax.random.normal(k2, shape),
                   -0.95 * gamma, 0.95 * gamma)
    if d.get("ref_mean", 0.0) or d.get("ref_std", 0.0):
        tmin, tmax = d["tau_min"], d["tau_max"]
        ws = d["ref_mean"] + d["ref_std"] * jax.random.normal(k3, shape)
        ws = jnp.clip(ws, -0.95 * tmin, 0.95 * tmax)
        rho = ws * gamma * (tmin + tmax) / (2 * tmin * tmax + ws * (tmin - tmax))
        rho = jnp.clip(rho, -0.95 * gamma, 0.95 * gamma)
    return gamma, rho


def pulse(x, dx, key, d):
    """Soft-bounds analog update of ``x`` (storage type) by the desired
    increment ``dx``; returns the new value in the storage type."""
    kd, ku, kz = jax.random.split(key, 3)
    gamma, rho = device_params(kd, x.shape, d)
    xf = x.astype(jnp.float32)
    n = dx / d["dw_min"]
    fl = jnp.floor(n)
    nq = fl + (jax.random.uniform(ku, x.shape) < n - fl).astype(jnp.float32)
    delta = nq * d["dw_min"]
    qp = (gamma + rho) * (1.0 - xf / d["tau_max"])
    qm = (gamma - rho) * (1.0 + xf / d["tau_min"])
    f, g = (qm + qp) * 0.5, (qm - qp) * 0.5
    noise = (d["dw_min"] * d["sigma_c2c"] * jnp.sqrt(jnp.abs(nq))
             * jnp.where(delta >= 0, qp, qm) * jax.random.normal(kz, x.shape))
    out = jnp.clip(xf + delta * f - jnp.abs(delta) * g + noise,
                   -d["tau_min"], d["tau_max"])
    return out.astype(x.dtype)


def lr_at(step: int, r: dict) -> float:
    """Warm-up then cosine to min_ratio, as the configuration states."""
    warm = min(1.0, (step + 1) / r["warmup_steps"]) if r["warmup_steps"] else 1.0
    frac = min(max((step - r["warmup_steps"])
                   / max(r["total_steps"] - r["warmup_steps"], 1), 0.0), 1.0)
    decay = r["min_ratio"] + (1 - r["min_ratio"]) * 0.5 * (1 + math.cos(math.pi * frac))
    return r["base_lr"] * warm * decay


def _tile_update(W, P, Qd, Qt, c, scale, grad, key, lr, t, transfer=True):
    """(18a), (18b), (12) on one tile; also the step's expected pulses.
    ``transfer=False`` leaves out (18b), a planted fault."""
    dp_cfg, dw_cfg = t["device_p"], t["device_w"]
    kp, kw = jax.random.split(key)
    dp = -(lr * t["lr_p"]) * c * grad * scale
    P = pulse(P, dp, kp, dp_cfg)
    pf = P.astype(jnp.float32)
    dw = (lr * t["lr_w"]) * c * (pf - Qt.astype(jnp.float32))
    if transfer:
        W = pulse(W, dw, kw, dw_cfg)
    else:
        dw = jnp.zeros_like(dw)
    Qd = ((1 - t["eta"]) * Qd.astype(jnp.float32) + t["eta"] * pf).astype(Qd.dtype)
    pulses = (jnp.sum(jnp.abs(dp)) / dp_cfg["dw_min"]
              + jnp.sum(jnp.abs(dw)) / dw_cfg["dw_min"])
    return W, P, Qd, pulses


def _norm(x):
    x = x.astype(jnp.float32)
    return jnp.sqrt(jnp.sum(x * x))


class Reference:
    """Three steps of E-RIDER training from seeded weights ``w0`` (a
    {path: array} dict in the served type) on ``batches`` (a list of
    {tokens, labels} device arrays).

    ``mode`` is the matmul arithmetic ("f32", or "fp8" for the control);
    ``rows`` the batch rows per gradient block (blocks keep the float32
    logits within memory); ``drop_half`` leaves out the second half of each
    batch and ``transfer=False`` the transfer of P into W, (18b) (planted
    faults, for the control runs)."""

    def __init__(self, m, tile, recipe, w0, seed, *, mode="f32", rows=8,
                 remat=False, drop_half=False, transfer=True, stream=0):
        self.m, self.t, self.r = m, tile, recipe
        self.mode, self.rows, self.drop_half = mode, rows, drop_half
        self.key = _fold(jax.random.key(np.uint32(seed % 2**32)),
                         "reference", seed >> 32, mode, drop_half, stream,
                         *(() if transfer else ("no_transfer",)))
        self.analog = sorted(p for p, a in w0.items()
                             if not lm.is_digital(p, a.ndim))
        self.digital = sorted(p for p in w0 if p not in self.analog)
        tau = min(tile["device_w"]["tau_min"], tile["device_w"]["tau_max"])
        sdt = jnp.dtype(tile["state_dtype"])
        self.scale, self.tiles = {}, {}
        for p in self.analog:
            w = w0[p].astype(jnp.float32)
            s = jnp.maximum(jnp.max(jnp.abs(w)), tile["min_weight_range"]) / (
                tile["target_range"] * tau)
            z = jnp.zeros(w.shape, sdt)
            self.scale[p] = s
            self.tiles[p] = {"W": (w / s).astype(sdt), "P": z, "Qd": z, "Qt": z,
                             "c": jnp.float32(1.0)}
        self.theta = {p: w0[p] for p in self.digital}
        self.mu = {p: jnp.zeros(w0[p].shape, jnp.float32) for p in self.digital}
        # the state every change is measured from
        self.start = {p: self.tiles[p]["W"] for p in self.analog}
        self.start.update(self.theta)
        self.step_no = 0

        def lsum(eff, tok, lab):
            return lm.loss_sum(lm.unflatten(eff), tok, lab, m, mode, remat)

        self._vg = jax.jit(jax.value_and_grad(lsum))
        self._upd = jax.jit(functools.partial(_tile_update, t=tile,
                                              transfer=transfer))
        self._eff = jax.jit(lambda W, P, Qt, c, s: s * (
            W.astype(jnp.float32)
            + tile["gamma"] * c * (P.astype(jnp.float32) - Qt.astype(jnp.float32))))

    def effective(self) -> dict:
        eff = {p: v.astype(jnp.float32) for p, v in self.theta.items()}
        for p in self.analog:
            ts = self.tiles[p]
            eff[p] = self._eff(ts["W"], ts["P"], ts["Qt"], ts["c"], self.scale[p])
        return eff

    def loss_and_grads(self, batch):
        tok, lab = batch["tokens"], batch["labels"]
        if self.drop_half:
            tok, lab = tok[: tok.shape[0] // 2], lab[: lab.shape[0] // 2]
        eff = self.effective()
        total, grads = 0.0, None
        for i in range(0, tok.shape[0], self.rows):
            v, g = self._vg(eff, tok[i:i + self.rows], lab[i:i + self.rows])
            total = total + v
            grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
        n = tok.size
        return total / n, {p: g / n for p, g in grads.items()}

    def step(self, batch) -> dict:
        """One training step; returns the step's loss and, for the first
        step, what each leaf's optimizer received."""
        k = self.step_no
        key = _fold(self.key, "step", k)
        for p in self.analog:  # begin_step: chopper draw (17)
            ts = self.tiles[p]
            flip = jax.random.bernoulli(_fold(key, "chopper", p),
                                        self.t["chopper_p"])
            ts["c"] = jnp.where(flip, -ts["c"], ts["c"])
            ts["Qt"] = jnp.where(flip, ts["Qd"], ts["Qt"])
        loss, grads = self.loss_and_grads(batch)
        lr = lr_at(k, self.r)
        out = {"loss": float(loss),
               "grad_norm": {p: float(_norm(g)) for p, g in grads.items()}}
        # digital branch: global-norm clip, SGD with momentum, bf16 store
        gn = jnp.sqrt(sum(jnp.sum(grads[p] ** 2) for p in self.digital))
        clip = jnp.minimum(1.0, self.r["clip_norm"] / (gn + 1e-12))
        for p in self.digital:
            self.mu[p] = self.r["momentum"] * self.mu[p] + grads[p] * clip
            self.theta[p] = (self.theta[p].astype(jnp.float32)
                             - lr * self.mu[p]).astype(self.theta[p].dtype)
        out["received"] = {p: float(_norm(self.mu[p])) for p in self.digital}
        pulses = 0.0
        for p in self.analog:
            ts = self.tiles[p]
            ts["W"], ts["P"], ts["Qd"], n = self._upd(
                ts["W"], ts["P"], ts["Qd"], ts["Qt"], ts["c"], self.scale[p],
                grads[p], _fold(key, "update", p), jnp.float32(lr))
            pulses = pulses + n
            # P starts at zero: after one step it holds the first update
            out["received"][p] = float(_norm(ts["P"]))
        out["pulses"] = float(pulses)
        self.step_no += 1
        return out

    def change(self) -> dict:
        """{leaf: norm of its change since the start}: digital weights, and
        for a tile its two analog arrays W and P together."""
        out = {p: float(_norm(self.theta[p].astype(jnp.float32)
                              - self.start[p].astype(jnp.float32)))
               for p in self.digital}
        for p in self.analog:
            ts = self.tiles[p]
            dw = _norm(ts["W"].astype(jnp.float32) - self.start[p].astype(jnp.float32))
            out[p] = float(jnp.sqrt(dw ** 2 + _norm(ts["P"]) ** 2))
        return out

    def w_change(self) -> dict:
        """W alone, over every tile: the norm of its change since the start
        and the number of its elements that changed."""
        sq, moved = 0.0, 0
        for p in self.analog:
            d = (self.tiles[p]["W"].astype(jnp.float32)
                 - self.start[p].astype(jnp.float32))
            sq += float(jnp.sum(d * d))
            moved += int(jnp.sum(d != 0))
        return {"norm": math.sqrt(sq), "moved": moved}
