"""The seven analog training algorithms over a unified tile interface.

Every algorithm implements three pure functions:

  begin_step(state, key, cfg)        -> state'        (pre-forward phase:
                                                        chopper draw + E-RIDER
                                                        Q-tilde sync, Alg.3 l.3-6)
  effective_weight(state, cfg)       -> model weight   (what fwd/bwd sees)
  update(state, grad, key, cfg, lr)  -> (state', metrics)

``grad`` is the gradient w.r.t. the *model* weight returned by
``effective_weight`` — i.e. exactly the paper's ∇f(W̄_k; ξ_k) chain.

Algorithms (paper refs):
  sgd       — plain Analog SGD (eq. 2); exhibits the SP drift of eq. (4).
  ttv1      — Tiki-Taka v1 (Gokmen & Haensch 2020): fast array P + main W,
              periodic analog transfer, fwd on W + γP.
  ttv2      — Tiki-Taka v2 (Gokmen 2021): + digital hidden accumulator H with
              thresholded transfer (forget-buffer).
  agad      — AGAD (Rasch et al. 2024): chopped TT-v2; gradients evaluated at
              the *main* array only (paper App. B.2).
  residual  — two-stage Residual Learning + ZS (paper Alg. 4; Wu et al. 2025):
              Q ≡ static SP estimate.
  rider     — RIDER (paper Alg. 2): eq. (11a), (12), (11b).
  erider    — E-RIDER (paper Alg. 3): chopper (17), updates (18a/18b),
              periodic Q̃ programming on chopper flips.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from .device import fg, symmetric_point
from .pulse import analog_update
from .tile import TileConfig, TileState, expected_pulses

Metrics = Dict[str, jnp.ndarray]


# ---------------------------------------------------------------------------
# shared helpers
# ---------------------------------------------------------------------------


def _au(x, dx, dev, dcfg, key, cfg: TileConfig, with_device: bool = False):
    """The pulse update of ``x``. ``with_device`` returns ``(x_new,
    params)``: the device params the update used where the diagnostics read
    them (``metrics='full'``), else None. A seeded device is then drawn
    once per step, not again for ``gp_sq``/``sp_err``."""
    keep = with_device and cfg.metrics == "full"
    out = analog_update(x, dx, dev, dcfg, key, bl=cfg.bl, mode=cfg.pulse_mode,
                        rng=cfg.rng, with_device=keep)
    return (out, None) if with_device and not keep else out


def _dev(st: TileState, which: str):
    """The device of array ``which``: its stored params, else the tile
    seed, which the pulse update draws them from (store_device=False —
    DESIGN.md §3 memory/compute trade; ``pulse.analog_update``)."""
    dev = st.get(f"dev_{which}")
    return dev if dev is not None else st[f"seed_{which}"]


def _base_metrics(cfg: TileConfig, st: TileState, dw_p=None, dw_w=None,
                  dev_p=None) -> Metrics:
    # each diagnostic below is an extra full pass + reduction over the tile;
    # cfg.metrics trades them away at LM scale ('pulses' / 'none')
    with jax.named_scope("tile_metrics"):
        if cfg.metrics == "none":
            return {}
        m: Metrics = {}
        pulses = jnp.zeros((), jnp.float32)
        if dw_p is not None:
            pulses = pulses + expected_pulses(dw_p, cfg.device_p.dw_min, cfg.bl)
        if dw_w is not None:
            pulses = pulses + expected_pulses(dw_w, cfg.device_w.dw_min, cfg.bl)
        m["pulses"] = pulses
        if cfg.metrics == "pulses":
            return m
        if dev_p is not None:
            _, g = fg(st["P"].astype(jnp.float32), dev_p, cfg.device_p)
            m["gp_sq"] = jnp.mean(g * g)
            if st.get("Qd") is not None:
                sp = symmetric_point(dev_p, cfg.device_p)
                m["sp_err"] = jnp.mean((st["Qd"].astype(jnp.float32) - sp) ** 2)
        return m


def _grad_to_analog(st: TileState, grad, cfg: TileConfig):
    """Model-space gradient -> analog-space gradient (chain through scale).

    With grad_norm='absmean' the gradient is rescaled so a fast-LR of 1.0
    delivers ~1 pulse per element per step regardless of device granularity
    (the AIHWKit auto-granularity mechanism the paper's configs rely on).

    The mean-|g| here must be *per tile*: the batched tile engine drives this
    through jax.vmap over the TileBank stack axis, so `jnp.mean` sees one
    tile's slice, never the whole stack. Callers operating on stacked arrays
    directly must vmap — a raw call would normalize across the group and
    couple tiles of different gradient magnitude.
    """
    g = grad.astype(jnp.float32) * st["scale"]
    if cfg.grad_norm == "absmean":
        g = g / (jnp.mean(jnp.abs(g)) + 1e-12) * cfg.device_p.dw_min
    return g


# ---------------------------------------------------------------------------
# begin_step
# ---------------------------------------------------------------------------


def begin_step(st: TileState, key, cfg: TileConfig) -> TileState:
    """Pre-forward phase: draw chopper c_k (17); E-RIDER syncs Q̃ on flips."""
    if cfg.algorithm not in ("agad", "erider"):
        return st
    st = TileState(st)
    flip = jax.random.bernoulli(key, cfg.chopper_p)
    c_new = jnp.where(flip, -st["c"], st["c"])
    st["c"] = c_new
    if cfg.algorithm == "erider":
        # Alg. 3 lines 4-6: on sign change, reprogram the analog Q̃ from the
        # digital Q (weight programming event).
        st["Qt"] = jnp.where(flip, st["Qd"], st["Qt"])
        st["prog"] = st["prog"] + flip.astype(jnp.int32)
    return st


# ---------------------------------------------------------------------------
# effective weight (model space)
# ---------------------------------------------------------------------------


def effective_weight(st: TileState, cfg: TileConfig):
    a = cfg.algorithm
    w = st["W"].astype(jnp.float32)
    if a == "sgd":
        eff = w
    elif a in ("ttv1", "ttv2"):
        eff = w + cfg.gamma * st["P"].astype(jnp.float32)
    elif a == "agad":
        eff = w  # gradients on the main array only (App. B.2)
    elif a == "residual":
        eff = w + cfg.gamma * (st["P"] - st["Qd"]).astype(jnp.float32)
    elif a == "rider":
        eff = w + cfg.gamma * (st["P"] - st["Qd"]).astype(jnp.float32)
    elif a == "erider":
        eff = w + cfg.gamma * st["c"] * (st["P"] - st["Qt"]).astype(jnp.float32)
    else:
        raise ValueError(a)
    # model-space weight in the tile's storage dtype (bf16 at LM scale)
    return (eff * st["scale"]).astype(st["W"].dtype)


# ---------------------------------------------------------------------------
# update
# ---------------------------------------------------------------------------


def update(
    st: TileState, grad, key, cfg: TileConfig, lr
) -> Tuple[TileState, Metrics]:
    a = cfg.algorithm
    st = TileState(st)
    # the kernel's inputs: analog-space gradient, keys and devices (params,
    # or the tile seed the update draws them from)
    with jax.named_scope("pulse_operands"):
        g = _grad_to_analog(st, grad, cfg)
        kp, kw, kq = jax.random.split(key, 3)
        alpha = lr * cfg.lr_p
        beta = lr * cfg.lr_w
        dev_w = _dev(st, "w")
        dev_p = _dev(st, "p") if (
            st.get("dev_p") is not None or st.get("seed_p") is not None) else None

    if a == "sgd":
        with jax.named_scope("pulse_operands"):
            dw = -beta * g
        st["W"] = _au(st["W"], dw, dev_w, cfg.device_w, kw, cfg)
        metrics = _base_metrics(cfg, st, dw_w=dw)

    elif a in ("ttv1", "ttv2", "agad"):
        c = st["c"] if a == "agad" else jnp.ones((), jnp.float32)
        with jax.named_scope("pulse_operands"):
            dp = -alpha * c * g
        st["P"], dev_p = _au(st["P"], dp, dev_p, cfg.device_p, kp, cfg,
                              with_device=True)
        do_transfer = (st["t"] % cfg.transfer_every) == 0
        read = st["P"].astype(jnp.float32)  # analog readout of the fast array
        if a == "ttv1":
            dw = jnp.where(do_transfer, beta * read, 0.0)
            st["W"] = _au(st["W"], dw, dev_w, cfg.device_w, kw, cfg)
        else:
            if a == "agad":
                # Dynamic reference estimation (Rasch et al. 2024): an
                # un-demodulated low-pass of the readout isolates the DC
                # component = the fast array's drift point; transfers are
                # demodulated *and* offset-corrected.
                st["Qd"] = ((1.0 - cfg.eta) * st["Qd"].astype(jnp.float32)
                            + cfg.eta * read).astype(st["Qd"].dtype)
                read = read - st["Qd"].astype(jnp.float32)
            # TT-v2 / AGAD: digital hidden accumulator with thresholded
            # transfer and forget-buffer semantics.
            thr = cfg.threshold * cfg.device_w.dw_min
            h = st["H"] + jnp.where(do_transfer, beta * c * read, 0.0)
            n = jnp.trunc(h / thr)
            dw = n * thr
            st["H"] = h - dw
            st["W"] = _au(st["W"], dw, dev_w, cfg.device_w, kw, cfg)
        metrics = _base_metrics(cfg, st, dw_p=dp, dw_w=dw, dev_p=dev_p)

    elif a in ("residual", "rider", "erider"):
        c = st["c"] if a == "erider" else jnp.ones((), jnp.float32)
        # (11a)/(18a): P <- P - alpha c grad  (asymmetric pulse update)
        with jax.named_scope("pulse_operands"):
            dp = -alpha * c * g
        st["P"], dev_p = _au(st["P"], dp, dev_p, cfg.device_p, kp, cfg,
                              with_device=True)
        p_new = st["P"].astype(jnp.float32)
        # (11b)/(18b): W <- W + beta c (P_{k+1} - Q_k)
        q_ref = st["Qt"] if a == "erider" else st["Qd"]
        with jax.named_scope("pulse_operands"):
            dw = beta * c * (p_new - q_ref.astype(jnp.float32))
        if cfg.buffered_transfer:
            # digital forget-buffer: emit only whole-pulse increments
            thr = cfg.threshold * cfg.device_w.dw_min
            h = st["H"] + dw
            dw = jnp.trunc(h / thr) * thr
            st["H"] = h - dw
        st["W"] = _au(st["W"], dw, dev_w, cfg.device_w, kw, cfg)
        # (12): digital EMA tracking (rider/erider only)
        if a in ("rider", "erider"):
            st["Qd"] = ((1.0 - cfg.eta) * st["Qd"].astype(jnp.float32)
                        + cfg.eta * p_new).astype(st["Qd"].dtype)
        metrics = _base_metrics(cfg, st, dw_p=dp, dw_w=dw, dev_p=dev_p)
        if a == "erider" and cfg.metrics != "none":
            with jax.named_scope("tile_metrics"):
                metrics["prog_events"] = st["prog"].astype(jnp.float32)

    else:
        raise ValueError(a)

    st["t"] = st["t"] + 1
    return st, metrics


# ---------------------------------------------------------------------------
# batched update (the grouped engine's 'fused' backend)
# ---------------------------------------------------------------------------


def _hash_noise_batched(seeds, shape):
    """Per-tile fastrng streams for a (n, *shape) stack: row i consumes
    exactly the bits ``kops.analog_update(rng='hash')`` would draw for tile
    i alone (seed = raw key data, salts 1/2), so the batched update stays
    bit-identical to the vmapped per-tile one."""
    from repro.kernels import fastrng

    ub = jax.vmap(lambda s: fastrng.hash_bits(s, shape, 1))(seeds)
    zt = jax.vmap(lambda s: fastrng.hash_normal(s, shape, 2))(seeds)
    return ub, zt


def update_batched(
    st: TileState, grad, keys_raw, cfg: TileConfig, lr
) -> Tuple[TileState, Metrics]:
    """``update`` over a whole (n, *member) group stack in one program.

    ``st`` is a TileBank group stack (array leaves (n, *member), per-tile
    scalars (n,), seeds (n, 2)); ``keys_raw`` is the (n, 2) raw key data the
    vmap backend would hand each tile. Noise comes from per-tile fastrng
    hash streams (no threefry while-loops over weight-sized arrays) and the
    pulse update runs on the full stack — on TPU that is one 3-D batched
    Pallas kernel launch per array. Bit-identical to
    ``jax.vmap(update)(..., rng='hash')`` (tested): same per-tile key
    derivation, same hash bits, same elementwise math — only the program
    shape differs. Per-tile reductions (absmean grad norm, metrics) reduce
    over member axes only, so tiles never couple.
    """
    from .device import sample_device

    a = cfg.algorithm
    st = TileState(st)
    nd = st["W"].ndim
    axes = tuple(range(1, nd))
    member = st["W"].shape[1:]

    def bc(x):  # per-tile scalar (n,) -> broadcast shape (n, 1, ..., 1)
        return x.reshape(x.shape + (1,) * (nd - x.ndim))

    def dev_of(which):
        dev = st.get(f"dev_{which}")
        if dev is not None:
            return dev
        dcfg = cfg.device_p if which == "p" else cfg.device_w
        return jax.vmap(lambda sd: sample_device(
            jax.random.wrap_key_data(sd), member, dcfg, method="hash")
        )(st[f"seed_{which}"])

    def au(x, dx, dev, dcfg, kraw):
        with jax.named_scope("pulse_operands"):
            noise = _hash_noise_batched(kraw, member)
        return analog_update(x, dx, dev, dcfg, None, bl=cfg.bl,
                             mode=cfg.pulse_mode, noise=noise)

    def pulses_of(dw, dw_min):
        n = jnp.abs(dw.astype(jnp.float32)) / dw_min
        if cfg.bl:
            n = jnp.minimum(n, float(cfg.bl))
        return jnp.sum(n, axis=axes)

    def base_metrics(dw_p=None, dw_w=None) -> Metrics:
        with jax.named_scope("tile_metrics"):
            if cfg.metrics == "none":
                return {}
            m: Metrics = {}
            pulses = jnp.zeros(st["scale"].shape, jnp.float32)
            if dw_p is not None:
                pulses = pulses + pulses_of(dw_p, cfg.device_p.dw_min)
            if dw_w is not None:
                pulses = pulses + pulses_of(dw_w, cfg.device_w.dw_min)
            m["pulses"] = pulses
            has_dev_p = st.get("dev_p") is not None or st.get("seed_p") is not None
            if cfg.metrics == "pulses":
                return m
            if st.get("P") is not None and has_dev_p:
                dev_p = dev_of("p")
                _, gg = fg(st["P"].astype(jnp.float32), dev_p, cfg.device_p)
                m["gp_sq"] = jnp.mean(gg * gg, axis=axes)
                if st.get("Qd") is not None:
                    sp = symmetric_point(dev_p, cfg.device_p)
                    m["sp_err"] = jnp.mean(
                        (st["Qd"].astype(jnp.float32) - sp) ** 2, axis=axes)
            return m

    with jax.named_scope("pulse_operands"):
        g = grad.astype(jnp.float32) * bc(st["scale"])
        if cfg.grad_norm == "absmean":
            g = (g / (jnp.mean(jnp.abs(g), axis=axes, keepdims=True) + 1e-12)
                 * cfg.device_p.dw_min)
        # per-tile kp/kw key chain, identical to update()'s split(key, 3)
        ks = jax.vmap(lambda kr: jax.random.key_data(
            jax.random.split(jax.random.wrap_key_data(kr), 3)))(keys_raw)
        kp, kw = ks[:, 0], ks[:, 1]
        alpha = lr * cfg.lr_p
        beta = lr * cfg.lr_w
        dev_w = dev_of("w")
        dev_p = dev_of("p") if (st.get("dev_p") is not None
                                or st.get("seed_p") is not None) else None

    if a == "sgd":
        with jax.named_scope("pulse_operands"):
            dw = -beta * g
        st["W"] = au(st["W"], dw, dev_w, cfg.device_w, kw)
        metrics = base_metrics(dw_w=dw)

    elif a in ("ttv1", "ttv2", "agad"):
        c = bc(st["c"]) if a == "agad" else jnp.ones((), jnp.float32)
        with jax.named_scope("pulse_operands"):
            dp = -alpha * c * g
        st["P"] = au(st["P"], dp, dev_p, cfg.device_p, kp)
        do_transfer = bc((st["t"] % cfg.transfer_every) == 0)
        read = st["P"].astype(jnp.float32)
        if a == "ttv1":
            dw = jnp.where(do_transfer, beta * read, 0.0)
            st["W"] = au(st["W"], dw, dev_w, cfg.device_w, kw)
        else:
            if a == "agad":
                st["Qd"] = ((1.0 - cfg.eta) * st["Qd"].astype(jnp.float32)
                            + cfg.eta * read).astype(st["Qd"].dtype)
                read = read - st["Qd"].astype(jnp.float32)
            thr = cfg.threshold * cfg.device_w.dw_min
            h = st["H"] + jnp.where(do_transfer, beta * c * read, 0.0)
            n = jnp.trunc(h / thr)
            dw = n * thr
            st["H"] = h - dw
            st["W"] = au(st["W"], dw, dev_w, cfg.device_w, kw)
        metrics = base_metrics(dw_p=dp, dw_w=dw)

    elif a in ("residual", "rider", "erider"):
        c = bc(st["c"]) if a == "erider" else jnp.ones((), jnp.float32)
        with jax.named_scope("pulse_operands"):
            dp = -alpha * c * g
        st["P"] = au(st["P"], dp, dev_p, cfg.device_p, kp)
        p_new = st["P"].astype(jnp.float32)
        q_ref = st["Qt"] if a == "erider" else st["Qd"]
        with jax.named_scope("pulse_operands"):
            dw = beta * c * (p_new - q_ref.astype(jnp.float32))
        if cfg.buffered_transfer:
            thr = cfg.threshold * cfg.device_w.dw_min
            h = st["H"] + dw
            dw = jnp.trunc(h / thr) * thr
            st["H"] = h - dw
        st["W"] = au(st["W"], dw, dev_w, cfg.device_w, kw)
        if a in ("rider", "erider"):
            st["Qd"] = ((1.0 - cfg.eta) * st["Qd"].astype(jnp.float32)
                        + cfg.eta * p_new).astype(st["Qd"].dtype)
        metrics = base_metrics(dw_p=dp, dw_w=dw)
        if a == "erider" and cfg.metrics != "none":
            with jax.named_scope("tile_metrics"):
                metrics["prog_events"] = st["prog"].astype(jnp.float32)

    else:
        raise ValueError(a)

    st["t"] = st["t"] + 1
    return st, metrics
