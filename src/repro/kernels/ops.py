"""jit-ready wrappers around the Pallas kernels with pure-jnp fallbacks.

Dispatch policy: on a TPU backend the fused Pallas kernels run, compiled
(``platform.run``); on any other backend the default is the jnp reference
path (the Pallas interpreter executes block-by-block in Python and is only
meant for correctness tests). Both paths consume *identical* random bits so
they are bit-comparable: tests assert allclose between backends for the
same key.

All wrappers accept arbitrary-rank inputs; internally tensors are viewed as
2-D (or kept as 3-D tile stacks) and tiled by blocks that divide them where
such blocks exist, zero-padded to block multiples otherwise.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from . import fastrng, ref
from .analog_matmul import DEFAULT_BLOCKS as MVM_BLOCKS
from .analog_matmul import analog_mvm_pallas
from .analog_update import DEFAULT_BLOCK as UPD_BLOCK
from .analog_update import SEEDED_BLOCK, analog_update_pallas, analog_update_seeded_pallas
from .sp_filter import sp_filter_pallas

_BACKEND: Optional[str] = None  # None = auto


def set_backend(name: Optional[str]) -> None:
    """Force kernel backend: 'ref', 'pallas', or None for auto."""
    global _BACKEND
    assert name in (None, "ref", "pallas")
    _BACKEND = name


def backend() -> str:
    if _BACKEND is not None:
        return _BACKEND
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def _fit(dim: int, target: int, align: int) -> int:
    """Block length for one array dim: the whole dim when it fits in
    ``target``, else the largest multiple of ``align`` up to ``target``
    that divides it (no padding copy), else ``target`` (ops pad)."""
    if dim <= target:
        return dim
    for b in range(target - target % align, 0, -align):
        if dim % b == 0:
            return b
    return target


def _update_block(m: int, n: int, target=UPD_BLOCK):
    # sublane blocks in multiples of 16 (bf16 packs two rows per sublane),
    # lane blocks in multiples of 128
    return _fit(m, target[0], 16), _fit(n, target[1], 128)


def _pad2d(x, bm, bn, fill=0.0):
    m, n = x.shape
    pm = (-m) % bm
    pn = (-n) % bn
    if pm or pn:
        x = jnp.pad(x, ((0, pm), (0, pn)), constant_values=fill)
    return x


def _pad3d(x, bm, bn, fill=0.0):
    _, m, n = x.shape
    pm = (-m) % bm
    pn = (-n) % bn
    if pm or pn:
        x = jnp.pad(x, ((0, 0), (0, pm), (0, pn)), constant_values=fill)
    return x


def _view2d(x):
    """View an arbitrary-rank array as 2-D (leading dims flattened)."""
    if x.ndim == 0:
        return x.reshape(1, 1)
    if x.ndim == 1:
        return x.reshape(1, -1)
    if x.ndim == 2:
        return x
    return x.reshape(-1, x.shape[-1])


# ---------------------------------------------------------------------------
# analog pulse update
# ---------------------------------------------------------------------------


def analog_update(
    w,
    dw,
    gamma,
    rho,
    key,
    *,
    dw_min: float,
    tau_min: float,
    tau_max: float,
    sigma_c2c: float,
    bl: int = 0,
    rng: str = "threefry",
    noise=None,
):
    """Fused analog pulse update; see kernels/ref.analog_update_ref.

    rng='threefry' uses jax.random (paper-grade, bit-stable); rng='hash'
    uses the fused stateless hash (kernels/fastrng.py) — required at LM
    scale where threefry's while-loop blocks GSPMD sharding propagation.
    ``noise`` optionally supplies pre-drawn ``(ubits, zeta)`` at ``w.shape``
    (the grouped engine's fused backend draws one batched stream for a
    whole tile stack); when given, ``key``/``rng`` are ignored and may be
    None.
    """
    kwargs = dict(
        dw_min=dw_min, tau_min=tau_min, tau_max=tau_max, sigma_c2c=sigma_c2c, bl=bl
    )

    def make_noise(shape):
        if noise is not None:
            return noise
        if rng == "hash":
            seed = fastrng.seed_from_key(key)
            return (fastrng.hash_bits(seed, shape, 1),
                    fastrng.hash_normal(seed, shape, 2))
        ku, kz = jax.random.split(key)
        return (jax.random.bits(ku, shape, dtype=jnp.uint32),
                jax.random.normal(kz, shape, dtype=jnp.float32))

    # named scopes (core/trainer.py TRACE_SCOPES): noise draws and padding
    # are "pulse_operands", the kernel or its jnp oracle "pulse_kernel"
    if backend() != "pallas":
        # Pure-jnp path operates on the ORIGINAL shapes: everything is
        # element-wise, and any reshape/pad of a (scan, zero, model)-sharded
        # tile array would force GSPMD to rematerialize it replicated.
        with jax.named_scope("pulse_operands"):
            ubits, zeta = make_noise(w.shape)
        with jax.named_scope("pulse_kernel"):
            return ref.analog_update_ref(w, dw, gamma, rho, ubits, zeta,
                                         **kwargs)

    shape = w.shape
    with jax.named_scope("pulse_operands"):
        ubits, zeta = make_noise(shape)
    if w.ndim == 3:
        # Tile-stack fast path: keep the member axis as the outermost kernel
        # grid dimension instead of flattening members into one 2-D view.
        m, n = shape[1:]
        bm, bn = _update_block(m, n)
        pad3 = lambda x, fill=0.0: _pad3d(x, bm, bn, fill=fill)
        with jax.named_scope("pulse_operands"):
            args = (pad3(w), pad3(dw), pad3(gamma, fill=1.0), pad3(rho),
                    pad3(ubits, fill=jnp.uint32(1 << 31)), pad3(zeta))
        with jax.named_scope("pulse_kernel"):
            out = analog_update_pallas(*args, block=(bm, bn), **kwargs)
        return out[:, :m, :n]
    w2 = _view2d(w)
    m, n = w2.shape
    bm, bn = _update_block(m, n)
    with jax.named_scope("pulse_operands"):
        w2 = _pad2d(w2, bm, bn)
        dw2 = _pad2d(_view2d(dw), bm, bn)
        g2 = _pad2d(_view2d(gamma), bm, bn, fill=1.0)
        r2 = _pad2d(_view2d(rho), bm, bn)
        # Draw noise at the ORIGINAL shape so ref and pallas consume
        # identical random bits for any (possibly non-block-multiple) tile,
        # then pad into the kernel grid: ubits=2^31 / zeta=0 keep the dw=0
        # padding inert.
        u2 = _pad2d(_view2d(ubits), bm, bn, fill=jnp.uint32(1 << 31))
        z2 = _pad2d(_view2d(zeta), bm, bn)
    with jax.named_scope("pulse_kernel"):
        out = analog_update_pallas(
            w2, dw2, g2, r2, u2, z2, block=(bm, bn), **kwargs
        )
    return out[:m, :n].reshape(shape)


def analog_update_seeded(w, dw, dev_seed, key, *, device, bl: int = 0,
                         with_device: bool = False):
    """``analog_update(rng='hash')`` for a device given by its seed.

    ``dev_seed`` is the (2,) uint32 seed that
    ``core/device.sample_device(method='hash')`` draws ``device``'s
    ``(gamma, rho)`` from. On the Pallas backend the seeded kernel draws
    them, and the noise, in VMEM: no weight-sized operand is written. The
    jnp path draws them over the whole array with the same functions, so
    both give the same bits. ``with_device`` returns ``(w_new, gamma,
    rho)``: the device params as drawn, for a reader that needs them too.
    """
    kwargs = dict(dw_min=device.dw_min, tau_min=device.tau_min,
                  tau_max=device.tau_max, sigma_c2c=device.sigma_c2c, bl=bl)
    if backend() != "pallas":
        with jax.named_scope("pulse_operands"):
            gamma, rho = ref.hash_device(dev_seed[0], dev_seed[1],
                                         fastrng.linear_index(w.shape), device)
        out = analog_update(w, dw, gamma, rho, key, rng="hash", **kwargs)
        return (out, gamma, rho) if with_device else out
    with jax.named_scope("pulse_operands"):
        seeds = jnp.concatenate([dev_seed, fastrng.seed_from_key(key)])[None]
    # a row-major 2-D view keeps every element's linear index
    w2 = _view2d(w)
    with jax.named_scope("pulse_kernel"):
        out = analog_update_seeded_pallas(
            w2, _view2d(dw), seeds, device=device, bl=bl,
            block=_update_block(*w2.shape, SEEDED_BLOCK),
            with_device=with_device)
    if with_device:
        return tuple(x.reshape(w.shape) for x in out)
    return out.reshape(w.shape)


# ---------------------------------------------------------------------------
# analog MVM
# ---------------------------------------------------------------------------


def analog_mvm(
    x,
    w,
    key,
    *,
    inp_res: float,
    inp_bound: float,
    out_res: float,
    out_bound: float,
    out_noise: float,
):
    """IO-quantized crossbar forward: x (..., K) @ w (K, N)."""
    batch_shape = x.shape[:-1]
    k = x.shape[-1]
    n = w.shape[-1]
    x2 = x.reshape(-1, k)
    m = x2.shape[0]
    kwargs = dict(
        inp_res=inp_res,
        inp_bound=inp_bound,
        out_res=out_res,
        out_bound=out_bound,
        out_noise=out_noise,
    )
    if backend() == "pallas":
        bm = min(MVM_BLOCKS[0], m)
        bn = min(MVM_BLOCKS[1], n)
        bk = min(MVM_BLOCKS[2], k)
        s = jnp.maximum(jnp.max(jnp.abs(x2.astype(jnp.float32)), axis=-1, keepdims=True), 1e-12)
        xp = _pad2d(x2, bm, bk)
        wp = _pad2d(w, bk, bn)
        sp = _pad2d(s, bm, 1, fill=1.0)
        # noise at the original output shape (bit-identical to the ref path),
        # zero-padded into the kernel grid
        noise = _pad2d(jax.random.normal(key, (m, n), dtype=jnp.float32),
                       xp.shape[0], wp.shape[1])
        out = analog_mvm_pallas(xp, wp, sp, noise, **kwargs)
        out = out[:m, :n].astype(x.dtype)
    else:
        noise = jax.random.normal(key, (m, n), dtype=jnp.float32)
        out = ref.analog_mvm_ref(x2, w, noise, **kwargs)
    return out.reshape(*batch_shape, n)


# ---------------------------------------------------------------------------
# SP filter
# ---------------------------------------------------------------------------


def sp_filter(
    q,
    p,
    gamma,
    rho,
    *,
    eta: float,
    tau_min: float,
    tau_max: float,
):
    """EMA tracking update (12) + telemetry. Returns (q_new, gp_sq, err_sq)."""
    shape = q.shape
    q2 = _view2d(q)
    m, n = q2.shape
    bm = min(256, m)
    bn = min(512, n)
    q2 = _pad2d(q2, bm, bn)
    p2 = _pad2d(_view2d(p), bm, bn)
    g2 = _pad2d(_view2d(gamma), bm, bn, fill=1.0)
    r2 = _pad2d(_view2d(rho), bm, bn)
    if backend() == "pallas":
        q_new, gp, err = sp_filter_pallas(
            q2, p2, g2, r2, eta=eta, tau_min=tau_min, tau_max=tau_max,
        )
        # padded gamma=1, rho=0 regions contribute 0 to gp but (q-w_sp)^2 = 0
        # there as well since q=p=0 and w_sp=0.
    else:
        q_new, gp, err = ref.sp_filter_ref(
            q2, p2, g2, r2, eta=eta, tau_min=tau_min, tau_max=tau_max
        )
    return q_new[: m, : n].reshape(shape), gp, err
