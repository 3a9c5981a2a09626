"""Fused analog pulse-update Pallas kernels (compiled on TPU; platform.py).

The analog update (paper eq. 2) is purely element-wise: arithmetic
intensity << 1 FLOP/byte when every input streams from HBM. Two forms
share one pulse-math function (``_pulse``):

* the operand kernel (``analog_pulse_update``) reads W, dW, per-element
  device params (gamma, rho) and two noise streams (uint32 rounding bits,
  standard-normal c2c noise) — 6 weight-sized arrays, one read of each
  and one write of the output per element, streamed through VMEM in
  (block_m, block_n) tiles. It serves stored device arrays and threefry
  noise, whose draws cannot be made element by element.
* the seeded kernel (``analog_pulse_update_seeded``) reads only W and dW
  plus two per-tile seeds. It draws gamma, rho and the noise in VMEM from
  the hash of ``kernels/fastrng.py`` at each element's linear index, with
  the XLA path's functions, so it consumes the same bits: 8 B per element
  cross HBM, not 24 (16 B when it also writes the gamma and rho it drew).

Why the noise is hash-drawn in the kernel rather than ``pltpu.prng_*``:
the hash is plain integer jnp math, so the interpreter runs it on the CPU
and the CPU tests compare bit for bit against the XLA path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import fastrng, platform
from .ref import hash_device, uniform_from_bits

# Default VMEM tile: 6 f32 operands + 1 output at (256, 512) = ~3.7 MiB,
# comfortably inside a 16 MiB VMEM budget; last dim is a multiple of 128
# (lane width) and second-to-last a multiple of 8 (sublane width).
DEFAULT_BLOCK = (256, 512)
# The seeded kernel streams 8 B per element (w bf16, dw f32, out bf16), so
# its blocks can be larger: (512, 512) is ~4 MiB double-buffered.
SEEDED_BLOCK = (512, 512)
# The seeded kernel runs its RNG chain on (rows, cols) slabs of the block
# so the ~400 element-wise ops of one element stay in vector registers:
# 32 x 128 f32 is 4 vregs per live value. On a v5e, (32, 128) and (16, 256)
# slabs time within 5% of each other, (8, 128) 60% slower; the block size
# matters less than 3%.
SLAB = (32, 128)
# stable names of the kernels in compiled programs and device traces
KERNEL_NAME = "analog_pulse_update"
SEEDED_KERNEL_NAME = "analog_pulse_update_seeded"


def _pulse(w, dw, gam, rho, u, zeta, *, dw_min: float, tau_min: float,
           tau_max: float, sigma_c2c: float, bl: int):
    """The Analog Update (2) on f32 values: stochastically rounded pulse
    count (``u`` the rounding uniform), soft-bounds response, aggregated
    c2c noise (``zeta`` standard normal), clip to the device range."""
    inv_dwmin = 1.0 / dw_min
    n_real = dw * inv_dwmin
    n_floor = jnp.floor(n_real)
    frac = n_real - n_floor
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
    noise = (dw_min * sigma_c2c) * jnp.sqrt(jnp.abs(n_q)) * q_dir * zeta

    return jnp.clip(w + upd + noise, -tau_min, tau_max)


def _kernel(w_ref, dw_ref, gamma_ref, rho_ref, ubits_ref, zeta_ref, out_ref,
            **pulse):
    f32 = lambda ref: ref[...].astype(jnp.float32)
    w_new = _pulse(f32(w_ref), f32(dw_ref), f32(gamma_ref), f32(rho_ref),
                   uniform_from_bits(ubits_ref[...]), f32(zeta_ref), **pulse)
    out_ref[...] = w_new.astype(out_ref.dtype)


def _pulse_kwargs(dw_min, tau_min, tau_max, sigma_c2c, bl):
    return dict(dw_min=float(dw_min), tau_min=float(tau_min),
                tau_max=float(tau_max), sigma_c2c=float(sigma_c2c), bl=int(bl))


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
        _kernel, **_pulse_kwargs(dw_min, tau_min, tau_max, sigma_c2c, bl))
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


def _slab(block: int, target: int, align: int) -> int:
    """Largest multiple of ``align`` up to ``target`` dividing ``block``,
    else the whole block."""
    for s in range(target - target % align, 0, -align):
        if block % s == 0:
            return s
    return block


@functools.partial(jax.jit, static_argnames=("device", "bl"))
def _seeded_slab(idx, dev0, dev1, nz0, nz1, w, dw, *, device, bl):
    """One slab of the seeded kernel: the device params and the noise drawn
    at linear indices ``idx``, then the pulse math; ``(w_new, gamma, rho)``
    in f32. Jitted so that its trace is made once per slab shape and device
    and shared by every kernel instance (tracing the ~400-op chain anew for
    each instance and each lowering branch costs seconds per step build)."""
    gam, rho = hash_device(dev0, dev1, idx, device)
    u = uniform_from_bits(fastrng.hash_at(nz0, nz1, idx, 1))
    zeta = fastrng.normal_from_bits(fastrng.hash_at(nz0, nz1, idx, 2))
    w_new = _pulse(w, dw, gam, rho, u, zeta, **_pulse_kwargs(
        device.dw_min, device.tau_min, device.tau_max, device.sigma_c2c, bl))
    return w_new, gam, rho


def _seeded_kernel(seeds_ref, w_ref, dw_ref, out_ref, *dev_refs, n: int,
                   block, slab, device, bl: int):
    bm, bn = block
    rows, cols = slab
    # linear index of the block's first element; the edge blocks of a
    # ragged grid compute values that the output's bounds drop
    base = pl.program_id(0) * (bm * n) + pl.program_id(1) * bn
    dev0, dev1, nz0, nz1 = (seeds_ref[0, c] for c in range(4))
    offs = (jax.lax.broadcasted_iota(jnp.int32, slab, 0) * n
            + jax.lax.broadcasted_iota(jnp.int32, slab, 1))

    def body(r, carry):
        r0 = pl.multiple_of(r * rows, rows)
        # lane offsets stay static: Mosaic slices lanes at proven multiples
        # of 128 only, and a narrow block (n < 128) has one slab per row
        for c0 in range(0, bn, cols):
            idx = (offs + (base + r0 * n + c0)).astype(jnp.uint32)
            at = (pl.ds(r0, rows), pl.ds(c0, cols))
            w_new, gam, rho = _seeded_slab(
                idx, dev0, dev1, nz0, nz1, w_ref[at].astype(jnp.float32),
                dw_ref[at].astype(jnp.float32), device=device, bl=bl)
            out_ref[at] = w_new.astype(out_ref.dtype)
            for ref, x in zip(dev_refs, (gam, rho)):
                ref[at] = x
        return carry

    jax.lax.fori_loop(0, bm // rows, body, 0)


def analog_update_seeded_pallas(
    w,
    dw,
    seeds,
    *,
    device,
    bl: int = 0,
    block=SEEDED_BLOCK,
    with_device: bool = False,
):
    """The analog update with device params and noise drawn in the kernel.

    ``seeds`` is a (1, 4) uint32 array: the device seed (the tile's raw
    key data, as ``core/device.sample_device(method='hash')`` reads it)
    and the noise seed (``fastrng.seed_from_key`` of the update's key).
    ``device`` is the ``DeviceConfig`` whose ``(gamma, rho)`` the device
    seed draws; its ``dw_min``, bounds and ``sigma_c2c`` drive the pulse
    math. Every element draws at its linear index in the 2-D ``(m, n)``
    tile (``ops`` views any rank row-major, which keeps the index), exactly
    as the XLA path draws over the whole array, so the result is that of
    ``sample_device`` + ``fastrng`` noise + the operand kernel. The grid is
    ``cdiv``-sized: nothing is padded. Under ``jax.vmap`` the seeds batch
    with the tiles, one seed row per tile. ``with_device`` also returns the
    f32 ``(gamma, rho)`` the kernel drew, for a reader that would otherwise
    draw them again: ``(w_new, gamma, rho)``.
    """
    assert w.ndim == 2, "ops.analog_update_seeded views tiles as 2-D"
    assert w.size < 2 ** 31, "the kernel forms linear indices in int32"
    m, n = w.shape
    bm = min(block[0], m)
    bn = min(block[1], n)
    slab = (_slab(bm, SLAB[0], 16), _slab(bn, SLAB[1], 128))
    kern = functools.partial(_seeded_kernel, n=n, block=(bm, bn), slab=slab,
                             device=device, bl=int(bl))
    spec = pl.BlockSpec((bm, bn), lambda i, j: (i, j))
    out_shape = [jax.ShapeDtypeStruct(w.shape, w.dtype)]
    if with_device:
        out_shape += [jax.ShapeDtypeStruct(w.shape, jnp.float32)] * 2
    out = platform.run(
        lambda interpret: pl.pallas_call(
            kern,
            out_shape=out_shape,
            grid=(pl.cdiv(m, bm), pl.cdiv(n, bn)),
            in_specs=[pl.BlockSpec((1, 4), lambda i, j: (0, 0),
                                   memory_space=pltpu.SMEM), spec, spec],
            out_specs=[spec] * len(out_shape),
            interpret=interpret,
            name=SEEDED_KERNEL_NAME,
        ),
        seeds, w, dw)
    return tuple(out) if with_device else out[0]
