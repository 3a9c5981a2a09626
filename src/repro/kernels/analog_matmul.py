"""IO-quantized analog MVM Pallas kernel (compiled on TPU; platform.py).

Simulates a crossbar forward pass with DAC/ADC non-idealities (paper Table 7):
ABS_MAX input scaling, 7-bit input quantization, MXU matmul, additive output
noise, ADC bound clipping and 9-bit output quantization — all fused so the
activation tensor makes a single HBM round trip instead of five.

Layout: grid (M/bm, N/bn, K/bk) with K innermost; the f32 output block acts
as the accumulator (initialized at k==0, epilogue applied at k==K-1), which
keeps the kernel backend-agnostic (no scratch allocation needed in interpret
mode). Block dims default to MXU-aligned (128, 128, 512).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import platform

DEFAULT_BLOCKS = (128, 256, 512)  # (bm, bn, bk)


def _kernel(
    x_ref,      # (bm, bk)
    w_ref,      # (bk, bn)
    s_ref,      # (bm, 1)   per-row ABS_MAX scale
    noise_ref,  # (bm, bn)
    o_ref,      # (bm, bn) f32 accumulator / output
    *,
    nk: int,
    inp_res: float,
    inp_bound: float,
    out_res: float,
    out_bound: float,
    out_noise: float,
):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    s = s_ref[...].astype(jnp.float32)
    xn = x_ref[...].astype(jnp.float32) / s
    xq = jnp.clip(xn, -inp_bound, inp_bound)
    xq = jnp.round(xq * (1.0 / inp_res)) * inp_res
    o_ref[...] += jnp.dot(xq, w_ref[...].astype(jnp.float32), preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _epilogue():
        y = o_ref[...]
        y = y + out_noise * noise_ref[...].astype(jnp.float32)
        y = jnp.clip(y, -out_bound, out_bound)
        y = jnp.round(y * (1.0 / out_res)) * out_res
        o_ref[...] = y * s


def analog_mvm_pallas(
    x,
    w,
    s,
    noise,
    *,
    inp_res: float,
    inp_bound: float,
    out_res: float,
    out_bound: float,
    out_noise: float,
    blocks=DEFAULT_BLOCKS,
):
    """x: (M, K), w: (K, N), s: (M, 1) row scales, noise: (M, N) N(0,1).

    Returns f32 (M, N); ``ops.analog_mvm`` handles batching/padding/casting.
    """
    m, k = x.shape
    k2, n = w.shape
    assert k == k2
    bm = min(blocks[0], m)
    bn = min(blocks[1], n)
    bk = min(blocks[2], k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, "ops.py pads"
    nk = k // bk
    grid = (m // bm, n // bn, nk)

    kern = functools.partial(
        _kernel,
        nk=nk,
        inp_res=float(inp_res),
        inp_bound=float(inp_bound),
        out_res=float(out_res),
        out_bound=float(out_bound),
        out_noise=float(out_noise),
    )
    return platform.run(
        lambda interpret: pl.pallas_call(
            kern,
            out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
            grid=grid,
            in_specs=[
                pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
                pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
                pl.BlockSpec((bm, 1), lambda i, j, kk: (i, 0)),
                pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
            interpret=interpret,
        ),
        x, w, s, noise)
