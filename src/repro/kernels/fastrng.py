"""Fused stateless per-element RNG for analog-update noise at LM scale.

``jax.random.bits``/``normal`` (threefry) lower to a 5-round while loop over
whole arrays; under GSPMD the loop blocks backward sharding propagation, so
the bit arrays materialize *replicated* — hundreds of MB of HBM per tile per
step. This module derives randomness from a murmur3-style integer hash of
(linear index, seed, salt): a short elementwise chain that XLA fuses into
the consumer and GSPMD shards with it. Its per-element functions
(``hash_at``, ``u32_to_f32``, ``normal_from_bits``) also run inside the
seeded pulse-update kernel (``kernels/analog_update.py``), which draws the
same bits in VMEM.

Statistical quality is far above the needs of stochastic pulse rounding and
c2c noise (verified empirically in tests/test_properties.py); the
paper-grade threefry path remains the default (TileConfig.rng).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_SQRT2 = 1.4142135623730951


def _finalize(x):
    """murmur3 fmix32 finalizer (elementwise, u32)."""
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    return x


def linear_index(shape):
    """uint32 row-major position of every element of ``shape``.

    Built from per-dimension broadcasted_iotas (not a 1-D iota + reshape)
    so GSPMD can shard the whole chain with its consumer.
    """
    idx = jnp.zeros(shape, jnp.uint32)
    stride = 1
    for d in range(len(shape) - 1, -1, -1):
        idx = idx + jax.lax.broadcasted_iota(jnp.uint32, shape, d) * jnp.uint32(stride)
        stride *= int(shape[d])
    return idx


def hash_at(seed0, seed1, idx, salt: int):
    """The hash of (linear index, seed, salt), element by element.

    ``idx`` is a uint32 array of linear indices, ``seed0``/``seed1`` uint32
    scalars. Every draw of this module goes through it: the XLA paths at
    ``linear_index(shape)``, the seeded pulse-update kernel at the indices
    of its block.
    """
    x = idx * jnp.uint32(0xCC9E2D51) + seed0 + jnp.uint32((salt * 0x9E3779B9) & 0xFFFFFFFF)
    x = _finalize(x)
    x = x ^ (seed1 + jnp.uint32(salt & 0xFFFFFFFF))
    return _finalize(x)


def hash_bits(seed, shape, salt: int):
    """seed: (2,) uint32; returns uint32 array of ``shape``."""
    return hash_at(seed[0], seed[1], linear_index(shape), salt)


def u32_to_f32(x):
    """uint32 -> f32, rounded to nearest even like ``astype(float32)``.

    The two 16-bit halves convert exactly through int32 and their sum
    rounds once, so the result is the correctly rounded one. The TPU
    kernel compiler has no uint32 -> f32 cast; this form runs there and
    in XLA alike, so both draw the same bits.
    """
    hi = jax.lax.shift_right_logical(x, jnp.uint32(16)).astype(jnp.int32)
    lo = (x & jnp.uint32(0xFFFF)).astype(jnp.int32)
    return hi.astype(jnp.float32) * 65536.0 + lo.astype(jnp.float32)


def hash_uniform(seed, shape, salt: int):
    """[0, 1) f32."""
    return u32_to_f32(hash_bits(seed, shape, salt)) * (1.0 / 4294967296.0)


# f32 just below 1: clamping |2u - 1| here caps samples at ~5.4 sigma and,
# critically, keeps erfinv off the exact +/-1 poles — without it, lattice
# values within ~6e-8 of the ends round to +/-1.0f and the inverse CDF
# returns +/-inf (once every ~1e7 draws: hours at toy scale, minutes at LM
# scale, and a single inf poisons W with NaN through the pulse update).
_ONE_MINUS_EPS = 0.99999994
_LN2 = 0.6931471805599453

# Giles (2012), "Approximating the erfinv function": single-precision
# central (w < 5) and tail polynomials in w = -log(1 - x^2).
_ERFINV_CENTRAL = (3.43273939e-07, -3.5233877e-06, -4.39150654e-06,
                   0.00021858087, -0.00125372503, -0.00417768164,
                   0.246640727, 1.50140941)
_ERFINV_TAIL = (0.000100950558, 0.00134934322, -0.00367342844,
                0.00573950773, -0.0076224613, 0.00943887047,
                1.00167406, 2.83297682)


def _fast_neg_log(y):
    """-log(y) for f32 y in (0, 1] via exponent/mantissa bitcast split.

    ``jax.lax.erf_inv``'s dominant cost is its internal log; this bitcast
    log (Mineiro's fastlog2: linear exponent term + rational mantissa
    correction, |err| < 3e-4) is ~5x cheaper and the erfinv polynomial
    contracts the error further (~5e-5 in the returned sample — far inside
    the f32 noise floor of the pulse math that consumes it).
    """
    bi = jax.lax.bitcast_convert_type(y, jnp.int32)
    mant = jax.lax.bitcast_convert_type(
        (bi & 0x007FFFFF) | 0x3F000000, jnp.float32)  # mantissa/2 in [.5, 1)
    log2y = (bi.astype(jnp.float32) * 1.1920928955078125e-07
             - 124.22551499 - 1.498030302 * mant
             - 1.72587999 / (0.3520887068 + mant))
    return -_LN2 * log2y


def hash_normal(seed, shape, salt: int):
    """Standard normal via the inverse CDF over one hashed uniform."""
    return normal_from_bits(hash_bits(seed, shape, salt))


def normal_from_bits(bits):
    """Standard normal from uint32 hash bits, element by element.

    One hash draw + a fused-friendly erfinv (fast bitcast log + Giles'
    polynomials) is ~5x cheaper than Box-Muller's log/cos pair and stays
    the exact inverse-CDF transform to ~5e-5 absolute, so distribution
    tests that pass for threefry pass here too. The +0.5 centers the
    uint32 lattice inside (0, 1).
    """
    u = (u32_to_f32(bits) + 0.5) * (1.0 / 4294967296.0)
    x = jnp.clip(2.0 * u - 1.0, -_ONE_MINUS_EPS, _ONE_MINUS_EPS)
    w = _fast_neg_log(1.0 - x * x)
    wc = w - 2.5
    p1 = jnp.float32(2.81022636e-08)
    for c in _ERFINV_CENTRAL:
        p1 = p1 * wc + jnp.float32(c)
    ws = jnp.sqrt(jnp.maximum(w, 5.0)) - 3.0
    p2 = jnp.float32(-0.000200214257)
    for c in _ERFINV_TAIL:
        p2 = p2 * ws + jnp.float32(c)
    return _SQRT2 * jnp.where(w < 5.0, p1, p2) * x


def seed_from_key(key):
    """PRNG key -> (2,) uint32 seed scalars."""
    data = jax.random.key_data(key).astype(jnp.uint32)
    return data.reshape(-1)[:2]
