"""Per-kernel shape/dtype sweeps: Pallas (interpret) vs pure-jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.analog_matmul import analog_mvm_pallas
from repro.kernels.analog_update import analog_update_pallas
from repro.kernels.sp_filter import sp_filter_pallas

KEY = jax.random.PRNGKey(0)


def _pad(x, bm, bn, fill=0.0):
    m, n = x.shape
    return jnp.pad(x, ((0, (-m) % bm), (0, (-n) % bn)), constant_values=fill)


@pytest.mark.parametrize("shape", [(8, 128), (256, 512), (300, 700), (512, 1024)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_analog_update_matches_ref(shape, dtype):
    ks = jax.random.split(KEY, 6)
    m, n = shape
    w = jax.random.uniform(ks[0], shape, jnp.float32, -0.8, 0.8).astype(dtype)
    dw = (0.05 * jax.random.normal(ks[1], shape)).astype(dtype)
    gamma = jnp.exp(0.1 * jax.random.normal(ks[2], shape))
    rho = 0.3 * jax.random.normal(ks[3], shape)
    ubits = jax.random.bits(ks[4], shape, dtype=jnp.uint32)
    zeta = jax.random.normal(ks[5], shape)
    kw = dict(dw_min=0.01, tau_min=1.0, tau_max=1.0, sigma_c2c=0.1, bl=10)
    bm, bn = min(256, m), min(512, n)
    got = analog_update_pallas(
        _pad(w, bm, bn), _pad(dw, bm, bn), _pad(gamma, bm, bn, 1.0),
        _pad(rho, bm, bn), _pad(ubits, bm, bn).astype(jnp.uint32),
        _pad(zeta, bm, bn), block=(bm, bn), **kw)[:m, :n]
    want = ref.analog_update_ref(w, dw, gamma, rho, ubits, zeta, **kw)
    tol = 1e-6 if dtype == jnp.float32 else 1e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol)


@pytest.mark.parametrize("mkn", [(64, 128, 96), (256, 384, 512), (128, 512, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_analog_mvm_matches_ref(mkn, dtype):
    m, k, n = mkn
    ks = jax.random.split(KEY, 3)
    x = jax.random.normal(ks[0], (m, k)).astype(dtype)
    w = (0.1 * jax.random.normal(ks[1], (k, n))).astype(dtype)
    noise = jax.random.normal(ks[2], (m, n))
    io = dict(inp_res=1 / 126, inp_bound=1.0, out_res=1 / 510, out_bound=12.0,
              out_noise=0.06)
    s = jnp.maximum(jnp.max(jnp.abs(x.astype(jnp.float32)), -1, keepdims=True), 1e-12)
    got = analog_mvm_pallas(x, w, s, noise, blocks=(64, 128, 128), **io)
    # compare against the oracle in f32 (bf16 inputs upcast exactly); the
    # only legitimate difference is K-block accumulation order flipping an
    # ADC LSB -> tolerance = 2 LSB x row scale
    want = ref.analog_mvm_ref(x.astype(jnp.float32), w.astype(jnp.float32),
                              noise, **io)
    tol = float(2 * io["out_res"] * jnp.max(s))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=tol)


@pytest.mark.parametrize("shape", [(256, 512), (512, 1024)])
def test_sp_filter_matches_ref(shape):
    ks = jax.random.split(KEY, 4)
    q = 0.1 * jax.random.normal(ks[0], shape)
    p = 0.2 * jax.random.normal(ks[1], shape)
    gamma = jnp.exp(0.1 * jax.random.normal(ks[2], shape))
    rho = 0.3 * jax.random.normal(ks[3], shape)
    got_q, got_g, got_e = sp_filter_pallas(q, p, gamma, rho, eta=0.3,
                                           tau_min=1.0, tau_max=1.0)
    want_q, want_g, want_e = ref.sp_filter_ref(q, p, gamma, rho, eta=0.3,
                                               tau_min=1.0, tau_max=1.0)
    np.testing.assert_allclose(np.asarray(got_q), np.asarray(want_q), atol=1e-6)
    np.testing.assert_allclose(float(got_g), float(want_g), rtol=1e-5)
    np.testing.assert_allclose(float(got_e), float(want_e), rtol=1e-5)


@pytest.mark.parametrize("shape", [(33, 97), (3, 33, 97)])
@pytest.mark.parametrize("rng", ["threefry", "hash"])
def test_ops_backends_bit_identical_on_ragged_shapes(shape, rng):
    """ref and pallas paths must consume identical random bits: noise is
    drawn at the original (non-block-multiple) shape and padded, so both
    backends agree everywhere including the last partial block."""
    from repro.kernels import ops

    ks = jax.random.split(KEY, 3)
    w = jax.random.uniform(ks[0], shape, jnp.float32, -0.8, 0.8)
    dw = 0.05 * jax.random.normal(ks[1], shape)
    gamma = jnp.exp(0.1 * jax.random.normal(ks[2], shape))
    rho = 0.3 * jnp.tanh(jax.random.normal(ks[2], shape))
    kw = dict(dw_min=0.01, tau_min=1.0, tau_max=1.0, sigma_c2c=0.1, bl=10,
              rng=rng)
    try:
        ops.set_backend("ref")
        want = ops.analog_update(w, dw, gamma, rho, KEY, **kw)
        ops.set_backend("pallas")
        got = ops.analog_update(w, dw, gamma, rho, KEY, **kw)
    finally:
        ops.set_backend(None)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


def test_ops_mvm_backends_identical_on_ragged_shapes():
    from repro.kernels import ops

    ks = jax.random.split(KEY, 2)
    x = jax.random.normal(ks[0], (5, 33, 47))
    w = 0.1 * jax.random.normal(ks[1], (47, 29))
    io = dict(inp_res=1 / 126, inp_bound=1.0, out_res=1 / 510, out_bound=12.0,
              out_noise=0.06)
    try:
        ops.set_backend("ref")
        want = ops.analog_mvm(x, w, KEY, **io)
        ops.set_backend("pallas")
        got = ops.analog_mvm(x, w, KEY, **io)
    finally:
        ops.set_backend(None)
    tol = 2 * io["out_res"] * float(jnp.max(jnp.abs(x)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=tol)


@pytest.mark.parametrize("preset", ["reram_hfo2", "reram_om",
                                    "softbounds_2000", "ecram", "ideal"])
def test_analog_update_pallas_matches_fused_generic(preset):
    """The Pallas kernel's inline softbounds response must agree with the
    generic jnp oracle (``pulse._fused_generic``) for every named device
    preset — same injected (ubits, zeta) noise, so any drift is math, not
    RNG. |w| stays inside 0.8x the device range to keep the oracle's
    positive-definiteness clip (responses() eps floor) inactive; outside it
    the kernel intentionally skips the clip (TPU fast path)."""
    from repro.core import device, pulse

    cfg = device.PRESETS[preset]
    shape = (256, 512)
    ks = jax.random.split(KEY, 5)
    lim = 0.8 * min(cfg.tau_min, cfg.tau_max)
    w = jax.random.uniform(ks[0], shape, jnp.float32, -lim, lim)
    dw = 3.0 * cfg.dw_min * jax.random.normal(ks[1], shape)
    dp = device.sample_device(ks[2], shape, cfg)
    ubits = jax.random.bits(ks[3], shape, dtype=jnp.uint32)
    zeta = jax.random.normal(ks[4], shape)
    got = analog_update_pallas(
        w, dw, dp["gamma"], dp["rho"], ubits, zeta,
        dw_min=cfg.dw_min, tau_min=cfg.tau_min, tau_max=cfg.tau_max,
        sigma_c2c=cfg.sigma_c2c, bl=10)
    want = pulse._fused_generic(w, dw, dp, cfg, None, bl=10,
                                noise=(ubits, zeta))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


def test_analog_update_pallas_batched_stack_matches_per_tile():
    """The 3-D (stack, m, n) kernel form — one grid axis per class member —
    must be bitwise the per-member 2-D kernel: the grouped engine's fused
    backend relies on this to process a whole TileBank class in one call."""
    ks = jax.random.split(KEY, 6)
    shape = (3, 64, 128)
    w = jax.random.uniform(ks[0], shape, jnp.float32, -0.8, 0.8)
    dw = 0.05 * jax.random.normal(ks[1], shape)
    gamma = jnp.exp(0.1 * jax.random.normal(ks[2], shape))
    rho = 0.3 * jax.random.normal(ks[3], shape)
    ubits = jax.random.bits(ks[4], shape, dtype=jnp.uint32)
    zeta = jax.random.normal(ks[5], shape)
    kw = dict(dw_min=0.01, tau_min=1.0, tau_max=1.0, sigma_c2c=0.1, bl=10,
              block=(64, 128))
    got = analog_update_pallas(w, dw, gamma, rho, ubits, zeta, **kw)
    for i in range(shape[0]):
        want_i = analog_update_pallas(w[i], dw[i], gamma[i], rho[i],
                                      ubits[i], zeta[i], **kw)
        np.testing.assert_array_equal(np.asarray(got[i]), np.asarray(want_i),
                                      err_msg=f"member {i}")


def test_hash_normal_finite_at_lattice_edges(monkeypatch):
    """Regression: the inverse-CDF transform must stay finite at the ends of
    the uint32 lattice. Without the clip in hash_normal, bits near 0 and
    2^32-1 round |2u-1| to exactly 1.0f and erfinv returns +-inf — one such
    draw (~1e-7 probability per element) NaN-poisons W through the pulse
    update."""
    from repro.kernels import fastrng

    edge = jnp.array([0, 1, 2 ** 31 - 1, 2 ** 31, 2 ** 32 - 2, 2 ** 32 - 1],
                     dtype=jnp.uint32)
    monkeypatch.setattr(fastrng, "hash_bits", lambda seed, shape, salt: edge)
    z = np.asarray(fastrng.hash_normal(jnp.zeros(2, jnp.uint32),
                                       edge.shape, 0))
    assert np.all(np.isfinite(z)), z
    # the clip caps samples at ~5.4 sigma; the ends are symmetric
    assert np.all(np.abs(z) < 6.0), z
    np.testing.assert_allclose(z[0], -z[-1], rtol=1e-5)
    assert z[0] < -3.0 and z[-1] > 3.0, z


def test_hash_normal_matches_exact_inverse_cdf(monkeypatch):
    """hash_normal's fast erfinv (bitcast log + Giles polynomials) tracks
    the exact inverse CDF to well inside the f32 noise floor of the pulse
    math that consumes it."""
    from repro.kernels import fastrng

    rng = np.random.default_rng(0)
    bits = jnp.asarray(rng.integers(0, 2 ** 32, size=1 << 16,
                                    dtype=np.uint32))
    monkeypatch.setattr(fastrng, "hash_bits", lambda seed, shape, salt: bits)
    got = np.asarray(fastrng.hash_normal(jnp.zeros(2, jnp.uint32),
                                         bits.shape, 0))
    u = (bits.astype(jnp.float32) + 0.5) * (1.0 / 4294967296.0)
    x = jnp.clip(2.0 * u - 1.0, -fastrng._ONE_MINUS_EPS,
                 fastrng._ONE_MINUS_EPS)
    exact = np.asarray(fastrng._SQRT2 * jax.lax.erf_inv(x.astype(jnp.float64)),
                       np.float64)
    err = np.abs(got - exact)
    assert err.mean() < 1e-4, err.mean()
    assert err.max() < 0.02, err.max()  # worst case sits in the clamped tail


def test_ops_wrappers_arbitrary_rank():
    """ops.* accept >2-D and 1-D inputs (reshape/pad handled)."""
    from repro.kernels import ops

    w = jax.random.uniform(KEY, (3, 40, 50), jnp.float32, -0.5, 0.5)
    out = ops.analog_update(
        w, 0.01 * jnp.ones_like(w), jnp.ones_like(w), jnp.zeros_like(w),
        KEY, dw_min=0.01, tau_min=1.0, tau_max=1.0, sigma_c2c=0.0)
    assert out.shape == w.shape
    x = jax.random.normal(KEY, (2, 5, 48))
    wmat = jax.random.normal(KEY, (48, 32)) * 0.1
    y = ops.analog_mvm(x, wmat, KEY, inp_res=1 / 126, inp_bound=1.0,
                       out_res=1 / 510, out_bound=12.0, out_noise=0.0)
    assert y.shape == (2, 5, 32)


# the benchmark's tile devices: P with a shifted symmetric point (every
# device draw is made), W with none (two draws)
SEEDED_P = dict(dw_min=1e-3, sigma_d2d=0.1, sigma_pm=0.3, sigma_c2c=0.05,
                ref_mean=0.1, ref_std=0.1)
SEEDED_W = dict(dw_min=1e-3, sigma_d2d=0.1, sigma_pm=0.3, sigma_c2c=0.05)


def _operand_path(w, dw, dev_seed, key, dev, bl):
    """The parent path of a seeded device: ``sample_device(method='hash')``
    and fastrng noise drawn over the whole array, then the jnp oracle."""
    from repro.core import device
    from repro.kernels import fastrng

    dp = device.sample_device(jax.random.wrap_key_data(dev_seed), w.shape,
                              dev, method="hash")
    seed = fastrng.seed_from_key(key)
    return ref.analog_update_ref(
        w, dw, dp["gamma"], dp["rho"], fastrng.hash_bits(seed, w.shape, 1),
        fastrng.hash_normal(seed, w.shape, 2), dw_min=dev.dw_min,
        tau_min=dev.tau_min, tau_max=dev.tau_max, sigma_c2c=dev.sigma_c2c,
        bl=bl)


@pytest.mark.parametrize("shape,vmapped", [
    ((64, 384), False),        # 2-D, blocks divide it
    ((600, 700), False),       # ragged: edge blocks past both bounds
    ((3, 40, 200), False),     # 3-D tile (scanned layers)
    ((4, 96, 256), True),      # vmapped over a group of tiles
    ((2, 3, 33, 97), True),    # vmapped 3-D tiles, ragged
])
@pytest.mark.parametrize("dev_kw", [SEEDED_P, SEEDED_W], ids=["P", "W"])
@pytest.mark.parametrize("bl", [0, 3])
def test_seeded_kernel_bit_identical_to_operand_path(shape, vmapped, dev_kw,
                                                     bl):
    """The seeded kernel (interpreted) draws gamma, rho and the noise at
    each element's linear index: bit for bit what the operand path draws
    over the whole array and feeds the oracle, for the same seeds."""
    from repro.core.device import DeviceConfig
    from repro.kernels import ops

    dev = DeviceConfig(**dev_kw)
    ks = jax.random.split(KEY, 4)
    n = shape[0] if vmapped else 1
    w = jax.random.uniform(ks[0], shape, jnp.float32, -0.8, 0.8
                           ).astype(jnp.bfloat16)
    dw = 3.0 * dev.dw_min * jax.random.normal(ks[1], shape)
    dev_seed = jax.random.key_data(jax.random.split(ks[2], n)
                                   ).astype(jnp.uint32)
    keys = jax.random.key_data(jax.random.split(ks[3], n))
    if not vmapped:
        dev_seed, keys = dev_seed[0], keys[0]

    def seeded(w, dw, s, k):
        return ops.analog_update_seeded(w, dw, s, jax.random.wrap_key_data(k),
                                        device=dev, bl=bl)

    def operands(w, dw, s, k):
        return _operand_path(w, dw, s, jax.random.wrap_key_data(k), dev, bl)

    lift = jax.vmap if vmapped else (lambda f: f)
    want = jax.jit(lift(operands))(w, dw, dev_seed, keys)
    ops.set_backend("pallas")
    try:
        got = jax.jit(lift(seeded))(w, dw, dev_seed, keys)
    finally:
        ops.set_backend(None)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    assert np.count_nonzero(np.asarray(got != w)) > 0.1 * w.size


def test_seeded_ref_backend_is_the_operand_path():
    """Off the Pallas backend ``analog_update_seeded`` is the operand path
    itself: the device drawn over the array, then ``analog_update``."""
    from repro.core.device import DeviceConfig
    from repro.kernels import ops

    dev = DeviceConfig(**SEEDED_P)
    shape = (5, 33, 97)
    w = jax.random.uniform(KEY, shape, jnp.float32, -0.8, 0.8)
    dw = 3e-3 * jax.random.normal(KEY, shape)
    seed = jnp.array([3, 4], jnp.uint32)
    got = jax.jit(lambda *a: ops.analog_update_seeded(*a, device=dev))(
        w, dw, seed, KEY)
    want = jax.jit(lambda *a: _operand_path(*a, dev, 0))(w, dw, seed, KEY)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_u32_to_f32_is_the_rounded_conversion():
    """The two-halves uint32 -> f32 conversion equals ``astype(float32)``
    (round to nearest even) on the edges of the rounding and a sweep."""
    from repro.kernels import fastrng

    edges = [0, 1, 2 ** 24 - 1, 2 ** 24, 2 ** 24 + 1, 2 ** 24 + 3,
             2 ** 31 - 1, 2 ** 31, 2 ** 31 + 128, 2 ** 31 + 129,
             2 ** 31 + 384, 2 ** 32 - 129, 2 ** 32 - 128, 2 ** 32 - 1]
    rng = np.random.default_rng(1)
    bits = np.concatenate([np.array(edges, np.uint32),
                           rng.integers(0, 2 ** 32, 1 << 20, np.uint32)])
    got = np.asarray(jax.jit(fastrng.u32_to_f32)(jnp.asarray(bits)))
    np.testing.assert_array_equal(got, bits.astype(np.float32))
