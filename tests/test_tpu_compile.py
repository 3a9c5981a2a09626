"""Compile the pulse-update kernel for a described TPU v5e (no chip needed).

The TPU compiler is installed with jaxlib, and it compiles for a chip that
is described rather than attached. These tests compile the main path's
kernel at qwen2-0.5b member shapes in bf16, in the three forms the trainer
uses, and check that the compiled program holds the Mosaic kernel
(``tpu_custom_call``) and not the interpreter. Nothing runs.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.analog_update import analog_update_pallas

# qwen2-0.5b: 24 scanned layers, d_model 896, d_ff 4864, kv width 128
MLP_IN = (24, 896, 4864)
KW = dict(dw_min=1e-4, tau_min=1.0, tau_max=1.0, sigma_c2c=0.05)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a described chip's program is written to the persistent cache but
    # cannot be read back without a chip; keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def pallas_backend():
    # the described chip is not the default backend: steer ops by hand
    ops.set_backend("pallas")
    try:
        yield
    finally:
        ops.set_backend(None)


def _operands(shape, sharding):
    def sds(dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    return (sds(jnp.bfloat16), sds(jnp.float32), sds(jnp.float32),
            sds(jnp.float32), sds(jnp.uint32), sds(jnp.float32))


def _compiled_text(fn, args):
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("shape", [(896, 4864), (4864, 896), MLP_IN])
def test_analog_update_kernel_compiles_for_v5e(one_chip, shape):
    """The kernel alone, 2-D tile and 3-D layer stack, at blocks that
    divide the member shape (what ``ops`` picks)."""
    bm, bn = ops._update_block(*shape[-2:])
    assert shape[-2] % bm == 0 and shape[-1] % bn == 0, (bm, bn)
    text = _compiled_text(
        lambda *a: analog_update_pallas(*a, block=(bm, bn), **KW),
        _operands(shape, one_chip))
    assert "tpu_custom_call" in text


def test_vmapped_ops_update_compiles_for_v5e(one_chip, pallas_backend):
    """``jax.vmap(ops.analog_update)`` over a stack of two MLP members, as
    the grouped engine runs it, with the hash RNG the full-width config
    uses."""
    stack = (2,) + MLP_IN
    w, dw, gamma, rho = (jax.ShapeDtypeStruct(stack, dt, sharding=one_chip)
                         for dt in (jnp.bfloat16, jnp.float32, jnp.float32,
                                    jnp.float32))
    keys = jax.ShapeDtypeStruct((2, 2), jnp.uint32, sharding=one_chip)

    def update(w, dw, gamma, rho, kr):
        return jax.vmap(lambda *a: ops.analog_update(
            *a[:4], jax.random.wrap_key_data(a[4]), rng="hash", **KW))(
                w, dw, gamma, rho, kr)

    text = _compiled_text(update, (w, dw, gamma, rho, keys))
    assert "tpu_custom_call" in text


def test_kernel_is_named_in_the_compiled_program(one_chip, pallas_backend):
    """``ops.analog_update`` on a v5e: the Mosaic kernel is the instruction
    named ``analog_pulse_update``, its op_name under the ``pulse_kernel``
    scope, and the noise it reads under ``pulse_operands``."""
    from repro.kernels.analog_update import KERNEL_NAME

    shape = (2, 64, 256)
    w, dw, gamma, rho = (jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
                         for dt in (jnp.bfloat16, jnp.float32, jnp.float32,
                                    jnp.float32))
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)

    def update(w, dw, gamma, rho, kr):
        return ops.analog_update(w, dw, gamma, rho,
                                 jax.random.wrap_key_data(kr), rng="hash", **KW)

    text = _compiled_text(update, (w, dw, gamma, rho, key))
    kernels = [ln for ln in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(kernels) == 1
    assert re.match(rf"\s*(ROOT )?%{KERNEL_NAME}\.\d+ = ", kernels[0])
    assert re.search(rf'op_name="[^"]*/pulse_kernel/[^"]*/{KERNEL_NAME}/',
                     kernels[0])
    assert "/pulse_operands/" in text
