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


# minicpm3-4b: the untied 2560 x 73448 head, which no lane block divides,
# and the 32-wide decoupled-RoPE key projection, narrower than a lane tile
HEAD = (2560, 73448)
ROPE_K = (4, 2560, 32)
P_DEV = dict(kind="softbounds", dw_min=1e-4, sigma_d2d=0.1, sigma_pm=0.3,
             sigma_c2c=0.05, ref_mean=0.1, ref_std=0.1)


def _seeded_text(shape, one_chip, vmapped=False):
    """Optimized HLO of ``ops.analog_update_seeded`` at ``shape`` (under
    ``jax.vmap`` over the leading axis when ``vmapped``)."""
    from repro.core.device import DeviceConfig

    dev = DeviceConfig(**P_DEV)
    w, dw = (jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
             for dt in (jnp.bfloat16, jnp.float32))
    n_seeds = (shape[0], 2) if vmapped else (2,)
    seed, key = (jax.ShapeDtypeStruct(n_seeds, jnp.uint32, sharding=one_chip)
                 for _ in range(2))

    def update(w, dw, seed, kr):
        return ops.analog_update_seeded(w, dw, seed,
                                        jax.random.wrap_key_data(kr),
                                        device=dev)

    fn = jax.vmap(update) if vmapped else update
    return _compiled_text(fn, (w, dw, seed, key))


@pytest.mark.parametrize("shape,vmapped", [
    ((896, 4864), False), ((4864, 896), False), (MLP_IN, False),
    (MLP_IN, True), (HEAD, False), ((1,) + HEAD, True), (ROPE_K, True)])
def test_seeded_kernel_compiles_for_v5e(one_chip, pallas_backend, shape,
                                        vmapped):
    """The seeded kernel in its 2-D, 3-D and vmapped forms at qwen2-0.5b's
    member shapes and minicpm3-4b's ragged head and narrow RoPE key
    projection: one Mosaic kernel named
    ``analog_pulse_update_seeded`` that reads w, dw and the seeds only,
    with no padding of either."""
    from repro.kernels.analog_update import SEEDED_KERNEL_NAME

    text = _seeded_text(shape, one_chip, vmapped)
    calls = _kernel_calls(text)
    assert [name for name, _ in calls] == [SEEDED_KERNEL_NAME]
    assert sorted(dt for dt, _ in calls[0][1]) == ["bf16", "f32", "u32"]
    assert not re.search(r"= (bf16|f32)\[[^\]]*\]\S* pad\(", text)


def _kernel_calls(text):
    """[(kernel name, [(operand dtype, operand dims)])] of every Mosaic
    kernel in an optimized HLO text."""
    out = []
    for ln in text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in ln:
            continue
        name = re.match(r"\s*(?:ROOT )?%([\w-]+)\.\d+ = ", ln).group(1)
        args = re.search(r"operand_layout_constraints=\{(.*?)\}, \w+=", ln)
        out.append((name, re.findall(r"(\w+)\[([\d,]*)\]", args.group(1))))
    return out


def _train_step_text(one_chip, store_device, arch="qwen2-0.5b"):
    """Optimized HLO of the grouped E-RIDER train step of ``arch`` at full
    width and 2 layers, with the benchmark's tile configuration, for one
    described v5e."""
    import dataclasses

    from repro import api
    from repro.configs import get_config
    from repro.core.digital_opt import DigitalOptConfig
    from repro.core.trainer import AnalogTrainer, TrainerConfig
    from repro.launch.train import make_tile_cfg
    from repro.models.lm import LM

    model = LM(dataclasses.replace(get_config(arch), n_layers=2,
                                   n_periods=2))
    plan = api.plan_from_spec("erider", lambda a: dataclasses.replace(
        make_tile_cfg(a, smoke=False), store_device=store_device))
    trainer = AnalogTrainer(model.loss, TrainerConfig(
        digital=DigitalOptConfig(kind="sgdm", clip_norm=1.0)), plan=plan)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    state = jax.eval_shape(trainer.init, jax.random.PRNGKey(1), params)
    on_chip = lambda t: jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip), t)
    batch = {k: jax.ShapeDtypeStruct((2, 128), jnp.int32)
             for k in ("tokens", "labels")}
    return trainer.jit_step().lower(on_chip(state), on_chip(batch)).compile(
    ).as_text()


@pytest.mark.parametrize("arch", ["qwen2-0.5b", "minicpm3-4b"])
def test_train_step_draws_devices_and_noise_in_the_kernel(one_chip,
                                                           pallas_backend,
                                                           arch):
    """With seeded devices (the benchmark's ``store_device: false``, hash
    RNG) every pulse update of the step is the seeded kernel, and each
    reads w and its f32 increment only: no weight-sized f32 or u32 device
    parameter or noise array is written for it."""
    from repro.kernels.analog_update import SEEDED_KERNEL_NAME

    calls = _kernel_calls(_train_step_text(one_chip, False, arch))
    assert calls and {name for name, _ in calls} == {SEEDED_KERNEL_NAME}
    for _, operands in calls:
        (seeds, sdims), (w, wdims), (dw, dwdims) = operands
        assert (seeds, w, dw) == ("u32", "bf16", "f32"), operands
        assert sdims.endswith("1,4") and wdims == dwdims, operands


def test_train_step_with_stored_devices_keeps_the_operand_kernel(
        one_chip, pallas_backend):
    """Stored device arrays are not drawn from a seed: the operand kernel
    ``analog_pulse_update`` still runs the step's pulse updates."""
    from repro.kernels.analog_update import KERNEL_NAME

    calls = _kernel_calls(_train_step_text(one_chip, store_device=True))
    assert calls and {name for name, _ in calls} == {KERNEL_NAME}
