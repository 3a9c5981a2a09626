"""The train step's phases are named in the compiled program.

``jax.named_scope`` writes each phase of ``AnalogTrainer.train_step`` into
the op_name metadata of the optimized HLO (``TRACE_SCOPES``), which is
where the benchmark attributes device time from. The scopes are metadata
only: with the metadata stripped, the program equals the one traced with
every scope turned into a null context.
"""
import contextlib
import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import pytest

from bench.scopes import segments
from repro import api
from repro.configs import get_config
from repro.core.digital_opt import DigitalOptConfig
from repro.core.trainer import TRACE_SCOPES, AnalogTrainer, TrainerConfig
from repro.launch.train import make_tile_cfg
from repro.models.lm import LM

BACKENDS = ["vmap", "fused"]


def _step_text(backend: str) -> str:
    """Optimized HLO of the grouped E-RIDER step at smoke size (2 layers of
    width 64) with the full-width tile configuration (hash noise, device
    parameters regenerated from seeds, bf16 state, every diagnostic)."""
    model = LM(get_config("qwen2-0.5b", smoke=True))
    plan = api.plan_from_spec("erider", lambda a: dataclasses.replace(
        make_tile_cfg(a, smoke=False), update_backend=backend))
    trainer = AnalogTrainer(model.loss, TrainerConfig(
        digital=DigitalOptConfig(kind="sgdm", clip_norm=1.0), microbatch=2),
        plan=plan)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    state = jax.eval_shape(trainer.init, jax.random.PRNGKey(1), params)
    batch = {k: jax.ShapeDtypeStruct((4, 16), jnp.int32)
             for k in ("tokens", "labels")}
    return trainer.jit_step().lower(state, batch).compile().as_text()


@functools.lru_cache(maxsize=None)
def _scoped(backend):
    return _step_text(backend)


def _strip(text):
    """The program without metadata: no op_names, no source locations."""
    text = re.sub(r"\n(FileNames|FunctionNames|FileLocations|StackFrames)\n"
                  r".*?(?=\n\n)", "", text, flags=re.S)
    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    assert "op_name" not in text and "ENTRY" in text
    assert "FunctionNames" not in text and "StackFrames" not in text
    return text


@pytest.mark.parametrize("backend", BACKENDS)
def test_every_phase_is_named_in_the_compiled_step(backend):
    # whole paths only (a reduction's own computation holds a relative
    # one); an instruction merged from several ops joins their paths by ";"
    names = [segments(p)
             for n in re.findall(r'op_name="([^"]*)"', _scoped(backend))
             for p in n.split(";") if p.startswith("jit(")]
    seen = {s for segs in names for s in segs}
    assert set(TRACE_SCOPES) <= seen, set(TRACE_SCOPES) - seen
    for inner in ("pulse_operands", "pulse_kernel", "tile_metrics"):
        placed = [segs for segs in names if inner in segs]
        assert placed and all(
            "tile_update" in segs[:segs.index(inner)] for segs in placed), inner
    # the five phases never nest in one another
    phases = TRACE_SCOPES[:5]
    assert all(sum(s in segs for s in phases) <= 1 for segs in names)


@pytest.mark.parametrize("backend", BACKENDS)
def test_scopes_add_no_operation(backend, monkeypatch):
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = _step_text(backend)
    assert not set(TRACE_SCOPES) & {
        s for n in re.findall(r'op_name="([^"]*)"', bare) for s in segments(n)}
    assert _strip(bare) == _strip(_scoped(backend))
