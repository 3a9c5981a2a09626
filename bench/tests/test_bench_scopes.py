"""Device time by the program's named scopes (``bench/scopes.py``) and the
four per-layer metrics that read it (CPU only)."""
import importlib.util
import os

import pytest

from bench import run, scopes
from bench import trace as tr

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA = os.path.join(BENCH, "tests", "data")
NEW = ["fwd_bwd_ms.train", "tile_engine_ms.train", "pulse_operands_ms.train",
       "tile_metrics_ms.train"]
OLD = ["train_mfu", "pulse_kernel_ms.train", "pulse_kernel_roofline.train",
       "device_idle.train", "peak_hbm_gib.train"]


def _reader(name):
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _event(name, body):
    return f"%{name} = {body}"


# two traced steps: seconds of each instruction, as trace.reduce keeps them
OPS = {
    _event("while.3", "(f32[]) while(%tuple.1), body=%body.3"): 9.0,
    _event("fusion.1", "bf16[8,64]{1,0} fusion(%p), kind=kLoop"): 0.010,
    _event("fusion.2", "f32[64,64]{1,0} fusion(%a), kind=kOutput"): 0.020,
    _event("fusion.3", "f32[64,64]{1,0} fusion(%a), kind=kLoop"): 0.030,
    _event("analog_pulse_update.4", 'bf16[2,64,64]{2,1,0} custom-call(%x), '
           'custom_call_target="tpu_custom_call"'): 0.040,
    _event("fusion.5", "f32[2,64,64]{2,1,0} fusion(%y), kind=kLoop"): 0.050,
    _event("multiply_reduce_fusion.6", "f32[2]{0} fusion(%z), kind=kLoop"): 0.060,
    _event("fusion.7", "f32[] fusion(%m), kind=kLoop"): 0.070,
    _event("copy.8", "bf16[8,64]{1,0} copy(%q)"): 0.080,
    _event("fusion.9", "u32[2,64,64]{2,1,0} fusion(%k), kind=kLoop"): 0.090,
}
STEP = "jit(train_step)"
OP_SCOPES = {
    "while.3": f"{STEP}/fwd_bwd/while",
    "fusion.1": f"{STEP}/tile_effective/vmap()/mul",
    "fusion.2": f"{STEP}/fwd_bwd/jvp(loss)/dot_general",
    "fusion.3": f"{STEP}/transpose(jvp(fwd_bwd))/dot_general",
    "analog_pulse_update.4": f"{STEP}/tile_update/while/body/closed_call/"
                             "vmap(pulse_kernel)/cond/branch_0_fun/"
                             "analog_pulse_update/pallas_call",
    "fusion.5": f"{STEP}/tile_update/while/body/vmap(pulse_operands)/mul",
    "multiply_reduce_fusion.6": f"{STEP}/tile_update/while/body/"
                                "vmap(tile_metrics)/reduce_sum",
    "fusion.7": f"{STEP}/tile_update/tile_metrics/reduce_sum;"
                f"{STEP}/digital_opt/mul",
    "fusion.9": f"{STEP}/tile_begin/vmap(jit(_bernoulli))/lt",
}


def _rec(op_scopes=OP_SCOPES):
    rec = {"trace": {"ops_s": dict(OPS), "window_s": 1.0, "busy_s": 0.45},
           "steps": 2}
    if op_scopes is not None:
        rec["op_scopes"] = op_scopes
    return rec


def test_segments_take_transforms_off():
    assert scopes.segments("jit(f)/transpose(jvp(fwd_bwd))/vmap(pulse_kernel)/x") == [
        "f", "fwd_bwd", "pulse_kernel", "x"]
    assert scopes.segments("jit(f)/tile_metrics/a;jit(f)/digital_opt/b") == [
        "f", "tile_metrics", "a"]


UPD = "jit(train_step)/tile_update"
KERNEL_OP = f"{UPD}/vmap(pulse_kernel)/cond/branch_0_fun/analog_pulse_update"
HLO = (
    "HloModule jit_train_step, is_scheduled=true\n\n"
    "FileNames\n1 \"x.py\"\n\n"
    "%fused_computation.1 (param_0: f32[2]) -> f32[] {\n"
    "  %param_0 = f32[2]{0} parameter(0)\n"
    "  ROOT %reduce.1 = f32[] reduce(%param_0, %c), to_apply=%add, "
    'metadata={op_name="tile_metrics/reduce_sum"}\n}\n\n'
    "%fused_computation.2 (param_0.1: f32[2,64,60]) -> f32[2,64,64] {\n"
    "  %param_0.1 = f32[2,64,60]{2,1,0} parameter(0)\n"
    "  %pad.3 = f32[2,64,64]{2,1,0} pad(%param_0.1, %c), padding=0_0x0_0x0_4, "
    f'metadata={{op_name="{UPD}/vmap(pulse_operands)/pad"}}\n'
    "  ROOT %bitcast.4 = f32[2,64,64]{2,1,0:T(8,128)} bitcast(%pad.3), "
    f'metadata={{op_name="{KERNEL_OP}/squeeze"}}\n}}\n\n'
    "ENTRY %main.9 (a: f32[2], b: f32[2,64,60]) -> (f32[], bf16[2,64,64]) {\n"
    "  %a = f32[2]{0} parameter(0)\n"
    "  %b = f32[2,64,60]{2,1,0} parameter(1)\n"
    "  %pad_clamp_fusion = s32[2]{0} fusion(%a), kind=kLoop, "
    'calls=%fused_computation.1, metadata={op_name="gather"}\n'
    "  %multiply_reduce_fusion.6 = f32[]{:T(128)} fusion(%a, "
    "%pad_clamp_fusion), kind=kLoop, "
    "calls=%fused_computation.1, metadata={op_name="
    f'"{UPD}/tile_metrics/reduce_sum" source_file=1 source_line=3}}\n'
    "  %pad_bitcast_fusion = f32[2,64,64]{2,1,0:T(8,128)} fusion(%b), "
    "kind=kLoop, calls=%fused_computation.2, metadata={op_name="
    f'"{KERNEL_OP}/squeeze"}}\n'
    "  %analog_pulse_update.5 = bf16[2,64,64]{2,1,0:T(8,128)(2,1)} "
    "custom-call(%pad_bitcast_fusion), custom_call_target=\"tpu_custom_call\", "
    f'metadata={{op_name="{KERNEL_OP}/pallas_call"}}\n'
    "  %copy-start.7 = (bf16[2,64,64]{2,1,0:T(8,128)(2,1)S(1)}, "
    "bf16[2,64,64]{2,1,0:T(8,128)(2,1)}, u32[]{:S(2)}) "
    "copy-start(%analog_pulse_update.5)\n"
    "  %copy-done.8 = bf16[2,64,64]{2,1,0:T(8,128)(2,1)} copy-done(%copy-start.7)\n"
    "  ROOT %tuple.9 = (f32[], bf16[2,64,64]) tuple(%multiply_reduce_fusion.6, "
    "%copy-done.8)\n}\n")


def test_op_scopes_from_hlo_text():
    names = scopes.op_scopes(HLO)
    # a whole path is taken as it is; a relative one is not a path
    assert names["multiply_reduce_fusion.6"] == f"{UPD}/tile_metrics/reduce_sum"
    assert names["analog_pulse_update.5"] == f"{KERNEL_OP}/pallas_call"
    assert "a" not in names
    # a fusion rooted in a bitcast is named by what it relabels: the pad
    assert names["pad_bitcast_fusion"] == f"{UPD}/vmap(pulse_operands)/pad"
    # inserted copies of the kernel's output: its phase, not the kernel
    assert names["copy-start.7"] == names["copy-done.8"] == UPD
    # an op_name that is not a whole path: the phase of its first user
    assert names["pad_clamp_fusion"] == UPD


def test_scope_seconds_skip_containers_and_take_the_innermost_scope():
    rec = _rec()
    # the while loop (9 s) is left out: its time is its operations'
    assert scopes.scope_seconds(rec, ("fwd_bwd",)) == pytest.approx(0.050)
    # the kernel counts for tile_update unless pulse_kernel is excluded
    upd = 0.040 + 0.050 + 0.060 + 0.070
    assert scopes.scope_seconds(rec, ("tile_update",)) == pytest.approx(upd)
    assert scopes.scope_seconds(rec, ("tile_update",), ("pulse_kernel",)) == (
        pytest.approx(upd - 0.040))
    assert scopes.scope_seconds(rec, ("pulse_kernel",)) == pytest.approx(0.040)
    assert scopes.scope_seconds(rec, ("tile_update",), ("tile_metrics",)) == (
        pytest.approx(0.040 + 0.050))
    # an op with no scope (the copy) is in none
    every = scopes.scope_seconds(rec, scopes.PHASES)
    assert every == pytest.approx(sum(OPS.values()) - 9.0 - 0.080)


def test_the_four_readers():
    rec = _rec()
    want = {"fwd_bwd_ms.train": 0.050,
            "tile_engine_ms.train": 0.010 + 0.050 + 0.060 + 0.070 + 0.090,
            "pulse_operands_ms.train": 0.050,
            "tile_metrics_ms.train": 0.060 + 0.070}
    for name, s in want.items():
        assert _reader(name)(rec) == pytest.approx(1e3 * s / 2), name


@pytest.mark.parametrize("name", NEW)
def test_a_reader_is_silent_without_scopes(name):
    read = _reader(name)
    assert read(_rec(op_scopes=None)) is None
    # a program without the scopes: op_names, none of them a phase
    assert read(_rec({k: "jit(train_step)/mul" for k in OP_SCOPES})) is None
    assert read({"steps": 2}) is None


def test_existing_metrics_read_the_same_with_op_scopes():
    red = tr.reduce(tr.load(os.path.join(DATA, "pulse_kernel.xplane.pb")))
    peaks = {"bf16_flops_per_s": 1.97e14, "hbm_bytes_per_s": 8.19e11}
    base = {"trace": red, "steps": 2, "tokens": 2048, "flops_per_token": 3e9,
            "update_bytes_per_step": 1e6, "peak_bytes": 2**30, "peaks": peaks}
    with_scopes = dict(base, op_scopes=OP_SCOPES)
    for name in OLD:
        assert _reader(name)(with_scopes) == _reader(name)(base), name


@pytest.mark.parametrize("cell", ["train.qwen2-0.5b.b8x128",
                                  "train.minicpm3-4b-l4.b8x128",
                                  "train.qwen2-0.5b.b8x2048"])
def test_every_training_cell_reports_the_phase_metrics(cell):
    s = run.spec(cell)
    names = [m["name"] for m in run.metrics_for(s["benchmark"], cell, True)]
    assert set(NEW) <= set(names)
    assert [m["name"] for m in run.metrics_for(s["benchmark"], cell, False)] == [
        "train_tokens_per_s", "setup_s"]


def test_bench_phases_are_the_programs():
    from repro.core.trainer import TRACE_SCOPES

    assert scopes.PHASES == TRACE_SCOPES[:5]


def test_op_scopes_of_a_compiled_cell_hold_every_phase():
    """The tiny cell's executable, as a traced run reads it: every phase
    names some instruction, and the instructions' names are those of the
    program text."""
    from bench.drivers import train

    from test_bench_faults import tiny_spec

    s = tiny_spec()
    cell = train.Cell(s["config"], s["traffic"], 2**33 + 5, s["reference"])
    text = cell.step.as_text()
    names = scopes.op_scopes(text)
    found = {g for op in names.values() for g in scopes.segments(op)}
    assert set(scopes.PHASES) | {"pulse_operands", "pulse_kernel",
                                 "tile_metrics"} <= found
    assert all(f"%{n} = " in text for n in names)


def test_a_traced_run_reads_the_running_cells_scopes(monkeypatch):
    """A traced run's record carries the scopes of the step it ran, built
    from that executable and not again, and the four readers read every
    phase from them. The CPU has no TPU plane, so the trace's reduction is
    stood in for: each instruction of the step ran 1 ms."""
    import jax

    from bench.drivers import train

    from test_bench_faults import tiny_spec

    red = {"ops_s": {}, "window_s": 1.0, "busy_s": 1.0, "device_ops": [],
           "idle_gaps": []}
    monkeypatch.setattr(tr, "find", lambda d: d)
    monkeypatch.setattr(tr, "load", lambda path: None)
    monkeypatch.setattr(tr, "reduce", lambda t: red)
    built = []
    op_scopes = scopes.op_scopes

    def from_the_step(text):
        names = op_scopes(text)
        built.append(names)
        red["ops_s"].update({f"%{n} = f32[] fusion(%x), kind=kLoop": 1e-3
                             for n in names})
        return names

    monkeypatch.setattr(scopes, "op_scopes", from_the_step)
    line = run.execute(tiny_spec(), seed=2**33 + 5, seconds=0.5, trace=True,
                       devs=jax.devices())
    assert len(built) == 1 and train.scopes is scopes
    found = {g for op in built[0].values() for g in scopes.segments(op)}
    assert set(scopes.PHASES) | {"pulse_operands", "pulse_kernel",
                                 "tile_metrics"} <= found
    rec = {"trace": red, "steps": 2, "op_scopes": built[0]}
    for name in NEW:
        got = line["metrics"][name]["value"]
        assert got > 0 and got == _reader(name)(rec), name
