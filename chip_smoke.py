"""Smoke test of the main path on a TPU: E-RIDER training, then serving.

    python chip_smoke.py              # one chip: all phases below
    python chip_smoke.py --chips 4    # four chips: the sharded-training phase

One chip, at the published width of qwen2-0.5b (24 layers, d_model 896,
d_ff 4864, vocab 151936; random weights from fixed seeds):

1. the device as JAX reports it;
2. the pulse-update kernel (Pallas, compiled by Mosaic) against the jnp
   reference on one tile stack of the MLP, with the same random operands:
   they must agree within one bf16 ulp, and the compiled program must hold
   the kernel (``tpu_custom_call``);
   then the seeded kernel, which draws the device parameters and the noise
   itself, against the operand path that draws them in XLA from the same
   seeds (the benchmark's P device): they must agree within one bf16 ulp;
   and whether the chip's XLA uint32 -> f32 conversion equals the
   two-halves one the kernel uses (``fastrng.u32_to_f32``);
3. ``repro.launch.train.main`` for 5 E-RIDER steps at batch 8 x 128
   tokens: compile seconds, every loss (all finite), seconds per step, peak
   device memory, and the kernel in the compiled train step;
4. ``repro.launch.serve.main`` on the checkpoint that run wrote, 4 greedy
   requests of 32 prompt + 16 new tokens, with the continuous and the fixed
   engine: every request gets its 16 tokens; tokens/s and the share of
   tokens on which the two engines agree are printed.

With ``--chips 4`` it runs only ``train.main`` on a (data 2, model 2) mesh
and the same steps on one chip of that host, in this process: the losses
must agree per step within ``LOSS_RTOL`` and the tile state must be spread
over the chips (the fullest device holds at most half of it).

The last line of standard output is one JSON object naming the device. The
script refuses to run, and prints no result, where JAX finds no TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.device import DeviceConfig, sample_device  # noqa: E402
from repro.kernels import fastrng, ops  # noqa: E402
from repro.kernels.analog_update import SEEDED_KERNEL_NAME  # noqa: E402
from repro.launch import compile_cache, serve, train  # noqa: E402

ARCH = "qwen2-0.5b"
STEPS = 5
# one qwen2-0.5b MLP input projection: 24 scanned layers of (896, 4864)
KERNEL_SHAPE = (24, 896, 4864)
# sharded vs one-chip loss, relative: about one bf16 ulp of the loss
# (2**-7 = 0.0078), rounded up. Sharding reorders the bf16 matmul and
# gradient reductions; that may move a stochastic pulse-rounding decision,
# never the trajectory.
LOSS_RTOL = 1e-2
BF16_MANTISSA_BITS = 7
# the P device of the benchmark's qwen2-0.5b tile configuration: every
# device draw (slope, asymmetry, SP target) is made
P_DEVICE = DeviceConfig(kind="softbounds", dw_min=1e-4, sigma_d2d=0.1,
                        sigma_pm=0.3, sigma_c2c=0.05, ref_mean=0.1,
                        ref_std=0.1)


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def device_info() -> dict:
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def bf16_ulps(a, b) -> np.ndarray:
    """|a - b| in units of the bf16 spacing at the larger magnitude."""
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    mag = np.maximum(np.maximum(np.abs(a), np.abs(b)),
                     np.finfo(np.float32).tiny)
    ulp = np.exp2(np.floor(np.log2(mag)) - BF16_MANTISSA_BITS)
    return np.abs(a - b) / ulp


def kernel_parity(shape=KERNEL_SHAPE) -> dict:
    """One tile stack through ``ops.analog_update`` on the Pallas and the
    reference path, same ``(ubits, zeta)``."""
    ks = jax.random.split(jax.random.PRNGKey(11), 6)
    w = jax.random.uniform(ks[0], shape, jnp.float32, -0.5, 0.5
                           ).astype(jnp.bfloat16)
    dw = 3e-4 * jax.random.normal(ks[1], shape)
    gamma = jnp.exp(0.1 * jax.random.normal(ks[2], shape))
    rho = 0.3 * jnp.tanh(jax.random.normal(ks[3], shape))
    noise = (jax.random.bits(ks[4], shape, jnp.uint32),
             jax.random.normal(ks[5], shape))
    kw = dict(dw_min=1e-4, tau_min=1.0, tau_max=1.0, sigma_c2c=0.05)

    def update(w, dw, gamma, rho, noise):
        return ops.analog_update(w, dw, gamma, rho, None, noise=noise, **kw)

    args = (w, dw, gamma, rho, noise)
    outs, text = {}, ""
    for name in ("pallas", "ref"):
        ops.set_backend(name)
        try:
            fn = jax.jit(update).lower(*args).compile()
        finally:
            ops.set_backend(None)
        if name == "pallas":
            text = fn.as_text()
        outs[name] = np.asarray(fn(*args).astype(jnp.float32))
    ulps = bf16_ulps(outs["pallas"], outs["ref"])
    return {"shape": list(shape), "max_ulps": float(ulps.max()),
            "differing": int(np.count_nonzero(ulps)),
            "moved": int(np.count_nonzero(outs["ref"] != np.asarray(
                w.astype(jnp.float32)))),
            "kernel_in_hlo": "tpu_custom_call" in text}


def seeded_parity(shape=KERNEL_SHAPE, device=P_DEVICE) -> dict:
    """One tile stack through the seeded kernel and through the operand
    path (``sample_device(method='hash')`` and fastrng noise in XLA, then
    the operand kernel), same device and noise seeds."""
    ks = jax.random.split(jax.random.PRNGKey(12), 4)
    w = jax.random.uniform(ks[0], shape, jnp.float32, -0.5, 0.5
                           ).astype(jnp.bfloat16)
    dw = 3e-4 * jax.random.normal(ks[1], shape)
    dev_seed = jax.random.key_data(ks[2]).astype(jnp.uint32)
    kw = dict(dw_min=device.dw_min, tau_min=device.tau_min,
              tau_max=device.tau_max, sigma_c2c=device.sigma_c2c)

    def seeded(w, dw, dev_seed, key):
        return ops.analog_update_seeded(w, dw, dev_seed, key, device=device)

    def operands(w, dw, dev_seed, key):
        dp = sample_device(jax.random.wrap_key_data(dev_seed), w.shape,
                           device, method="hash")
        return ops.analog_update(w, dw, dp["gamma"], dp["rho"], key,
                                 rng="hash", **kw)

    args = (w, dw, dev_seed, ks[3])
    outs, texts = {}, {}
    ops.set_backend("pallas")
    try:
        for name, fn in (("seeded", seeded), ("operands", operands)):
            c = jax.jit(fn).lower(*args).compile()
            texts[name] = c.as_text()
            outs[name] = np.asarray(c(*args).astype(jnp.float32))
    finally:
        ops.set_backend(None)
    ulps = bf16_ulps(outs["seeded"], outs["operands"])
    return {"shape": list(shape), "max_ulps": float(ulps.max()),
            "differing": int(np.count_nonzero(ulps)),
            "moved": int(np.count_nonzero(outs["operands"] != np.asarray(
                w.astype(jnp.float32)))),
            "kernel_in_hlo": ("tpu_custom_call" in texts["seeded"]
                              and SEEDED_KERNEL_NAME in texts["seeded"])}


def u32_conversion(n: int = 1 << 22) -> dict:
    """XLA's uint32 -> f32 conversion against ``fastrng.u32_to_f32`` on the
    lattice edges and ``n`` hashed values."""
    edges = jnp.array([0, 1, (1 << 24) + 1, (1 << 31) + 129, 0xFFFFFF7F,
                       0xFFFFFF80, 0xFFFFFFFF], jnp.uint32)
    bits = jnp.concatenate(
        [edges, fastrng.hash_bits(jnp.array([5, 7], jnp.uint32), (n,), 3)])
    xla, halves = jax.jit(lambda b: (b.astype(jnp.float32),
                                     fastrng.u32_to_f32(b)))(bits)
    return {"values": int(bits.size),
            "differing": int(np.count_nonzero(np.asarray(xla)
                                              != np.asarray(halves)))}


def peak_bytes():
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def train_phase(ckpt_dir: str, smoke: bool = False, steps: int = STEPS
                ) -> dict:
    argv = ["--arch", ARCH, "--algorithm", "erider", "--steps", str(steps),
            "--log-every", "1", "--ckpt-dir", ckpt_dir]
    if smoke:
        argv += ["--smoke", "--batch", "2", "--seq", "16"]
    out = train.main(argv)
    losses = [h["loss"] for h in out["history"]]
    return {"compile_s": out["compile_s"], "losses": losses,
            "step_s": [h["step_s"] for h in out["history"][1:]],
            "finite": bool(np.all(np.isfinite(losses))),
            "kernel_in_step": "tpu_custom_call" in out["step"].as_text(),
            "tile_bytes": out["tile_bytes"], "peak_bytes": peak_bytes()}


def serve_phase(ckpt_dir: str, workdir: str, smoke: bool = False,
                requests: int = 4, prompt_len: int = 32,
                gen: int = 16) -> dict:
    res = {}
    tokens = {}
    for engine in ("continuous", "fixed"):
        dump = os.path.join(workdir, f"tokens_{engine}.json")
        argv = ["--arch", ARCH, "--ckpt-dir", ckpt_dir, "--algorithm",
                "erider", "--engine", engine, "--requests", str(requests),
                "--prompt-len", str(prompt_len), "--gen", str(gen),
                "--dump-tokens", dump]
        if smoke:
            argv.append("--smoke")
        out = serve.main(argv)
        with open(dump) as f:
            tokens[engine] = json.load(f)
        res[engine] = {
            "tokens_per_s": out["summary"]["tokens_per_s"],
            "generated_tokens": out["summary"]["generated_tokens"],
            "lengths": sorted(len(t) for t in tokens[engine].values())}
    cont, fixed = tokens["continuous"], tokens["fixed"]
    same = sum(int(a == b) for r in cont
               for a, b in zip(cont[r], fixed.get(r, [])))
    total = sum(len(t) for t in cont.values())
    res["requests"] = requests
    res["gen"] = gen
    res["agreement"] = same / total if total else 0.0
    res["complete"] = all(
        res[e]["lengths"] == [gen] * requests for e in ("continuous", "fixed")
    ) and set(cont) == set(fixed)
    return res


def sharded_phase(smoke: bool = False, steps: int = STEPS) -> dict:
    """The same E-RIDER steps on one chip and on a (data 2, model 2) mesh."""
    extra = ("--smoke", "--batch", "4", "--seq", "16") if smoke else ()
    argv = ["--arch", ARCH, "--algorithm", "erider", "--steps", str(steps),
            "--log-every", "1", *extra]
    one = train.main(argv)
    four = train.main(argv + ["--data-parallel", "2", "--model-parallel", "2"])
    l1 = np.array([h["loss"] for h in one["history"]])
    l4 = np.array([h["loss"] for h in four["history"]])
    rel = np.abs(l4 - l1) / np.maximum(np.abs(l1), 1e-12)
    tb = four["tile_bytes"]
    return {"losses_1": l1.tolist(), "losses_4": l4.tolist(),
            "max_rel_diff": float(rel.max()),
            "losses_agree": bool(rel.max() <= LOSS_RTOL
                                 and np.all(np.isfinite(l4))),
            "compile_s_1": one["compile_s"], "compile_s_4": four["compile_s"],
            "step_s_1": [h["step_s"] for h in one["history"][1:]],
            "step_s_4": [h["step_s"] for h in four["history"][1:]],
            "tile_bytes_1": one["tile_bytes"], "tile_bytes_4": tb,
            "sharded": tb["per_device"] <= tb["total"] / 2 + 4096}


def check(ok: bool, what: str) -> None:
    if not ok:
        log(f"FAILED: {what}")
        sys.exit(1)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    dev = device_info()
    log(f"platform={dev['platform']} device_kind={dev['kind']} "
        f"devices={dev['count']}")
    if dev["platform"] != "tpu":
        log("no TPU found; this script measures nothing elsewhere")
        sys.exit(2)
    if dev["count"] < args.chips:
        log(f"--chips {args.chips} needs {args.chips} devices")
        sys.exit(2)
    log(f"compile cache: {compile_cache.enable()}")

    if args.chips == 4:
        r = sharded_phase()
        log(f"one chip losses:  {r['losses_1']}")
        log(f"2x2 mesh losses:  {r['losses_4']}")
        log(f"max relative loss difference {r['max_rel_diff']!r} "
            f"(tolerance {LOSS_RTOL})")
        log(f"compile_s one={r['compile_s_1']!r} mesh={r['compile_s_4']!r}")
        log(f"step_s one={r['step_s_1']} mesh={r['step_s_4']}")
        log(f"tile bytes one chip {r['tile_bytes_1']}, "
            f"2x2 mesh {r['tile_bytes_4']}")
        check(r["losses_agree"], "sharded losses disagree with one chip")
        check(r["sharded"], "tile state is not spread over the chips")
        print(json.dumps({"ok": True, "device": dev}))
        return

    t0 = time.perf_counter()
    k = kernel_parity()
    log(f"kernel parity {k['shape']} bf16: max {k['max_ulps']!r} bf16 ulp, "
        f"{k['differing']} elements differ, {k['moved']} moved, "
        f"tpu_custom_call={k['kernel_in_hlo']} "
        f"({time.perf_counter() - t0:.1f}s)")
    check(k["max_ulps"] <= 1.0, "Pallas and reference differ by > 1 ulp")
    check(k["moved"] > 0, "the update moved no weight")
    check(k["kernel_in_hlo"], "no tpu_custom_call in the kernel program")
    t0 = time.perf_counter()
    sp = seeded_parity()
    log(f"seeded kernel parity {sp['shape']} bf16: max {sp['max_ulps']!r} "
        f"bf16 ulp, {sp['differing']} elements differ, {sp['moved']} moved, "
        f"{SEEDED_KERNEL_NAME} kernel={sp['kernel_in_hlo']} "
        f"({time.perf_counter() - t0:.1f}s)")
    u = u32_conversion()
    log(f"uint32 -> f32: XLA and the two-halves conversion differ on "
        f"{u['differing']} of {u['values']} values")
    check(sp["max_ulps"] <= 1.0, "seeded kernel and operand path differ by > 1 ulp")
    check(sp["moved"] > 0, "the seeded update moved no weight")
    check(sp["kernel_in_hlo"], f"no {SEEDED_KERNEL_NAME} kernel")

    work = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        ckpt_dir = os.path.join(work, "ckpt")
        t = train_phase(ckpt_dir)
        log(f"train compile_s={t['compile_s']!r}")
        log(f"train losses={t['losses']}")
        log(f"train step_s after the first={t['step_s']}")
        log(f"train tile bytes={t['tile_bytes']} "
            f"peak_bytes_in_use={t['peak_bytes']}")
        log(f"train step holds tpu_custom_call={t['kernel_in_step']}")
        check(len(t["losses"]) == STEPS and t["finite"],
              "training losses missing or not finite")
        check(t["kernel_in_step"], "no tpu_custom_call in the train step")

        s = serve_phase(ckpt_dir, work)
        for e in ("continuous", "fixed"):
            log(f"serve {e}: {s[e]['generated_tokens']} tokens, "
                f"{s[e]['tokens_per_s']!r} tok/s, lengths {s[e]['lengths']}")
        log(f"serve agreement continuous vs fixed: {s['agreement']!r}")
        log(f"peak_bytes_in_use={peak_bytes()}")
        check(s["complete"], "a request did not get all its tokens")
    finally:
        shutil.rmtree(work, ignore_errors=True)

    print(json.dumps({"ok": True, "device": dev}))


if __name__ == "__main__":
    main()
