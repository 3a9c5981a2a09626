"""Training driver CLI: analog LM training with checkpoint/restart, fault
tolerance and the full data pipeline.

With ``--smoke`` it runs reduced configs end to end on a CPU (see
examples/lm_analog_training.py); without it, the published widths, as
``chip_smoke.py`` runs qwen2-0.5b on one TPU v5e. ``--data-parallel`` /
``--model-parallel`` lay a mesh over the host's devices: state and batches
are placed by the sharding rules and the tile updates run as a manual map
over the tile stacks (ZeRO over the stack axis).

``--algorithm`` takes either a single algorithm name (one policy on every
analog leaf) or a comma-separated mixed plan of ``pattern=algorithm`` rules
matched in order (globs, ``re:`` regexes, or bare substrings;
``digital`` is a valid algorithm):

  --algorithm erider
  --algorithm "attn=rider,**=erider"
  --algorithm "re:mlp/(wi|wg)$=ttv2,wo=rider,**=erider"

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b --smoke \
      --steps 100 --algorithm erider --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import api
from repro.configs import ARCHS, get_config
from repro.core.device import DeviceConfig
from repro.core.digital_opt import DigitalOptConfig, ScheduleConfig
from repro.core.tile import TileConfig
from repro.core.trainer import AnalogTrainer, TrainerConfig
from repro.checkpoint import ckpt
from repro.data import BigramLM, Prefetcher
from repro.distributed import sharding
from repro.distributed.fault import PreemptionHandler, StragglerMonitor
from repro.launch import compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models.common import set_shard_rules
from repro.models.lm import LM


def make_tile_cfg(algorithm: str, smoke: bool) -> TileConfig:
    # device_w carries PCM-grade lifetime coefficients (drift_nu ~ 0.06,
    # cf. the pcm_gst preset): checkpoints trained by this driver can be
    # aged and drift-compensated by repro.lifetime / bench_lifetime.
    dev = DeviceConfig(kind="softbounds", dw_min=2e-4 if smoke else 1e-4,
                       sigma_d2d=0.1, sigma_pm=0.3, sigma_c2c=0.05,
                       drift_nu=0.06, drift_nu_std=0.02, drift_t0=20.0,
                       prog_noise=0.01, prog_noise_slope=0.07, prog_rounds=3,
                       read_noise=0.005)
    dev_p = DeviceConfig(kind="softbounds", dw_min=2e-4 if smoke else 1e-4,
                         sigma_d2d=0.1, sigma_pm=0.3, sigma_c2c=0.05,
                         ref_mean=0.1, ref_std=0.1)
    return TileConfig(
        algorithm=algorithm, device_p=dev_p, device_w=dev,
        state_dtype=jnp.float32 if smoke else jnp.bfloat16,
        store_device=smoke, rng="threefry" if smoke else "hash",
        lr_p=0.5, lr_w=0.05, gamma=0.1, eta=0.5, chopper_p=0.05,
    )


def make_plan(algorithm: str, smoke: bool) -> api.AnalogPlan:
    """CLI ``--algorithm`` value -> AnalogPlan (see api.plan_from_spec)."""
    return api.plan_from_spec(algorithm, lambda a: make_tile_cfg(a, smoke))


def ckpt_extra(trainer, state) -> dict:
    """Extra manifest keys for ``ckpt.save``: the GDC t0 signatures of the
    effective analog weights (``repro.lifetime.gdc``). Serve-time Global
    Drift Compensation divides these programming-time references by the
    aged signatures to recover the per-matrix drift scale. Computed with
    the exact jitted function the serve side re-runs, over the exact
    merged tree it rebuilds, so an unaged restore reproduces every
    signature bit-for-bit (the GDC t0 token-identity contract)."""
    from repro.core.trainer import merge_effective
    from repro.lifetime import gdc

    tiles = state["tiles"]
    if not hasattr(tiles, "index"):
        return {}
    paths = [p for g, ps in tiles.index
             for p in ps
             if not (tiles.policy(g) is not None and tiles.policy(g).is_digital)]
    if not paths:
        return {}
    eff = merge_effective(state["params"], tiles, trainer.cfg.tile)
    sig_fn = jax.jit(lambda t: gdc.signature_tree(t, tuple(sorted(paths))))
    return {"gdc_signatures": {p: float(v)
                               for p, v in sig_fn(eff).items()}}


def _tile_bytes(tiles) -> dict:
    """Tile-state bytes in total and on the fullest device."""
    leaves = jax.tree.leaves(tiles)
    per_dev: dict = {}
    for leaf in leaves:
        for shard in leaf.addressable_shards:
            per_dev[shard.device] = per_dev.get(shard.device, 0) \
                + shard.data.nbytes
    return {"total": sum(l.nbytes for l in leaves),
            "per_device": max(per_dev.values())}


def main(argv=None) -> dict:
    """Train; returns ``{"compile_s", "history", "step", "tile_bytes"}``:
    the seconds the step took to compile, the logged steps' metrics (with
    ``step_s``, wall seconds per step since the previous log line), the
    compiled step, and the tile-state bytes in total and on the fullest
    device."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), default="qwen2-0.5b")
    ap.add_argument("--smoke", action="store_true", help="reduced config (CPU)")
    ap.add_argument("--algorithm", default="erider")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=0.1)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--data-parallel", type=int, default=1)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--metrics-out", default="")
    args = ap.parse_args(argv)

    compile_cache.enable()
    cfg = get_config(args.arch, smoke=args.smoke)
    model = LM(cfg)
    mesh = make_host_mesh(args.data_parallel, args.model_parallel)
    set_shard_rules(sharding.logical_rules(mesh))
    sharded = mesh.size > 1

    plan = make_plan(args.algorithm, args.smoke)
    tcfg = TrainerConfig(
        digital=DigitalOptConfig(kind="sgdm", clip_norm=1.0),
        schedule=ScheduleConfig(kind="cosine", base_lr=args.lr,
                                total_steps=args.steps, warmup_steps=min(20, args.steps // 5)),
    )
    trainer = AnalogTrainer(model.loss, tcfg, plan=plan,
                            mesh=mesh if sharded else None)

    key = jax.random.PRNGKey(0)
    params = model.init(key)
    print(f"[train] {trainer.describe_plan(params)}", flush=True)
    state = trainer.init(jax.random.PRNGKey(1), params)
    del params
    # on a mesh, state and batches are placed with the sharding rules and
    # the step is compiled for exactly that layout (state round-trips)
    state_sh = sharding.state_shardings(state, mesh) if sharded else None
    if sharded:
        state = jax.device_put(state, state_sh)

    start_step = 0
    if args.ckpt_dir and ckpt.latest_step(args.ckpt_dir) is not None:
        state = ckpt.restore(state, args.ckpt_dir, shardings=state_sh)
        start_step = int(np.asarray(state["step"]))
        print(f"[train] restored checkpoint at step {start_step}")

    data = BigramLM(vocab=cfg.vocab, seed=7)
    batch_sh = sharding.batch_shardings(
        data.batch(0, args.batch, args.seq), mesh) if sharded else None
    prefetch = Prefetcher(
        lambda s: data.batch(s, args.batch, args.seq), start_step=start_step)

    jit_kwargs = ({"in_shardings": (state_sh, batch_sh),
                   "out_shardings": (state_sh, None)} if sharded else {})
    it = iter(prefetch)
    first = jax.device_put(next(it), batch_sh)
    t0 = time.perf_counter()
    step_fn = trainer.jit_step(**jit_kwargs).lower(state, first).compile()
    compile_s = time.perf_counter() - t0
    print(f"[train] compiled step in {compile_s:.1f}s", flush=True)

    preempt = PreemptionHandler()
    monitor = StragglerMonitor()
    history = []
    pending = None
    t_log, s_log = time.perf_counter(), start_step
    batch = first
    for step in range(start_step, args.steps):
        if step > start_step:
            batch = jax.device_put(next(it), batch_sh)
        # a profiler step: on the device trace's clock, xprof's step view
        with jax.profiler.StepTraceAnnotation("train", step_num=step):
            state, metrics = step_fn(state, batch)
        if step % args.log_every == 0 or step == args.steps - 1:
            m = {k: float(v) for k, v in metrics.items()}  # waits for the step
            now = time.perf_counter()
            m["step"] = step
            m["step_s"] = (now - t_log) / (step + 1 - s_log)
            # the dispatch returns before the device finishes: the monitor
            # is fed the synced time per step since the previous log
            m["straggler"] = monitor.record(m["step_s"])
            t_log, s_log = now, step + 1
            history.append(m)
            print(f"[train] step={step} loss={m['loss']:.4f} "
                  f"acc={m.get('accuracy', 0):.3f} "
                  f"sp_err={m.get('tile/sp_err', -1):.4f} "
                  f"step_s={m['step_s']:.3f} ema_s={monitor.ema:.3f}",
                  flush=True)
        if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
            pending = ckpt.save(state, args.ckpt_dir, step + 1,
                                asynchronous=True,
                                extra=ckpt_extra(trainer, state))
        if preempt.should_stop:
            print("[train] preemption signal — checkpointing and exiting")
            if args.ckpt_dir:
                ckpt.save(state, args.ckpt_dir, step + 1,
                          extra=ckpt_extra(trainer, state))
            break
    prefetch.close()
    if args.ckpt_dir:
        if pending is not None:
            pending.join(timeout=60)
        ckpt.save(state, args.ckpt_dir, int(np.asarray(state["step"])),
                  extra=ckpt_extra(trainer, state))
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            json.dump(history, f, indent=2)
    print(f"[train] done; stragglers flagged: {monitor.flagged}")
    return {"compile_s": compile_s, "history": history, "step": step_fn,
            "tile_bytes": _tile_bytes(state["tiles"])}


if __name__ == "__main__":
    main()
