"""Readings that the limits of a training cell are set from, with no program.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 [--variants twin,fp8,half,no_transfer]

For each seed the plain reference runs the cell's first three steps once,
then again in the program's place as one of the variants. The cell's
numbers are computed between the two, and judged against the cell's own
limits (``bench/limits/<cell>.json``), exactly as a run computes and judges
them between program and reference. Each variant's line gives its numbers
and ``correct``:

- ``twin``: the reference again with other random draws (the floor that
  the draws alone give the statistical numbers);
- ``fp8``: the control, every matrix product in float8 e4m3 (the precision
  below the configuration's bfloat16);
- ``half``: a planted fault, the second half of every batch left out and
  the mean taken over the rest;
- ``no_transfer``: a planted fault, E-RIDER's transfer of P into W (18b)
  left out.

The fault "a step that returns its state unchanged" needs no run: its
change reads 1 by construction. Not run by the benchmark's own runs; needs
the cell's chip, where it runs at the cell's own size.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VARIANTS = {"twin": {"stream": 1}, "fp8": {"mode": "fp8"},
            "half": {"drop_half": True}, "no_transfer": {"transfer": False}}


def readings(config: dict, traffic: dict, seed: int, variants,
             limits: dict) -> dict:
    import jax

    from bench.drivers import train
    from bench.reference import erider, lm
    from bench.traffic import bigram

    m, B, S = config["model"], traffic["batch"], traffic["seq"]
    data = bigram.batches(seed, m["vocab"], train.RING, B, S)
    ring = [{k: jax.device_put(v[i]) for k, v in data.items()}
            for i in range(train.CHECKED_STEPS)]
    kw = {"rows": train.reference_rows(traffic), "remat": True}

    def three(**extra):
        w0 = lm.leaf_paths(jax.jit(functools.partial(lm.init_params, m))(
            lm.seed_key(seed)))
        ref = erider.Reference(m, config["tile"], traffic["recipe"], w0, seed,
                               **kw, **extra)
        del w0
        steps = [ref.step(b) for b in ring]
        return steps, ref.change(), ref.w_change(), set(ref.digital)

    ref_steps, ref_change, ref_w, digital = three()
    out = {"w_moved": ref_w["moved"]}
    for name in variants:
        steps, change, w, _ = three(**VARIANTS[name])
        prog = {"loss": [s["loss"] for s in steps], "digital": digital,
                "received": steps[0]["received"], "pulses": steps[0]["pulses"]}
        nums = train.compare(prog, ref_steps, ref_change, change, ref_w, w)
        checks, correct = train.verdict(nums, limits)
        out[name] = {"correct": correct,
                     "over": [k for k, c in checks.items() if c["value"] > c["limit"]],
                     **{k: (v[0] if isinstance(v, tuple) else v)
                        for k, v in nums.items()},
                     "w_moved": w["moved"]}
    out["left_out"] = sorted(set(ref_steps[0]["grad_norm"])
                             - set(train.kept_leaves(ref_steps)))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--variants", default="twin,fp8,half,no_transfer")
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import run

    s = run.spec(args.workload)
    run.enable_compile_cache()
    run.devices(s["cell"]["chips"])
    for seed in (int(x) for x in args.seeds.split(",")):
        r = readings(s["config"], s["traffic"], seed, args.variants.split(","),
                     s["limits"])
        print(json.dumps({"workload": args.workload, "seed": seed, **r}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
