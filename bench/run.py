"""Run one benchmark cell and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is made of is found by name: its entry in BENCHMARK.json
names a configuration (``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``, whose ``kind`` picks the module that
runs it, ``bench/drivers/<kind>.py``); its limits are ``bench/limits/<cell>.json``;
each per-layer metric is read by ``bench/metrics/<metric>.py``. The run
refuses to start where JAX finds no TPU, or fewer chips than the cell asks
for. The last line of standard output is one JSON object; the numbers the
check compared, each beside its limit, end standard error.
"""
from __future__ import annotations

import argparse
import importlib
import importlib.util
import json
import os
import sys
import time

T0 = time.perf_counter()  # set-up is counted from the process's start
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def spec(name: str) -> dict:
    """The cell's entry, configuration, traffic mix and limits."""
    bench = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    return {
        "benchmark": bench,
        "cell": cell,
        "config": load_json(BENCH, "configs", cell["config"] + ".json"),
        "traffic": load_json(BENCH, "traffic", cell["traffic"] + ".json"),
        "limits": load_json(BENCH, "limits", name + ".json"),
        "peaks": load_json(BENCH, "peaks.json"),
    }


def metrics_for(bench: dict, cell: str, trace: bool) -> list:
    """The cell's metric entries: end-to-end ones without a trace, per-layer
    ones with it. A metric without ``workloads`` belongs to every cell that
    reports the end-to-end metric it moves (or, end to end, to every cell)."""
    e2e = [m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if cell in m.get("workloads", [cell] if m["moves"] in names else [])]


def reader(name: str):
    """``read(record) -> float | None`` of ``bench/metrics/<name>.py``."""
    path = os.path.join(BENCH, "metrics", name + ".py")
    mod_spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def enable_compile_cache() -> None:
    """JAX's persistent compilation cache at a fixed directory of the
    checkout (or where ``JAX_COMPILATION_CACHE_DIR`` points), every program
    kept, so only a cell's first run in a checkout compiles."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def devices(chips: int):
    """The TPU devices the cell runs on; exits non-zero without a result
    where there are none or too few."""
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise SystemExit(f"no accelerator: {e}")
    if devs[0].platform != "tpu":
        raise SystemExit(f"no TPU found (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise SystemExit(f"cell asks for {chips} chips, {len(devs)} found")
    return devs[:chips]


def result_line(s: dict, res: dict, devs, trace: bool) -> dict:
    bench, name = s["benchmark"], s["cell"]["name"]
    metrics = {}
    for m in metrics_for(bench, name, trace):
        if trace:
            v = reader(m["name"])(dict(res["record"], peaks=s["peaks_row"]))
        else:
            v = res["e2e"].get(m["name"])
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": res["peak_bytes"]}
    line = {"correct": res["correct"], "attempted": res["attempted"],
            "failed": res["failed"], "metrics": metrics, "device": device}
    if trace:
        red = res["record"]["trace"]
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        line["breakdown"] = {"device_ops": red["device_ops"],
                             "idle_gaps": red["idle_gaps"]}
    line["checks"] = res["checks"]
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        raise SystemExit("the program (src/repro) is not in this checkout")
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import counts

    s = spec(args.workload)
    enable_compile_cache()
    devs = devices(s["cell"]["chips"])
    s["peaks_row"] = counts.peaks(s["peaks"], devs[0].device_kind)
    line = execute(s, args.seed, args.seconds, bool(args.trace), devs)
    print(json.dumps(line), flush=True)
    return 0


def execute(s: dict, seed: int, seconds: float, trace: bool, devs,
            fault: str | None = None) -> dict:
    """Run the cell's driver and build the result line; the compared
    numbers go to standard error, each beside its limit. ``fault`` plants a
    fault in the timed path (the harness's own tests)."""
    driver = importlib.import_module(f"bench.drivers.{s['traffic']['kind']}")
    res = driver.run(s["config"], s["traffic"], seed, seconds, trace,
                     s["limits"], fault=fault, t0=T0)
    line = result_line(s, res, devs, trace)
    print("seconds: " + ", ".join(f"{k} {v:.3f}" for k, v in res["timing"].items()),
          file=sys.stderr)
    if res.get("w_moved"):
        print("W over the checked steps: " + ", ".join(
            f"{k} {v['moved']} elements changed, norm {v['norm']!r}"
            for k, v in res["w_moved"].items()), file=sys.stderr)
    if res.get("left_out"):
        print(f"left out of the leaf-wise checks: {res['left_out']}",
              file=sys.stderr)
    for k, c in res["checks"].items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}"
              + (f" worst leaf {c['leaf']}" if "leaf" in c else ""),
              file=sys.stderr)
    sys.stderr.flush()
    return line


if __name__ == "__main__":
    sys.exit(main())
