"""Reduce a JAX profiler trace (``.xplane.pb``) to device busy time, idle
share, per-operation time and the longest idle gaps.

The window is the host span named ``WINDOW`` that the harness opens around
the traced steps. Device planes are those named ``/device:TPU:<n>``; on each,
the operations are the events of the line ``XLA Ops``. Busy time is the union of the operation
intervals inside the window, averaged over the device planes.
"""
from __future__ import annotations

import glob
import os
import re

WINDOW = "bench_window"
OPS_LINE = "XLA Ops"
_DEVICE = re.compile(r"^/device:TPU:\d+$")


def find(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {trace_dir}, "
                                f"found {len(paths)}")
    return paths[0]


def _events(line):
    return [(e.name, float(e.start_ns), float(e.duration_ns)) for e in line.events]


def load(path: str) -> dict:
    """{"window": (start_ns, end_ns), "devices": {plane: {line: events}},
    "host": [events on host threads]} from an ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        if _DEVICE.match(plane.name):
            devices[plane.name] = {ln.name: _events(ln) for ln in plane.lines}
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                host.extend(_events(ln))
    wins = [(s, s + d) for n, s, d in host if n == WINDOW]
    if not wins:
        raise ValueError(f"no host span named {WINDOW!r} in {path}")
    if not devices:
        raise ValueError(f"no TPU device plane in {path}")
    return {"window": wins[0], "devices": devices, "host": host}


def _clip(events, lo, hi):
    out = []
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((name, a, b))
    return out


def union_ns(intervals) -> float:
    total, end = 0.0, float("-inf")
    for a, b in sorted(intervals):
        if b <= end:
            continue
        total += b - max(a, end)
        end = b
    return total


def gaps(intervals, lo, hi):
    """Idle intervals of [lo, hi] not covered by ``intervals``."""
    out, cur = [], lo
    for a, b in sorted(intervals):
        if a > cur:
            out.append((cur, a))
        cur = max(cur, b)
    if hi > cur:
        out.append((cur, hi))
    return out


_CONTAINERS = ("while", "conditional", "call")


def short(name: str) -> str:
    """``%fusion.12 = bf16[8,128]{...} fusion(...), ...`` -> ``%fusion.12
    fusion bf16[8,128]``: the instruction, its opcode and its first output
    type."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name
    m = re.search(r"\s([a-z][a-z0-9_\-]*)\(", " " + rest)
    op = m.group(1) if m else "?"
    if 'custom_call_target="tpu_custom_call"' in rest:
        op = "tpu_custom_call"
    t = re.match(r"\(?([a-z0-9]+\[[0-9,]*\])", rest)
    return f"{head} {op}" + (f" {t.group(1)}" if t else "")


def opcode(name: str) -> str:
    return short(name).split(" ")[1] if " = " in name else ""


def reduce(trace: dict, top: int = 10) -> dict:
    """Busy and window seconds, per-operation seconds (summed over devices
    and divided by their number), the operations that took most time (loops and calls, which contain other
    operations, left out), and the longest idle gaps of the first device,
    each named by the host span that covers most of it."""
    lo, hi = trace["window"]
    nd = len(trace["devices"])
    busy, ops = 0.0, {}
    first_gaps = None
    for plane in sorted(trace["devices"]):
        lines = trace["devices"][plane]
        evs = _clip(lines.get(OPS_LINE, []), lo, hi)
        iv = [(a, b) for _, a, b in evs]
        busy += union_ns(iv)
        for name, a, b in evs:
            ops[name] = ops.get(name, 0.0) + (b - a) / nd
        if first_gaps is None:
            first_gaps = gaps(iv, lo, hi)
    leaf_ops = {}
    for k, v in ops.items():
        if opcode(k) not in _CONTAINERS:
            leaf_ops[short(k)] = leaf_ops.get(short(k), 0.0) + v
    host = [(n, s, s + d) for n, s, d in trace["host"] if n != WINDOW]
    named = []
    for a, b in sorted(first_gaps or [], key=lambda g: g[0] - g[1])[:top]:
        best, cover = "idle", 0.0
        for n, s, e in host:
            c = min(e, b) - max(s, a)
            if c > cover:
                best, cover = n, c
        named.append([best, (b - a) * 1e-9])
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy / nd * 1e-9,
        "ops_s": {k: v * 1e-9 for k, v in ops.items()},
        "device_ops": [[k, v * 1e-9] for k, v in
                       sorted(leaf_ops.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": named,
    }


def op_seconds(red: dict, pattern: str) -> float | None:
    """Seconds of the operations whose name matches ``pattern``; None where
    none ran."""
    rx = re.compile(pattern)
    hits = [v for k, v in red["ops_s"].items() if rx.search(k)]
    return sum(hits) if hits else None
