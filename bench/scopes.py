"""Device time of a traced window by the program's named scopes.

The device trace names each operation by its HLO instruction
(``%multiply_reduce_fusion.6 = f32[2]{0} fusion(...)``) and carries no
op_name. The optimized HLO of the executable that ran does: each
instruction's ``metadata={op_name="jit(train_step)/tile_update/..."}``
holds the ``jax.named_scope`` path it was traced under. ``op_scopes`` maps
instruction names to op_names; ``scope_seconds`` sums the operations'
seconds by the scopes their op_name holds.

A traced run's record carries ``op_scopes`` of the executable it ran
(``bench/drivers/train.py`` reads the compiled step's ``as_text()`` after
the window).

A segment of an op_name counts as a scope with its transforms taken off:
``jvp(fwd_bwd)``, ``transpose(jvp(fwd_bwd))`` and ``vmap(pulse_kernel)``
are ``fwd_bwd``, ``fwd_bwd`` and ``pulse_kernel``. An instruction XLA
merged from ops of several scopes counts under the first one's path.
"""
from __future__ import annotations

import re

from . import trace

# the program's phase scopes (core/trainer.py TRACE_SCOPES, in step order);
# pulse_operands, pulse_kernel and tile_metrics lie inside tile_update
PHASES = ("tile_begin", "tile_effective", "fwd_bwd", "digital_opt",
          "tile_update")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%([^\s(]+) .*\{$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([^\s=]+) = (.*)$")
_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(([^()]*)\)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%([\w.\-]+)")
# roots that only relabel their operand: a fusion rooted in one is named by
# what computes that operand (a pad or slice fused under a kernel's bitcast)
_RELAYS = ("bitcast", "reshape")
_WRAPPED = re.compile(r"^[^()]*\((.*)\)$")


def _parse(hlo_text: str) -> dict:
    """{computation: [(name, opcode, operands, calls, op_name, is_root)]}."""
    comps, cur = {}, None
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            cur = comps.setdefault(m.group(1), [])
            continue
        m = _INSTR.match(line) if cur is not None else None
        code = _OPCODE.search(" " + m.group(2)) if m else None
        if code:
            op = _OP_NAME.search(line)
            calls = _CALLS.search(line)
            cur.append((m.group(1), code.group(1),
                        re.findall(r"%([\w.\-]+)", code.group(2)),
                        calls.group(1) if calls else None,
                        op.group(1) if op else "",
                        line.lstrip().startswith("ROOT")))
    return comps


def _phase_path(op_name: str) -> str:
    """The op_name's path cut after its phase: ``jit(f)/tile_update``."""
    parts = op_name.split(";")[0].split("/")
    for k, seg in enumerate(parts):
        if segments(seg)[0] in PHASES:
            return "/".join(parts[:k + 1])
    return ""


def op_scopes(hlo_text: str) -> dict:
    """{instruction name: op_name} for the instructions of an optimized HLO
    module's text (``compiled.as_text()``).

    A fusion is named by its own op_name (its root's), or, where the root
    only relabels its operand (bitcast, reshape), by the whole op_name of
    the instruction that computes that operand. A copy or broadcast that
    XLA inserted has no op_name: it is charged to the phase (``PHASES``)
    of its first user with an op_name, else of the operand it was made
    from, and to no scope inside that phase (a copy of the kernel's
    output is not the kernel); so is one whose op_name is not a whole
    path from ``jit(...)``. Instructions left without one are left out."""
    comps = _parse(hlo_text)
    out = {}
    for instrs in comps.values():
        by_name = {i[0]: i for i in instrs}
        for name, opcode, operands, calls, op, _ in instrs:
            if opcode == "fusion" and calls in comps:
                fused = {i[0]: i for i in comps[calls]}
                cur = next((i for i in comps[calls] if i[5]), None)
                while cur and cur[1] in _RELAYS and cur[2] and cur[2][0] in fused:
                    cur = fused[cur[2][0]]
                if cur and cur[4].startswith("jit("):
                    op = cur[4]
            if op.startswith("jit("):  # a whole path, not a relative one
                out[name] = op
        # inserted instructions: the phase of the first user with an
        # op_name, else of the operand they were made from; chains of them
        # (copy-start, copy-done, copy) resolve in a few passes
        users = {}
        for i in instrs:
            for o in i[2]:
                users.setdefault(o, []).append(i[0])
        todo = [i for i in instrs if i[0] not in out
                and i[1] not in ("parameter", "constant")]
        while todo:
            left = []
            for name, _, operands, _, _, _ in todo:
                src = [out[u] for u in users.get(name, ()) if u in out]
                o, seen = (operands or [None])[0], set()
                while o in by_name and o not in out and o not in seen:
                    seen.add(o)
                    o = (by_name[o][2] or [None])[0]
                src += [out[o]] if o in out else []
                phase = next((p for p in map(_phase_path, src) if p), "")
                if phase:
                    out[name] = phase
                else:
                    left.append(by_name[name])
            if len(left) == len(todo):
                break
            todo = left
    return out


def segments(op_name: str) -> list:
    """The op_name's path segments, outermost first, transforms taken off.
    An instruction that XLA merged from several ops joins their paths with
    ``;``: the first path is taken."""
    out = []
    for seg in op_name.split(";")[0].split("/"):
        m = _WRAPPED.match(seg)
        while m:
            seg = m.group(1)
            m = _WRAPPED.match(seg)
        out.append(seg)
    return out


def instruction(event: str) -> str:
    """``%fusion.12 = bf16[8,128]{...} fusion(...)`` -> ``fusion.12``."""
    return event.partition(" = ")[0].strip().lstrip("%")


def scope_seconds(rec: dict, include, exclude=()) -> float | None:
    """Seconds of the traced operations whose innermost scope of
    ``include`` + ``exclude`` is one of ``include``: an operation under
    ``tile_update/.../pulse_kernel`` counts for ``include=("tile_update",)``
    unless ``exclude`` holds ``pulse_kernel``. Loops, conditionals and
    calls are left out, as their time is that of the operations inside
    them. None where the record has no ``op_scopes`` or no operation ran
    under ``include`` (a program without those scopes)."""
    red, names = rec.get("trace"), rec.get("op_scopes")
    if not red or not names:
        return None
    include, known = set(include), set(include) | set(exclude)
    total, hit = 0.0, False
    for event, s in red["ops_s"].items():
        if trace.opcode(event) in trace._CONTAINERS:
            continue
        inner = [g for g in segments(names.get(instruction(event), ""))
                 if g in known]
        if inner and inner[-1] in include:
            total += s
            hit = True
    return total if hit else None


def ms_per_step(rec: dict, include, exclude=()) -> float | None:
    """``scope_seconds`` in milliseconds per traced step."""
    if not rec.get("trace") or not rec.get("steps"):
        return None
    s = scope_seconds(rec, include, exclude)
    return None if s is None else 1e3 * s / rec["steps"]
