"""Counts read from a compiled step's HLO text: collective result bytes
per step, and the bytes each custom-call kernel moves per call.

A collective inside a ``while`` body runs once per trip.  XLA:TPU does
not print a trip count on the loop, so it is read from the loop's
condition: a scan's condition compares its counter ``LT`` a constant.
A loop whose condition has no such constant counts once.

XLA:TPU splits an asynchronous collective into a start fusion, any
number of continuation fusions (often sunk into an inner loop) and a
done fusion, and each of them holds the collective's instruction.  One
collective is counted where it starts: in the entry, a loop body, or a
fusion called by an ``async-collective-start`` instruction, and never
in other fusions.
"""
from __future__ import annotations

import math
import re

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")
DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
               "s16": 2, "u16": 2, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
               "f32": 4, "s64": 8, "u64": 8, "f64": 8}

_HEADER = re.compile(r"^(ENTRY )?%(\S+) .*\{\s*$")
_INSTR = re.compile(r"^\s*(?:ROOT )?%(\S+) = (.+?) ([a-z][a-z0-9-]*)\((.*)$")
_ARRAY = re.compile(r"\b(" + "|".join(DTYPE_BYTES) + r")\[([0-9,]*)\]")
_COLL = re.compile(r"^(" + "|".join(COLLECTIVES) + r")(-start)?$")


def shape_bytes(text: str) -> int:
    """Bytes of every array shape in ``text`` (a type or a tuple)."""
    return sum(DTYPE_BYTES[dt] * math.prod(int(d) for d in dims.split(",")
                                           if d)
               for dt, dims in _ARRAY.findall(text))


def computations(hlo: str) -> dict[str, list[str]]:
    """{computation name: its instruction lines}; the entry is ``ENTRY``."""
    comps: dict[str, list[str]] = {}
    cur = None
    for line in hlo.splitlines():
        m = _HEADER.match(line)
        if m:
            cur = "ENTRY" if m.group(1) else m.group(2)
            comps[cur] = []
        elif line.startswith("}"):
            cur = None
        elif cur is not None:
            comps[cur].append(line)
    return comps


def _trip_count(cond_lines: list[str]) -> int:
    text = "\n".join(cond_lines)
    if "direction=LT" not in text:
        return 1
    consts = re.findall(r"[su]32\[\]\S* constant\((\d+)\)", text)
    return int(consts[0]) if len(consts) == 1 else 1


def multipliers(comps: dict[str, list[str]], counted: set | None = None
                ) -> dict[str, int]:
    """How many times each computation runs per call of the entry.
    ``counted``, when given, is filled with the computations whose
    collectives count (see the module docstring)."""
    mult = {"ENTRY": 1}
    if counted is not None:
        counted.add("ENTRY")
    order = ["ENTRY"]
    seen = {"ENTRY"}
    while order:
        name = order.pop(0)
        for line in comps.get(name, ()):
            body = re.search(r"body=%([^\s,]+)", line)
            cond = re.search(r"condition=%([^\s,]+)", line)
            calls = re.findall(r"(?:calls|branch_computations)=\{?%([^\s,}]+)",
                               line)
            calls += re.findall(r",\s*%([^\s,}]+)", line.split(
                "branch_computations={", 1)[1]) if \
                "branch_computations={" in line else []
            kids = []
            if body and cond:
                kids.append((body.group(1),
                             _trip_count(comps.get(cond.group(1), []))))
                if counted is not None:
                    counted.add(body.group(1))
            kids += [(c, 1) for c in calls]
            if counted is not None and calls:
                m = _INSTR.match(line)
                starts = m and m.group(1).startswith("async-collective-start")
                if starts or "branch_computations=" in line:
                    counted.update(calls)
            for kid, n in kids:
                mult[kid] = mult.get(kid, 0) + mult[name] * n
                if kid not in seen:
                    seen.add(kid)
                    order.append(kid)
    return mult


def collective_bytes(hlo: str) -> dict[str, tuple[int, int]]:
    """{collective: (calls per step, result bytes per step)}."""
    comps = computations(hlo)
    counted: set = set()
    mult = multipliers(comps, counted)
    out: dict[str, tuple[int, int]] = {}
    for name, lines in comps.items():
        n = mult.get(name, 0)
        if not n or name not in counted:
            continue
        for line in lines:
            m = _INSTR.match(line)
            if not m:
                continue
            c = _COLL.match(m.group(3))
            if not c:
                continue
            rtype = m.group(2)
            if c.group(2):  # async start: (operands..., results...)
                arrays = _ARRAY.findall(rtype)
                rtype = "{}[{}]".format(*arrays[-1]) if arrays else ""
            calls, nbytes = out.get(c.group(1), (0, 0))
            out[c.group(1)] = (calls + n, nbytes + n * shape_bytes(rtype))
    return out


def custom_calls(hlo: str, target: str = "tpu_custom_call"
                 ) -> dict[str, dict]:
    """{instruction name: {"bytes": operand + result bytes, "calls": runs
    per step}} of every custom call to ``target``."""
    comps = computations(hlo)
    mult = multipliers(comps)
    shapes: dict[str, str] = {}
    for lines in comps.values():
        for line in lines:
            m = _INSTR.match(line)
            if m:
                shapes[m.group(1)] = m.group(2)
    out = {}
    for name, lines in comps.items():
        for line in lines:
            m = _INSTR.match(line)
            if not (m and m.group(3) == "custom-call"
                    and f'custom_call_target="{target}"' in line):
                continue
            args = m.group(4).split(")", 1)[0]
            operands = re.findall(r"%([^\s,)]+)", args)
            nbytes = shape_bytes(m.group(2)) + sum(
                shape_bytes(shapes.get(o, "")) for o in operands)
            out[m.group(1)] = {"bytes": nbytes, "calls": mult.get(name, 0)}
    return out


def collective_op_names(hlo: str) -> set[str]:
    """Names of the instructions that run collective work, as the
    device trace names its operations: collectives themselves, the
    async start and done halves, and fusions that hold a collective."""
    comps = computations(hlo)
    holds = {name for name, lines in comps.items()
             if any((m := _INSTR.match(line)) and _COLL.match(m.group(3))
                    for line in lines)}
    out = set()
    for lines in comps.values():
        for line in lines:
            m = _INSTR.match(line)
            if not m:
                continue
            calls = re.findall(r"calls=%([^\s,}]+)", line)
            if (_COLL.match(m.group(3)) or m.group(3).startswith(
                    ("all-gather-", "all-reduce-", "collective-permute-"))
                    or m.group(1).startswith("async-collective")
                    or any(c in holds for c in calls)):
                out.add(m.group(1))
    return out


def op_labels(hlo: str) -> dict[str, str]:
    """{instruction name: label} for naming device time: the pass
    (``fwd`` under ``jvp``, ``bwd`` under its transpose, ``step``
    outside both) and the JAX primitive of the instruction's
    ``op_name``; a custom call by its own name, a collective by its
    kind."""
    out = {}
    for lines in computations(hlo).values():
        for line in lines:
            m = _INSTR.match(line)
            if not m:
                continue
            name, opcode = m.group(1), m.group(3)
            if opcode == "custom-call":
                out[name] = re.sub(r"\.\d+$", "", name)
                continue
            coll = _COLL.match(opcode)
            if coll:
                out[name] = coll.group(1)
                continue
            meta = re.search(r'op_name="([^"]*)"', line)
            path = meta.group(1) if meta else ""
            phase = ("bwd" if "transpose(" in path else
                     "fwd" if "jvp(" in path else "step")
            prim = path.rsplit("/", 1)[-1] if "/" in path else opcode
            if prim == "shard_map" or prim.startswith("jit("):
                prim = opcode
            out[name] = f"{phase}/{prim}"
    return out
