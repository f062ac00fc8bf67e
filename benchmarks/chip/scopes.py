"""The program's own names for its work, read for the per-layer metrics.

The program names device work with ``jax.named_scope``s (``model.*``,
``fsdp.*``, ``optim.*``, listed in ``src/repro/spans.py``), which land
in each HLO instruction's ``op_name`` metadata, and names host work with
``train.*`` profiler spans in ``train_loop``.  This module reads both:

* ``op_scopes``: per HLO instruction, the program scopes on its
  ``op_name`` path and whether it is recomputed in the backward (under
  ``checkpoint/rematted_computation``);
* ``load_program_spans``: the ``train.*`` host events of a trace;
* ``share``: device time of the operations whose scopes satisfy a
  predicate, over the device's busy time in the window, averaged over
  the chips.

A fused operation is named by its own ``op_name`` (XLA gives a fusion
the metadata of one of its fused instructions), so its whole time goes
to that instruction's scopes.

The harness's namespace (``cell.reduce_trace``) holds the compiled
step's HLO text and the trace's path only as that function's locals;
``context`` reads them from its frame and puts ``scopes`` and
``program_spans`` on the namespace, once.  A program without scopes or
spans (one older than them) gives empty ones, and every metric built on
them then reads nothing.
"""
from __future__ import annotations

import re
import sys

from . import hlo
from . import trace as tr

SPAN_PREFIX = "train."
REMAT = "rematted_computation"
#: a program scope: a ``model.``, ``fsdp.`` or ``optim.`` name, alone on
#: its path component or inside a transform's parentheses
_SCOPE = re.compile(r"(?<![\w.])(?:model|fsdp|optim)(?:\.\w+)+")
_NAME = re.compile(r"^\s*(?:ROOT )?%(\S+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def op_scopes(hlo_text: str) -> dict[str, tuple[tuple[str, ...], bool]]:
    """{instruction name: (program scopes on its ``op_name`` path,
    outermost first; whether it is rematerialised)} for every
    instruction of every computation."""
    out = {}
    for lines in hlo.computations(hlo_text).values():
        for line in lines:
            m = _NAME.match(line)
            if not m:
                continue
            meta = _OP_NAME.search(line)
            path = meta.group(1) if meta else ""
            out[m.group(1)] = (tuple(_SCOPE.findall(path)), REMAT in path)
    return out


def load_program_spans(path: str) -> list[tuple[str, float, float]]:
    """``[(name, start s, end s)]`` of the host events named ``train.*``
    in the ``.xplane.pb`` at ``path``."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend((e.name, e.start_ns * 1e-9,
                            (e.start_ns + e.duration_ns) * 1e-9)
                           for e in line.events
                           if e.name.startswith(SPAN_PREFIX))
    return sorted(out, key=lambda s: s[1])


def _from_reduce_trace(ctx):
    """(HLO text, trace path) held by the ``cell.reduce_trace`` call
    whose namespace is ``ctx``; ``(None, None)`` if no caller holds
    them."""
    frame = sys._getframe(1)
    while frame is not None:
        loc = frame.f_locals
        if loc.get("ctx") is ctx and "text" in loc and loc.get("paths"):
            return loc["text"], loc["paths"][-1]
        frame = frame.f_back
    return None, None


def context(ctx):
    """``ctx`` with ``scopes`` (``op_scopes`` of the compiled step) and
    ``program_spans`` (``load_program_spans`` of the trace)."""
    if not hasattr(ctx, "scopes"):
        text, path = _from_reduce_trace(ctx)
        ctx.scopes = op_scopes(text) if text else {}
        ctx.program_spans = load_program_spans(path) if path else []
    return ctx


def scope_time(trace, window, scopes, pick) -> dict[int, float]:
    """Device time in the window of the operations whose ``(scopes,
    rematted)`` satisfy ``pick``, per device."""
    names = {n for n, (sc, remat) in scopes.items() if pick(sc, remat)}
    return tr.op_time(trace, window, names)


def share(ctx, pick, needs_scopes: bool = True) -> float | None:
    """Percent of the busy device time in ``ctx.window`` spent in
    operations whose ``(scopes, rematted)`` satisfy ``pick``, averaged
    over the chips.  ``None`` where nothing can be read: no busy device,
    or (with ``needs_scopes``) a program that names no scope."""
    ctx = context(ctx)
    if needs_scopes and not any(sc for sc, _ in ctx.scopes.values()):
        return None
    t = scope_time(ctx.trace, ctx.window, ctx.scopes, pick)
    busy = tr.busy(ctx.trace, ctx.window)
    shares = [100.0 * t.get(d, 0.0) / b for d, b in busy.items() if b > 0]
    return sum(shares) / len(shares) if shares else None


def in_any(*names: str):
    """A ``pick`` for ``share``: an operation under any of ``names``."""
    return lambda scopes, remat: any(s in names for s in scopes)
