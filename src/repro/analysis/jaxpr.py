"""Jaxpr-level extraction: CommTrace + BufferTrace from a traced step.

This module is PURE MECHANICS -- walk a closed jaxpr (recursing through
call primitives, scans, custom_vjp bodies; never into ``pallas_call``
bodies, whose values are tile-resident on TPU and not XLA buffers) and
extract:

  * ``CommTrace`` -- every collective equation (all_gather / psum_scatter /
    ppermute / psum / all_to_all) with its payload dtype, element count,
    mesh axes, and the scan-trip multiplier of the scope it sits in, so
    per-step wire bytes are computable without running anything.
  * ``BufferTrace`` -- every equation-output aval (the intermediate
    buffers XLA must materialize), every scan-carry aval, and a per-scope
    liveness peak for avals of a given size class (the gathered-buffer
    peak the two-slot prefetch bounds).

Invariant *checking* against a ShardingPlan lives in
``repro.analysis.verify``; this module knows nothing about plans.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Iterator, Optional, Sequence

import jax
import numpy as np

from ..compat import ClosedJaxpr, Jaxpr

_JAXPR_TYPES = (ClosedJaxpr, Jaxpr)

#: primitives that put payload on the inter-device wire
COLLECTIVE_PRIMS = frozenset(
    {"all_gather", "psum_scatter", "reduce_scatter", "ppermute", "psum",
     "all_to_all"})


def _sub_jaxprs(eqn) -> Iterator[Jaxpr]:
    """The sub-jaxprs of one equation's params (scan/cond bodies, pjit /
    remat / custom_vjp calls), as plain Jaxprs."""
    for p in jax.tree.leaves(eqn.params,
                             is_leaf=lambda x: isinstance(x, _JAXPR_TYPES)):
        if isinstance(p, ClosedJaxpr):
            yield p.jaxpr
        elif isinstance(p, Jaxpr):
            yield p


def _as_jaxpr(jaxpr) -> Jaxpr:
    return jaxpr.jaxpr if isinstance(jaxpr, ClosedJaxpr) else jaxpr


def iter_eqns(jaxpr, *, skip_pallas: bool = True,
              _mult: int = 1, _path: str = "") -> Iterator[tuple]:
    """Yield ``(eqn, trips, path)`` for every equation reachable from
    ``jaxpr``.  ``trips`` is how many times the equation executes per call
    of the top-level jaxpr (the product of enclosing scan lengths; while
    loops count as 1 -- the bound is unknowable statically).  ``path`` is
    a ``/``-joined primitive trail for Violation reports."""
    jx = _as_jaxpr(jaxpr)
    for i, eqn in enumerate(jx.eqns):
        name = eqn.primitive.name
        here = f"{_path}/{name}[{i}]"
        yield eqn, _mult, here
        if skip_pallas and "pallas" in name:
            continue
        sub_mult = _mult
        if name == "scan":
            length = eqn.params.get("length")
            if length is not None:
                sub_mult = _mult * int(length)
        for sub in _sub_jaxprs(eqn):
            yield from iter_eqns(sub, skip_pallas=skip_pallas,
                                 _mult=sub_mult, _path=here)


def intermediate_avals(jaxpr, *, skip_pallas: bool = True) -> list:
    """Every equation-output aval reachable from ``jaxpr`` -- the
    intermediates XLA materializes as buffers.  With ``skip_pallas`` (the
    default) values inside ``pallas_call`` bodies are excluded: the kernel
    body IS the fusion (tile-resident on TPU), so its values are not XLA
    buffers.  Generalizes the walker the fused-kernel jaxpr regressions
    were built on."""
    acc = []
    for eqn, _, _ in iter_eqns(jaxpr, skip_pallas=skip_pallas):
        if skip_pallas and "pallas" in eqn.primitive.name:
            continue
        for v in eqn.outvars:
            av = getattr(v, "aval", None)
            if av is not None and hasattr(av, "shape"):
                acc.append(av)
    return acc


def scan_carry_avals(jaxpr) -> list[tuple[tuple, str]]:
    """``(shape, dtype-name)`` of every scan-carry input across the whole
    program -- what the prefetch retention regression inspects (a gathered
    layer buffer in a carry means backward retains one buffer per layer)."""
    found = []
    for eqn, _, _ in iter_eqns(jaxpr):
        if eqn.primitive.name == "scan":
            nc = eqn.params["num_consts"]
            nk = eqn.params["num_carry"]
            for v in eqn.invars[nc:nc + nk]:
                found.append((tuple(v.aval.shape), str(v.aval.dtype)))
    return found


def has_full_f32(fn: Callable, *args, n: int) -> bool:
    """True if tracing ``fn(*args)`` materializes any fp32 intermediate of
    ``>= n`` elements outside pallas bodies (the gather-path fused-dequant
    regression: the fused kernel must show none, the unfused composition
    must show at least one)."""
    avals = intermediate_avals(jax.make_jaxpr(fn)(*args))
    return any(av.dtype == jax.numpy.float32
               and int(np.prod(av.shape)) >= n for av in avals)


def count_full_f32(fn: Callable, *args, n: int) -> int:
    """Number of fp32 intermediates of ``>= n`` elements outside pallas
    bodies in the trace of ``fn(*args)``."""
    avals = intermediate_avals(jax.make_jaxpr(fn)(*args))
    return sum(1 for av in avals
               if av.dtype == jax.numpy.float32
               and int(np.prod(av.shape)) >= n)


# --------------------------------------------------------------------------- #
# CommTrace
# --------------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class CollectiveEvent:
    """One collective equation in the traced program.

    ``trips`` is the scan-trip multiplier (executions per step);
    ``wire_bytes`` is per-device bytes ONE execution puts on the wire:
    an all_gather ships its (m-1) remote shards, a psum_scatter ships
    (m-1)/m of its input, a ppermute hop ships its whole operand, and a
    psum costs a reduce + broadcast (2(m-1)/m)."""

    kind: str                 # primitive name
    axes: tuple[str, ...]     # mesh axis names the collective runs over
    axis_size: int            # product of the named axes' sizes
    dtype: str                # payload dtype name
    elems: int                # payload elements (per-device input)
    trips: int                # executions per step (scan multiplier)
    path: str                 # jaxpr location trail

    @property
    def itemsize(self) -> int:
        return jax.numpy.dtype(self.dtype).itemsize

    @property
    def in_bytes(self) -> int:
        return self.elems * self.itemsize

    @property
    def wire_bytes(self) -> float:
        m = self.axis_size
        if m <= 1:
            return 0.0
        if self.kind == "all_gather":
            return float(self.in_bytes * (m - 1))
        if self.kind in ("psum_scatter", "reduce_scatter"):
            return float(self.in_bytes) * (m - 1) / m
        if self.kind == "ppermute":
            return float(self.in_bytes)
        if self.kind == "psum":
            return 2.0 * self.in_bytes * (m - 1) / m
        if self.kind == "all_to_all":
            return float(self.in_bytes) * (m - 1) / m
        return 0.0


def _axis_tuple(params) -> tuple[str, ...]:
    axes = params.get("axis_name", params.get("axes", ()))
    if isinstance(axes, (list, tuple)):
        return tuple(str(a) for a in axes)
    return (str(axes),)


@dataclasses.dataclass(frozen=True)
class CommTrace:
    """All collective events of one traced step."""

    events: tuple[CollectiveEvent, ...]
    axis_sizes: dict[str, int]

    def filter(self, *, kinds: Optional[Sequence[str]] = None,
               dtype: Optional[str] = None,
               elems: Optional[int] = None) -> "CommTrace":
        ev = self.events
        if kinds is not None:
            ev = tuple(e for e in ev if e.kind in kinds)
        if dtype is not None:
            ev = tuple(e for e in ev if e.dtype == dtype)
        if elems is not None:
            ev = tuple(e for e in ev if e.elems == elems)
        return CommTrace(ev, self.axis_sizes)

    @property
    def total_wire_bytes(self) -> float:
        return sum(e.wire_bytes * e.trips for e in self.events)

    def counts(self) -> dict[str, int]:
        out: dict[str, int] = {}
        for e in self.events:
            out[e.kind] = out.get(e.kind, 0) + e.trips
        return out

    def __len__(self) -> int:
        return len(self.events)


def extract_comm(jaxpr, axis_sizes: dict[str, int]) -> CommTrace:
    """Walk ``jaxpr`` and collect every collective equation as a
    CollectiveEvent.  ``axis_sizes`` maps mesh axis names to sizes (psum /
    ppermute params carry only names; all_gather also carries axis_size)."""
    events = []
    for eqn, trips, path in iter_eqns(jaxpr):
        name = eqn.primitive.name
        if name not in COLLECTIVE_PRIMS:
            continue
        axes = _axis_tuple(eqn.params)
        m = int(np.prod([axis_sizes.get(a, 1) for a in axes])) or 1
        if name == "ppermute":
            # hop count is encoded in the perm, not the axis: a full ring
            # permutation has m entries but each device sends once
            perm = eqn.params.get("perm", ())
            m = max(m, len(perm))
        for v in eqn.invars:
            av = getattr(v, "aval", None)
            if av is None or not hasattr(av, "shape"):
                continue
            events.append(CollectiveEvent(
                kind=name, axes=axes, axis_size=m,
                dtype=str(av.dtype),
                elems=int(np.prod(av.shape)) if av.shape else 1,
                trips=trips, path=path))
    return CommTrace(tuple(events), dict(axis_sizes))


# --------------------------------------------------------------------------- #
# BufferTrace
# --------------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class BufferTrace:
    """Materialized-buffer view of one traced step: every intermediate
    aval, every scan-carry aval, and per-scope liveness peaks for a size
    class of interest (gathered layer buffers)."""

    intermediates: tuple          # avals
    scan_carries: tuple[tuple[tuple, str], ...]
    # per-scope max simultaneously-live avals matching the probe class,
    # keyed by scope path -- see ``live_peak``
    _jaxpr: Any = dataclasses.field(repr=False, default=None)

    def full_f32(self, n: int) -> list:
        return [av for av in self.intermediates
                if av.dtype == jax.numpy.float32
                and int(np.prod(av.shape)) >= n]

    def live_peak(self, *, elems: int, dtype) -> int:
        """Max number of simultaneously-live values of exactly ``elems``
        elements in ``dtype`` within any single jaxpr scope -- a
        backward-liveness scan per scope (carries and scope inputs count
        as live throughout).  Gathered layer buffers never cross scope
        boundaries except via carries (which the scan-carry regression
        forbids), so the per-scope max IS the program peak for them."""
        want = (int(elems), str(jax.numpy.dtype(dtype)))

        def matches(v) -> bool:
            av = getattr(v, "aval", None)
            return (av is not None and hasattr(av, "shape")
                    and (int(np.prod(av.shape)) if av.shape else 1,
                         str(av.dtype)) == want)

        peak = 0

        def scan_scope(jx):
            nonlocal peak
            # backward pass: live set after the last eqn = outvars
            live = {id(v) for v in jx.outvars if matches(v)}
            # scope inputs that match are live for the whole scope
            base = {id(v) for v in list(jx.invars) + list(jx.constvars)
                    if matches(v)}
            peak = max(peak, len(live | base))
            for eqn in reversed(jx.eqns):
                produced = {id(v) for v in eqn.outvars if matches(v)}
                live -= produced
                for v in eqn.invars:
                    if matches(v):
                        live.add(id(v))
                peak = max(peak, len(live | base))
                if "pallas" in eqn.primitive.name:
                    continue
                for sub in _sub_jaxprs(eqn):
                    scan_scope(sub)

        if self._jaxpr is not None:
            scan_scope(_as_jaxpr(self._jaxpr))
        return peak


def extract_buffers(jaxpr) -> BufferTrace:
    return BufferTrace(
        intermediates=tuple(intermediate_avals(jaxpr)),
        scan_carries=tuple(scan_carry_avals(jaxpr)),
        _jaxpr=jaxpr,
    )


# --------------------------------------------------------------------------- #
# step tracing
# --------------------------------------------------------------------------- #
def _struct_of(tree):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype), tree)


def trace_train_step(runtime, optimizer=None, *, batch=None,
                     batch_size: int = 4, seq: int = 16):
    """``(closed_jaxpr, out_shapes)`` of one train step under the
    runtime's resolved plan -- pure abstract eval: parameters enter as
    ShapeDtypeStructs (``runtime.param_shapes()``), nothing is
    materialized beyond the optimizer's zero-init state, and nothing
    compiles.  ``batch`` defaults to the model's synthetic-pipeline batch
    structure so every arch (dense / MoE / encdec / recurrent) traces with
    the inputs training actually feeds it."""
    import jax.numpy as jnp

    from ..data.pipeline import DataConfig, SyntheticStream

    if optimizer is None:
        from ..optim import make_optimizer

        optimizer = make_optimizer(runtime.cfg)
    if batch is None:
        stream = SyntheticStream(
            DataConfig(runtime.cfg.vocab, seq, batch_size), runtime.cfg)
        batch = stream.batch(0)
    params = runtime.param_shapes()
    opt_state = _struct_of(optimizer.init(runtime))
    step = jax.ShapeDtypeStruct((), jnp.int32)
    fn = runtime.make_train_step(optimizer)
    closed, out_shapes = jax.make_jaxpr(fn, return_shape=True)(
        params, opt_state, step, _struct_of(batch))
    return closed, out_shapes
