"""Seeded weights, made on the device, in the program's layout and in
the reference's.

Every logical tensor is drawn from ``(seed, crc32(name), layer + 1)`` at
the scales ``FSDPRuntime._init_tensor`` uses: a normal with standard
deviation ``1 / sqrt(shape[0])`` for tensors of two or more dims, ones
for norm scales, zeros for other vectors (biases).  The same function
feeds the
program (packed into each group's flat buffer at the planner's offsets,
in one jitted call per group with the group's sharding) and the
reference (as a dict of stacked tensors), so neither takes weights from
the other.  ``w0`` is never kept: where a change from it is measured, it
is drawn again inside the same fused reduction.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np


def base_key(seed: int):
    """A key from any whole-number seed (more than 32 bits welcome)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.fold_in(jax.random.key(int(words[0])), int(words[1]))


def _is_norm(name: str) -> bool:
    return any(t in name for t in ("ln", "norm", "skip", "scale"))


def tensor(key, name: str, shape, layers: int | None):
    """One logical tensor, ``(layers, *shape)`` when stacked."""
    shape = tuple(shape)
    if len(shape) < 2:
        fill = 1.0 if _is_norm(name) else 0.0
        full = ((layers,) if layers else ()) + shape
        return jnp.full(full, fill, jnp.float32)
    k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    std = 1.0 / np.sqrt(max(shape[0], 1))  # shape[0], as the program

    def one(layer):
        return jax.random.normal(jax.random.fold_in(k, layer + 1), shape,
                                 jnp.float32) * std

    if layers:
        return jax.vmap(one)(jnp.arange(layers))
    return one(-1)


def logical(key, shapes: dict) -> dict:
    """{name: tensor} for ``shapes`` = {name: (shape, layers or None)}."""
    return {n: tensor(key, n, s, L) for n, (s, L) in shapes.items()}


def _placements(lo):
    return sorted(lo.plan.placements, key=lambda p: p.offset)


def check_layout(runtime, shapes: dict) -> None:
    """Every program tensor is one the benchmark makes, at its shape."""
    for lo in runtime.layouts.values():
        if lo.outer_size != 1 or lo.plan.mode == "fsdp2":
            raise ValueError(f"group {lo.name}: only contiguous, un-split "
                             f"layouts are packed here")
        for p in lo.plan.placements:
            want = shapes.get(p.spec.name)
            if want is None or tuple(want[0]) != tuple(p.spec.shape) or \
                    bool(want[1]) != bool(lo.n_layers):
                raise ValueError(f"program tensor {p.spec.name} "
                                 f"{p.spec.shape} in group {lo.name} is not "
                                 f"in the benchmark's model {want}")


def _pack(lo, arrays: dict):
    """Flat ``(L, total)`` / ``(total,)`` buffer: tensors at their offsets,
    zeros between and after."""
    lead = (lo.n_layers,) if lo.n_layers else ()
    parts, at = [], 0
    for p in _placements(lo):
        if p.offset > at:
            parts.append(jnp.zeros(lead + (p.offset - at,), jnp.float32))
        parts.append(arrays[p.spec.name].reshape(lead + (p.spec.size,)))
        at = p.end
    if lo.plan.total > at:
        parts.append(jnp.zeros(lead + (lo.plan.total - at,), jnp.float32))
    return jnp.concatenate(parts, axis=-1)


def program_params_fn(runtime, shapes: dict):
    """``seed -> params``: the program's parameter state made on the
    device, one jitted call per group, out-sharded like the group.  The
    store state is the store's own ``rebuild`` of the fp32 master."""
    from jax.sharding import NamedSharding

    fns = {}
    for name, lo in runtime.layouts.items():
        if lo.store.has_ef:
            raise ValueError(f"group {name}: stores with an error-feedback "
                             f"residual are not made here")
        sub = {p.spec.name: shapes[p.spec.name] for p in lo.plan.placements}
        sharding = jax.tree.map(
            lambda s: NamedSharding(runtime.mesh, s),
            lo.store.state_pspecs(lo.pspec()))
        fns[name] = jax.jit(lambda k, lo=lo, sub=sub: lo.store.rebuild(
            _pack(lo, logical(k, sub))), out_shardings=sharding)

    def make(seed: int):
        key = base_key(seed)
        return {name: fn(key) for name, fn in fns.items()}
    return make


def unpack(lo, flat) -> dict:
    """{tensor: (L, size) or (size,)} views of a group's flat buffer."""
    out = {}
    for p in lo.plan.placements:
        out[p.spec.name] = flat[..., p.offset:p.end]
    return out


def _norms(tree: dict, layered: dict) -> dict:
    out = {}
    for n, a in tree.items():
        a = a.reshape((a.shape[0], -1) if layered[n] else (-1,))
        out[n] = jnp.sqrt(jnp.sum(jnp.square(a), axis=-1))
    return out


def program_grad_norms_fn(runtime, b1: float):
    """``m -> {leaf: norm}`` of the first gradient, read back from AdamW's
    first moment after one step (``m1 = (1 - b1) * g``)."""
    def fn(m_state):
        out = {}
        for name, lo in runtime.layouts.items():
            parts = unpack(lo, m_state[name] / jnp.float32(1.0 - b1))
            out.update(_norms(parts, {n: bool(lo.n_layers) for n in parts}))
        return out
    return jax.jit(fn)


def program_change_norms_fn(runtime, shapes: dict):
    """``(params, key) -> {leaf: |w - w0|}``, ``w0`` drawn again from the
    key of the seed (``base_key``)."""
    def fn(params, key):
        out = {}
        for name, lo in runtime.layouts.items():
            parts = unpack(lo, lo.store.master_f32(params[name]))
            diff = {}
            for n, w in parts.items():
                s, L = shapes[n]
                diff[n] = w - tensor(key, n, s, L).reshape(w.shape)
            out.update(_norms(diff, {n: bool(lo.n_layers) for n in diff}))
        return out
    return jax.jit(fn)


def reference_change_norms_fn(shapes: dict):
    def fn(params, key):
        diff = {n: params[n] - tensor(key, n, *shapes[n]) for n in params}
        return _norms(diff, {n: bool(shapes[n][1]) for n in diff})
    return jax.jit(fn)
