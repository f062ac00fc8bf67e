"""JAX version-abstraction layer: the ONLY module allowed to touch
version-specific JAX symbols.

The runtime targets JAX 0.9.0 (jaxlib 0.9.0; libtpu 0.0.34 on the TPU) and
only that release.  Everything else in the repo imports these wrappers, so
the next JAX upgrade edits this file and nothing else:

  * ``shard_map(f, mesh=..., in_specs=..., out_specs=..., check=False)`` --
    ``jax.shard_map`` with ``check_vma``
  * ``make_mesh(axis_shapes, axis_names)`` -- ``jax.make_mesh`` with every
    axis ``AxisType.Auto`` (classic shard_map + NamedSharding semantics)
  * ``ClosedJaxpr`` / ``Jaxpr`` -- the jaxpr types, from ``jax.extend.core``
  * ``optimization_barrier`` -- a differentiable barrier over a pytree
  * ``float8_dtypes()`` -- the float8 wire/store dtypes by format alias
  * ``cost_analysis(compiled)`` -- ``Compiled.cost_analysis()`` as a dict
  * ``tree_flatten_with_path`` / ``tree_unflatten`` / ``tree_map_with_path``
    -- the ``jax.tree`` path utilities
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.extend.core import ClosedJaxpr, Jaxpr

__all__ = [
    "ClosedJaxpr", "Jaxpr", "cost_analysis", "float8_dtypes", "make_mesh",
    "optimization_barrier", "shard_map", "tree_flatten_with_path",
    "tree_map_with_path", "tree_unflatten",
]


# --------------------------------------------------------------------------- #
# shard_map
# --------------------------------------------------------------------------- #
def shard_map(f: Callable, *, mesh, in_specs, out_specs,
              check: bool = False) -> Callable:
    """``jax.shard_map``; ``check`` maps to ``check_vma`` (default False
    here: the runtime uses untraceable-replication collectives like
    psum_scatter)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check)


# --------------------------------------------------------------------------- #
# mesh construction
# --------------------------------------------------------------------------- #
def make_mesh(axis_shapes: tuple[int, ...], axis_names: tuple[str, ...],
              *, devices=None):
    """A Mesh with every axis marked ``AxisType.Auto`` so shard_map +
    NamedSharding keep their classic semantics."""
    return jax.make_mesh(
        axis_shapes, axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names),
        devices=devices,
    )


# --------------------------------------------------------------------------- #
# differentiable optimization barrier
# --------------------------------------------------------------------------- #
# The runtime uses it to force value materialization at layer seams inside
# fused scan bodies (XLA's bf16 pass may otherwise keep wider intermediates
# across the seam, changing bf16 roundings vs a per-layer scan-iteration
# boundary).  The custom VJP barriers the cotangents as their own group, so
# the backward seam is pinned the same way as the forward one.
def _barrier_inexact(tree):
    """Barrier inexact leaves; pass ints/float0 cotangents through (XLA's
    optimization_barrier rejects float0, and integer leaves don't carry
    numerics worth pinning)."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    f0 = jax.dtypes.float0
    keep = [jnp_issubdtype_inexact(l) and getattr(l, "dtype", None) != f0
            for l in leaves]
    picked = [l for l, k in zip(leaves, keep) if k]
    barriered = iter(jax.lax.optimization_barrier(picked) if picked else ())
    out = [next(barriered) if k else l for l, k in zip(leaves, keep)]
    return jax.tree_util.tree_unflatten(treedef, out)


def jnp_issubdtype_inexact(x) -> bool:
    dt = getattr(x, "dtype", None)
    return dt is not None and jnp.issubdtype(dt, jnp.inexact)


@jax.custom_vjp
def optimization_barrier(tree):
    return _barrier_inexact(tree)


def _ob_fwd(tree):
    return _barrier_inexact(tree), None


def _ob_bwd(_res, ct):
    return (_barrier_inexact(ct),)


optimization_barrier.defvjp(_ob_fwd, _ob_bwd)


# --------------------------------------------------------------------------- #
# float8 dtypes
# --------------------------------------------------------------------------- #
def float8_dtypes() -> dict:
    """The float8 dtypes as ``{format alias: dtype}``: ``fp8_e4m3`` ->
    float8_e4m3fn, ``fp8_e5m2`` -> float8_e5m2.  core.wire registers these
    as cast wire formats and core.store as ParamStore formats."""
    return {"fp8_e4m3": jnp.dtype(jnp.float8_e4m3fn),
            "fp8_e5m2": jnp.dtype(jnp.float8_e5m2)}


# --------------------------------------------------------------------------- #
# compiled-artifact introspection
# --------------------------------------------------------------------------- #
def cost_analysis(compiled) -> dict:
    """``Compiled.cost_analysis()`` as a plain dict (empty when the backend
    reports nothing)."""
    return dict(compiled.cost_analysis() or {})


# --------------------------------------------------------------------------- #
# tree utilities
# --------------------------------------------------------------------------- #
def tree_flatten_with_path(tree: Any):
    return jax.tree.flatten_with_path(tree)


def tree_unflatten(treedef, leaves):
    return jax.tree.unflatten(treedef, leaves)


def tree_map_with_path(f: Callable, tree: Any, *rest: Any):
    return jax.tree.map_with_path(f, tree, *rest)
