"""What decides ``correct``: the reference's first steps from the seed,
and the numbers that compare the program's steps with them.

``gaps`` reads every number below; a cell compares those its workload
file's ``limits`` name, each against its own limit:

* ``loss_gap``: the largest relative gap of a step's loss over the first
  ``check_steps`` steps;
* ``grad_gap``: the first gradient, by its worst leaf (a tensor of one
  layer): ``| |g| - |g_ref| |`` over the larger of ``|g_ref|`` and the
  median leaf's ``|g_ref|``;
* ``grad_median_gap``: the same per-leaf gap, at the median leaf;
* ``change_gap``: the parameters' change after ``check_steps`` steps,
  ``|w - w0|`` by its worst leaf, in the same way.  Leaves whose
  reference first gradient is under a thousandth of the median leaf's
  move under Adam by round-off alone and are left out.

Which numbers a cell compares, and the readings its limits were set
from, are in PERF.md.
"""
from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp
import numpy as np

from . import weights

NOUGHT = 1e-3  # of the median leaf's first gradient


def reference_module(conf: dict):
    return importlib.import_module(
        f"{__package__}.references.{conf['reference']}")


def fault_weights(kind: str, shape):
    """``(weights, denom)`` over the predicted positions ``shape`` =
    (B, T-1).  ``kind``: ``none``, or ``half`` (half the batch left out
    and the mean taken over the rest: half the rows, or half the
    positions of a single row)."""
    B, T1 = shape
    w = np.ones(shape, np.float32)
    if kind == "half":
        if B >= 2:
            w[B // 2:] = 0
        else:
            w[:, T1 // 2:] = 0
    elif kind != "none":
        raise ValueError(kind)
    return w, float(w.sum())


class Reference:
    """The plain reference of one cell: seeded init, ``check_steps``
    AdamW steps, and the numbers the comparison needs."""

    def __init__(self, conf: dict, opt: dict, *, mode="fp32"):
        ref = reference_module(conf)
        self.model = ref.Model(conf)
        self.shapes = self.model.shapes()
        self.step = ref.make_step(self.model, opt, mode=mode)
        self.change = weights.reference_change_norms_fn(self.shapes)
        self.init = jax.jit(lambda k: weights.logical(k, self.shapes))
        self.zeros = jax.jit(lambda: {n: jnp.zeros(
            ((L,) if L else ()) + tuple(s), jnp.float32)
            for n, (s, L) in self.shapes.items()})

    def run(self, seed: int, batches, fault: str = "none") -> dict:
        """``batches``: host token arrays, one per step."""
        key = weights.base_key(seed)
        p, mu, nu = self.init(key), self.zeros(), self.zeros()
        losses, grad = [], None
        for t, toks in enumerate(batches):
            w, denom = fault_weights(fault, (toks.shape[0], toks.shape[1] - 1))
            p, mu, nu, loss, g = self.step(p, mu, nu, jnp.asarray(toks),
                                           jnp.asarray(w), jnp.float32(denom),
                                           jnp.float32(t))
            losses.append(loss)
            if grad is None:
                grad = g
        change = self.change(p, key)
        out = {"loss": [float(x) for x in losses],
               "grad": jax.tree.map(np.asarray, grad),
               "change": jax.tree.map(np.asarray, change)}
        for a in (p, mu, nu):
            for x in jax.tree.leaves(a):
                x.delete()
        return out


def _leaves(tree: dict) -> dict[str, float]:
    out = {}
    for n, a in tree.items():
        a = np.atleast_1d(np.asarray(a, np.float64))
        if a.size == 1 and np.ndim(tree[n]) == 0:
            out[n] = float(a[0])
        else:
            out.update({f"{n}[{i}]": float(x) for i, x in enumerate(a)})
    return out


def _worst(got: dict, ref: dict, keep) -> tuple[float, str]:
    med = float(np.median([ref[k] for k in keep]))
    worst, at = 0.0, ""
    for k in keep:
        g = abs(got[k] - ref[k]) / max(ref[k], med)
        if not np.isfinite(g):
            return float("inf"), k
        if g > worst:
            worst, at = g, k
    return worst, at


def gaps(got: dict, ref: dict) -> dict:
    """The numbers compared, with the leaf each was read at."""
    lg = [abs(a - b) / abs(b) for a, b in zip(got["loss"], ref["loss"])]
    loss_gap = max(lg) if all(np.isfinite(lg)) else float("inf")
    g_got, g_ref = _leaves(got["grad"]), _leaves(ref["grad"])
    c_got, c_ref = _leaves(got["change"]), _leaves(ref["change"])
    if set(g_got) != set(g_ref) or set(c_got) != set(c_ref):
        raise ValueError("program and reference leaves differ: "
                         f"{sorted(set(g_got) ^ set(g_ref))}")
    med = float(np.median(list(g_ref.values())))
    moving = [k for k in c_ref if g_ref[k] >= NOUGHT * med]
    grad_gap, grad_at = _worst(g_got, g_ref, list(g_ref))
    change_gap, change_at = _worst(c_got, c_ref, moving)
    grad_median_gap = float(np.median(
        [abs(g_got[k] - g_ref[k]) / max(g_ref[k], med) for k in g_ref]))
    return {"loss_gap": loss_gap, "grad_gap": grad_gap,
            "grad_median_gap": grad_median_gap, "change_gap": change_gap,
            "grad_leaf": grad_at, "change_leaf": change_at,
            "left_out": sorted(set(c_ref) - set(moving))}


def judge(g: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {number: {"value", "limit"}}) over the numbers that the
    cell's ``limits`` name."""
    checks = {k: {"value": g[k], "limit": v} for k, v in limits.items()}
    ok = all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return ok, checks
