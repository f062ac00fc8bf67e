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

A model that routes tokens to experts is judged under the routing the
program applied: each checked step reports its choices (``routing``:
``experts`` and ``kept``, ``(moe_layers, tokens, top_k)`` in the batch's
token order), and the reference applies exactly those.  Four more
numbers, read only then:

* ``route_gap``: the share of the program's kept assignments, over the
  checked steps, whose expert the reference ranks below its own k-th
  largest float32 router logit by more than ``ROUTE_TIE`` (1 where the
  program kept none);
* ``kept_gap``: the share of the program's assignments whose ``kept``
  differs from the capacity that the reference's model applies to the
  program's experts (an exact comparison);
* ``route_margin``: the largest such distance, in logits;
* ``dropped_share``: the share of the program's assignments that its
  capacity dropped.

A routed cell compares ``ROUTED_NUMBERS`` at least.

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
# The distance, in router logits, that separates a near-tie from a wrong
# choice: the program's kept expert counts against ``route_gap`` where the
# reference's float32 logits rank it more than this below their own k-th.
# It is no bound on what bfloat16 does at depth.  On the CPU's tiny routed
# cell (2 layers) the program reads at most 0.42 over 18 seeds, so
# ``route_gap`` is 0 there; at Granite widths and 8 layers on a TPU v5e
# the sound program reads margins of 2.7 - 4.1 logits and the bfloat16
# reference up to 4.6, so a sound program has a small nonzero
# ``route_gap`` in such a cell and the cell's own limit on that share
# decides.  A wrong expert lies logits down: the routing fault puts 49% or
# more of the choices past this.  PERF.md section 2 gives the readings.
ROUTE_TIE = 1.0
# Compared by every routed cell: which experts, and which choices dropped.
ROUTED_NUMBERS = ("route_gap", "kept_gap")


def reference_module(conf: dict):
    """The configuration's plain reference: ``references/<reference>.py``."""
    return importlib.import_module(
        f"{__package__}.references.{conf['reference']}")


def require_routing(conf: dict, limits: dict, got: dict) -> None:
    """Raise where the configuration's reference routes tokens to experts
    and the program's first steps reported no routing, or the cell's
    limits leave out one of ``ROUTED_NUMBERS``: such a cell can only end
    in an error, never in ``correct``."""
    if not getattr(reference_module(conf), "ROUTED", False):
        return
    if "routing" not in got:
        raise ValueError(
            "a routed reference needs the program's routing of each "
            "checked step, and the step reported none")
    missing = [k for k in ROUTED_NUMBERS if k not in limits]
    if missing:
        raise ValueError(f"a routed cell compares {missing}")


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
    AdamW steps, and the numbers the comparison needs.

    A reference module that declares ``ROUTED`` routes tokens to experts
    over ``groups`` groups of tokens (one device's share of one
    microbatch each), and its ``run`` takes the routing of every step."""

    def __init__(self, conf: dict, opt: dict, *, mode="fp32", groups=1):
        ref = reference_module(conf)
        self.routed = bool(getattr(ref, "ROUTED", False))
        self.model = ref.Model(conf)
        self.shapes = self.model.shapes()
        if self.routed:
            self.step = ref.make_step(self.model, opt, mode=mode,
                                      groups=groups)
        else:
            self.step = ref.make_step(self.model, opt, mode=mode)
        self.change = weights.reference_change_norms_fn(self.shapes)
        self.init = jax.jit(lambda k: weights.logical(k, self.shapes))
        self.zeros = jax.jit(lambda: {n: jnp.zeros(
            ((L,) if L else ()) + tuple(s), jnp.float32)
            for n, (s, L) in self.shapes.items()})

    def _routing(self, routing, batches, fault):
        """(how the routed step routes, its routing argument each step)."""
        if routing is None:
            raise ValueError(
                "a routed reference needs the program's routing of each "
                "checked step, and the step reported none")
        if isinstance(routing, str):
            if routing != "own" or fault in ("route", "drop"):
                raise ValueError(f"routing {routing!r} with fault {fault!r}")
            return "own", [None] * len(batches)
        if len(routing) != len(batches):
            raise ValueError(f"routing of {len(routing)} steps for "
                             f"{len(batches)} batches")
        out = []
        for r, toks in zip(routing, batches):
            e, kept = np.asarray(r["experts"]), np.asarray(r["kept"])
            want = (self.model.moe_layers, toks.size, self.model.k)
            if e.shape != want or kept.shape != want:
                raise ValueError(f"routing {e.shape} / {kept.shape}, the "
                                 f"batch needs {want}")
            out.append({"experts": jnp.asarray(e, jnp.int32),
                        "kept": jnp.asarray(kept, bool)})
        return {"route": "swap", "drop": "drop"}.get(fault, "given"), out

    def run(self, seed: int, batches, fault: str = "none",
            routing=None) -> dict:
        """``batches``: host token arrays, one per step.  ``fault``:
        ``none``, ``half`` (``fault_weights``) or, routed, ``route``: one
        kept expert of each token swapped for the one this reference ranks
        last, or ``drop``: the capacity counted in reverse token order.
        ``routing``, routed only: the program's routing of each
        step, or ``"own"`` for the reference's own choices at its
        precision (where it stands in the program's place)."""
        if routing is not None and not self.routed:
            raise ValueError("routing handed to a reference without experts")
        key = weights.base_key(seed)
        p, mu, nu = self.init(key), self.zeros(), self.zeros()
        losses, grad, applied = [], None, []
        how, routes = (self._routing(routing, batches, fault) if self.routed
                       else (None, [None] * len(batches)))
        for t, (toks, r) in enumerate(zip(batches, routes)):
            w, denom = fault_weights(
                "none" if fault in ("route", "drop") else fault,
                (toks.shape[0], toks.shape[1] - 1))
            args = (p, mu, nu, jnp.asarray(toks), jnp.asarray(w),
                    jnp.float32(denom), jnp.float32(t))
            if self.routed:
                p, mu, nu, loss, g, route = self.step(*args, r, route=how)
                applied.append(jax.tree.map(np.asarray, route))
            else:
                p, mu, nu, loss, g = self.step(*args)
            losses.append(loss)
            if grad is None:
                grad = g
        change = self.change(p, key)
        out = {"loss": [float(x) for x in losses],
               "grad": jax.tree.map(np.asarray, grad),
               "change": jax.tree.map(np.asarray, change)}
        if self.routed:
            out["routing"] = [{"experts": a["experts"], "kept": a["kept"]}
                              for a in applied]
            out["margin"] = [a["margin"] for a in applied]
            out["capacity"] = [a["capacity"] for a in applied]
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


def route_numbers(got: list, ref: dict) -> dict:
    """``route_gap``, ``kept_gap``, ``route_margin`` and ``dropped_share``
    of the program's routing ``got`` (one dict a step) against the
    reference that applied it."""
    for a, b in zip(got, ref["routing"], strict=True):
        if not (np.array_equal(a["experts"], b["experts"])
                and np.array_equal(a["kept"], b["kept"])):
            raise ValueError("the reference applied another routing than "
                             "the program reported")
    kept = np.concatenate([np.asarray(r["kept"], bool).ravel() for r in got])
    capacity = np.concatenate([np.asarray(c, bool).ravel()
                               for c in ref["capacity"]])
    margin = np.concatenate([np.asarray(m, np.float64).ravel()
                             for m in ref["margin"]])[kept]
    # a step that keeps no choice at all is as wrong as one can be
    return {"route_gap": float(np.mean(margin > ROUTE_TIE))
            if margin.size else 1.0,
            "kept_gap": float(np.mean(kept != capacity)),
            "route_margin": float(margin.max()) if margin.size else 0.0,
            "dropped_share": float(np.mean(~kept))}


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
    out = {"loss_gap": loss_gap, "grad_gap": grad_gap,
           "grad_median_gap": grad_median_gap, "change_gap": change_gap,
           "grad_leaf": grad_at, "change_leaf": change_at,
           "left_out": sorted(set(c_ref) - set(moving))}
    if "margin" in ref and "routing" in got:
        out.update(route_numbers(got["routing"], ref))
    return out


def judge(g: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {number: {"value", "limit"}}) over the numbers that the
    cell's ``limits`` name."""
    checks = {k: {"value": g[k], "limit": v} for k, v in limits.items()}
    ok = all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return ok, checks
