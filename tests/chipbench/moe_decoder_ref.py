"""Plain float32 reference of the program's sparse-expert decoder as it
stands (``repro.models.transformer`` with ``repro.models.moe``), for the
tiny routed cell of these tests.  It is no published model, so it lives
here and not under ``benchmarks/chip/references``.

The decoder of ``references/qwen2.py`` without q, k, v biases, each
layer's MLP a routed mixture of SwiGLU experts:

* router logits ``h @ router``, one per expert, and their softmax;
* each token's ``k`` assignments are the program's, handed over step by
  step (``route="given"``), or this model's own (``route="own"``, where it
  stands in the program's place): the ``k`` largest logits, each dropped
  past its expert's capacity ``max(1, int(capacity_factor * N * k / E))``
  in token order within a group of ``N`` tokens;
* gates: the chosen experts' probabilities over their sum; a dropped
  assignment adds nothing;
* load balance, per group and layer, ``E * sum_e P_e * f_e * coef``: ``P``
  the group's mean probabilities, ``f`` each expert's share of the
  group's choices, dropped or not.  Its mean over groups, summed over
  layers and divided by their number, is added to the mean token cross
  entropy.

A group is the tokens one device takes in one microbatch: ``groups``
contiguous blocks of rows.  Every expert runs on every token, weighted by
its gate or by nought, so no dispatch stands between the routing and the
result.  ``route="swap"`` is the routing fault: the given routing with
the first kept expert of each token swapped for the one this model's
logits rank last, and dropped by this model's capacity.
``route="drop"`` is the drop fault: the given experts with the capacity
counted in reverse token order.  Every step also returns, for each
assignment, how far this model's logits (float32 in the reference
proper) rank it below their own k-th largest (``margin``, 0 inside its
own top k), and whether this model's capacity keeps the applied experts
(``capacity``).

Nothing here imports the program.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.chip.references.qwen2 import (
    _attention, _mm, _rms, adamw_update, leaf_norms)

ROUTED = True


class Model:
    """Shapes and math of one configuration file."""

    def __init__(self, conf: dict):
        self.D = conf["hidden_size"]
        self.H = conf["num_attention_heads"]
        self.Hkv = conf["num_key_value_heads"]
        self.hd = conf["assumed"]["head_dim"]
        self.F = conf["intermediate_size"]
        self.V = conf["vocab_size"]
        self.L = conf["num_hidden_layers"]
        self.E = conf["num_local_experts"]
        self.k = conf["num_experts_per_tok"]
        self.capacity = conf["capacity_factor"]
        self.aux_coef = conf["router_aux_loss_coef"]
        self.eps = conf["rms_norm_eps"]
        self.theta = conf["rope_theta"]
        self.moe_layers = self.L
        if (conf["tie_word_embeddings"] or conf["hidden_act"] != "silu"
                or conf["assumed"]["qkv_bias"]):
            raise ValueError("reference covers an untied head, SwiGLU "
                             "experts and no q, k, v biases")

    def shapes(self) -> dict[str, tuple[tuple[int, ...], int | None]]:
        """name -> (per-layer shape, layers or None)."""
        D, F, E, L = self.D, self.F, self.E, self.L
        return {
            "ln1": ((D,), L), "wq": ((D, self.H * self.hd), L),
            "wk": ((D, self.Hkv * self.hd), L),
            "wv": ((D, self.Hkv * self.hd), L),
            "wo": ((self.H * self.hd, D), L), "ln2": ((D,), L),
            "moe_router": ((D, E), L), "moe_w1": ((E, D, F), L),
            "moe_w3": ((E, D, F), L), "moe_w2": ((E, F, D), L),
            "emb": ((self.V, D), None), "final_ln": ((D,), None),
            "head": ((D, self.V), None),
        }

    def layered(self) -> dict[str, bool]:
        return {n: L is not None for n, (_, L) in self.shapes().items()}


def _capacity(m: Model, experts, groups: int, reverse: bool = False):
    """Which of ``experts`` (N, k) fit their expert's capacity: a choice's
    rank among its group's choices of the same expert, in token order (in
    reverse token order for the drop fault)."""
    N = experts.shape[0]
    n = N // groups
    flat = experts.reshape(groups, n * m.k)
    if reverse:
        flat = flat[:, ::-1]
    hit = (flat[..., None] == jnp.arange(m.E)).astype(jnp.int32)
    rank = jnp.sum((jnp.cumsum(hit, axis=1) - 1) * hit, axis=-1)
    keep = rank < max(1, int(m.capacity * n * m.k / m.E))
    if reverse:
        keep = keep[:, ::-1]
    return keep.reshape(N, m.k)


def _moe(m: Model, p, h, routing, route, groups, mode):
    """One routed MLP over h (B, T, D); returns (out, aux, applied)."""
    B, T, D = h.shape
    x = h.reshape(B * T, D)
    logits = _mm("nd,de->ne", x, p["moe_router"], mode)
    probs = jax.nn.softmax(logits, axis=-1)
    own = lax.stop_gradient(logits)
    top, top_e = lax.top_k(own, m.k)
    if route == "own":
        experts, kept = top_e, _capacity(m, top_e, groups)
    else:
        experts, kept = routing["experts"], routing["kept"]
        if route == "swap":
            first = kept & (jnp.cumsum(kept, axis=-1) == 1)
            experts = jnp.where(first, jnp.argmin(own, axis=-1)[:, None],
                                experts)
            kept = _capacity(m, experts, groups)
        elif route == "drop":
            kept = _capacity(m, experts, groups, reverse=True)
    gate = jnp.take_along_axis(probs, experts, axis=-1)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
    hot = (experts[..., None] == jnp.arange(m.E)).astype(jnp.float32)
    combine = jnp.einsum("nk,nke->ne", gate * kept, hot, precision="highest")
    g = _mm("nd,edf->nef", x, p["moe_w1"], mode)
    u = _mm("nd,edf->nef", x, p["moe_w3"], mode)
    act = jax.nn.silu(g) * u * combine[..., None]
    out = _mm("nef,efd->nd", act, p["moe_w2"], mode)

    n = B * T // groups
    mean_p = probs.reshape(groups, n, m.E).mean(axis=1)
    share = hot.reshape(groups, n * m.k, m.E).sum(axis=1) / (n * m.k)
    aux = jnp.mean(m.E * jnp.sum(mean_p * share, axis=-1)) * m.aux_coef
    margin = top[:, -1:] - jnp.take_along_axis(own, experts, axis=-1)
    applied = {"experts": experts, "kept": kept,
               "margin": jnp.maximum(margin, 0.0),
               "capacity": _capacity(m, experts, groups)}
    return out.reshape(B, T, D), aux, applied


def loss_fn(m: Model, params, tokens, weights, denom, routing, *,
            route: str, groups: int, mode: str = "fp32"):
    """(weighted token cross entropy over ``denom`` plus the load-balance
    term, routing applied).  ``routing``: ``experts`` and ``kept``, each
    (layers, B * T, k), or None with ``route="own"``."""
    if tokens.shape[0] % groups:
        raise ValueError(f"{tokens.shape[0]} rows in {groups} groups")
    x = params["emb"][tokens]
    layer_names = [n for n, (_, L) in m.shapes().items() if L]

    @jax.checkpoint
    def body(x, xs):
        p, r = xs
        x = x + _attention(m, p, _rms(x, p["ln1"], m.eps), mode)
        out, aux, applied = _moe(m, p, _rms(x, p["ln2"], m.eps), r, route,
                                 groups, mode)
        return x + out, (aux, applied)

    x, (aux, applied) = lax.scan(
        body, x, ({n: params[n] for n in layer_names}, routing))
    x = _rms(x, params["final_ln"], m.eps)
    logits = _mm("btd,dv->btv", x[:, :-1], params["head"], mode)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, tokens[:, 1:, None], -1)[..., 0]
    nll = jnp.sum((lse - picked) * weights) / denom
    return nll + jnp.sum(aux) / m.L, applied


def make_step(m: Model, opt: dict, *, mode: str, groups: int = 1):
    """jit'd ``(p, mu, nu, tokens, weights, denom, t, routing, route) ->
    (p, mu, nu, loss, grad leaf norms, applied routing and margins)``."""
    layered = m.layered()

    def step(p, mu, nu, tokens, weights, denom, t, routing, route):
        (loss, applied), g = jax.value_and_grad(
            partial(loss_fn, m, route=route, groups=groups, mode=mode),
            has_aux=True)(p, tokens, weights, denom, routing)
        p, mu, nu = adamw_update(p, g, mu, nu, t, opt, layered)
        return p, mu, nu, loss, leaf_norms(g, layered), applied

    return jax.jit(step, donate_argnums=(0, 1, 2), static_argnames=("route",))
