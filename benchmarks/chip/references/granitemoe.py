"""Plain float32 reference of a GraniteMoe decoder and of AdamW.

Written from the published description (hf ``GraniteMoeForCausalLM``,
``model_type: granitemoe``), with the configuration file's values:

* token embeddings times ``embedding_multiplier``;
* pre-norm blocks (RMSNorm), each branch added to the residual stream
  times ``residual_multiplier``;
* causal GQA attention without biases, RoPE on half-split heads
  (``rotate_half``), scores times ``attention_multiplier`` (in place of
  ``1 / sqrt(head_dim)``);
* a routed mixture of SwiGLU experts, dropless (hf's
  ``GraniteMoeParallelExperts`` has no capacity): router logits
  ``h @ router``; each token's ``k`` largest; gates the softmax over those
  ``k`` logits; every chosen expert's ``down(silu(gate(x)) * up(x))``
  weighed by its gate;
* the LM head tied to the embedding, logits over ``logits_scaling``, mean
  token cross entropy over the next tokens;
* the load balance of hf's ``load_balancing_loss_func`` times
  ``router_aux_loss_coef``: ``E * sum_e P_e * f_e``, ``P`` the mean router
  probabilities and ``f`` each expert's chosen tokens over the tokens.
  Departure, shared with the program: it is taken per layer and per group
  (the tokens one device takes in one microbatch) and averaged, where hf
  pools every layer's tokens before the product.

The RMSNorm gain is held as ``1 + scale``, as in ``qwen2.py``.

The routing is the program's, handed over step by step (``route="given"``),
or this model's own (``route="own"``, where it stands in the program's
place).  Every expert runs on every token, weighted by its gate or by
nought, so no dispatch stands between the routing and the result.
``route="swap"`` is the routing fault: each token's first kept expert
swapped for the one this model's logits rank last.  ``route="drop"`` is
the drop fault: the given experts with each expert's choices past an
even share (``N * k / E`` in token order) dropped, as a capacity would.
Every step also returns, for each choice, how far this model's float32
logits rank it below their own k-th largest (``margin``, 0 inside its own
top k), and what this model keeps of the applied experts (``capacity``:
all of them, since it drops none).

Nothing here imports the program.  Every matmul runs at
``Precision.HIGHEST``; attention runs in query blocks and each layer is
rematerialised.  ``mode="fp8"`` is the control and ``mode="bf16"`` the
witness, as in ``qwen2.py``.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.chip.references.qwen2 import (
    Q_BLOCK, _mm, _rms, _rope, adamw_update, leaf_norms)

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
        self.aux_coef = conf["router_aux_loss_coef"]
        self.eps = conf["rms_norm_eps"]
        self.theta = conf["rope_theta"]
        self.emb_mult = conf["embedding_multiplier"]
        self.attn_mult = conf["attention_multiplier"]
        self.res_mult = conf["residual_multiplier"]
        self.logits_scaling = conf["logits_scaling"]
        self.moe_layers = self.L
        if (not conf["tie_word_embeddings"] or conf["hidden_act"] != "silu"
                or conf["assumed"]["qkv_bias"]
                or conf["capacity_factor"] is not None):
            raise ValueError("reference covers a tied head, SwiGLU experts, "
                             "no q, k, v biases and dropless routing")

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
        }

    def layered(self) -> dict[str, bool]:
        return {n: L is not None for n, (_, L) in self.shapes().items()}


def _attention(m: Model, p, h, mode):
    B, T, _ = h.shape

    def proj(name, heads):
        return _mm("btd,dx->btx", h, p[name], mode).reshape(B, T, heads,
                                                            m.hd)

    q = _rope(proj("wq", m.H), m.theta)
    k = _rope(proj("wk", m.Hkv), m.theta)
    v = proj("wv", m.Hkv)
    rep = m.H // m.Hkv  # query head i reads kv head i // rep
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    outs = []
    for lo in range(0, T, Q_BLOCK):
        hi = min(T, lo + Q_BLOCK)
        s = _mm("bqhd,bkhd->bhqk", q[:, lo:hi], k, mode) * m.attn_mult
        causal = jnp.arange(lo, hi)[:, None] >= jnp.arange(T)[None, :]
        s = jnp.where(causal[None, None], s, -jnp.inf)
        outs.append(_mm("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v,
                        mode))
    o = jnp.concatenate(outs, axis=1).reshape(B, T, m.H * m.hd)
    return _mm("btx,xd->btd", o, p["wo"], mode)


def _even_share(m: Model, experts, groups: int):
    """Which of ``experts`` (N, k) lie within ``N * k / E`` choices of
    their expert, counted in token order within each group: the drop
    fault's drops."""
    N = experts.shape[0]
    n = N // groups
    flat = experts.reshape(groups, n * m.k)
    hit = (flat[..., None] == jnp.arange(m.E)).astype(jnp.int32)
    rank = jnp.sum((jnp.cumsum(hit, axis=1) - 1) * hit, axis=-1)
    return (rank < max(1, n * m.k // m.E)).reshape(N, m.k)


def _moe(m: Model, p, h, routing, route, groups, mode):
    """One routed MLP over h (B, T, D); returns (out, aux, applied)."""
    B, T, D = h.shape
    x = h.reshape(B * T, D)
    logits = _mm("nd,de->ne", x, p["moe_router"], mode)
    own = lax.stop_gradient(logits)
    top, top_e = lax.top_k(own, m.k)
    if route == "own":
        experts, kept = top_e, jnp.ones(top_e.shape, bool)
    else:
        experts, kept = routing["experts"], routing["kept"]
        if route == "swap":
            first = kept & (jnp.cumsum(kept, axis=-1) == 1)
            experts = jnp.where(first, jnp.argmin(own, axis=-1)[:, None],
                                experts)
            kept = jnp.ones(experts.shape, bool)
        elif route == "drop":
            kept = _even_share(m, experts, groups)
    gate = jax.nn.softmax(jnp.take_along_axis(logits, experts, axis=-1),
                          axis=-1)
    hot = (experts[..., None] == jnp.arange(m.E)).astype(jnp.float32)
    combine = jnp.einsum("nk,nke->ne", gate * kept, hot, precision="highest")
    g = _mm("nd,edf->nef", x, p["moe_w1"], mode)
    u = _mm("nd,edf->nef", x, p["moe_w3"], mode)
    act = jax.nn.silu(g) * u * combine[..., None]
    out = _mm("nef,efd->nd", act, p["moe_w2"], mode)

    n = B * T // groups
    mean_p = jax.nn.softmax(logits, axis=-1).reshape(groups, n, m.E).mean(1)
    share = hot.reshape(groups, n * m.k, m.E).sum(axis=1) / n
    aux = jnp.mean(m.E * jnp.sum(mean_p * share, axis=-1)) * m.aux_coef
    margin = top[:, -1:] - jnp.take_along_axis(own, experts, axis=-1)
    applied = {"experts": experts, "kept": kept,
               "margin": jnp.maximum(margin, 0.0),
               "capacity": jnp.ones(experts.shape, bool)}
    return out.reshape(B, T, D), aux, applied


def loss_fn(m: Model, params, tokens, weights, denom, routing, *,
            route: str, groups: int, mode: str = "fp32"):
    """(weighted token cross entropy over ``denom`` plus the load-balance
    term, routing applied).  ``routing``: ``experts`` and ``kept``, each
    (layers, B * T, k), or None with ``route="own"``."""
    if tokens.shape[0] % groups:
        raise ValueError(f"{tokens.shape[0]} rows in {groups} groups")
    x = params["emb"][tokens] * m.emb_mult
    layer_names = [n for n, (_, L) in m.shapes().items() if L]

    @jax.checkpoint
    def body(x, xs):
        p, r = xs
        x = x + m.res_mult * _attention(m, p, _rms(x, p["ln1"], m.eps), mode)
        out, aux, applied = _moe(m, p, _rms(x, p["ln2"], m.eps), r, route,
                                 groups, mode)
        return x + m.res_mult * out, (aux, applied)

    x, (aux, applied) = lax.scan(
        body, x, ({n: params[n] for n in layer_names}, routing))
    x = _rms(x, params["final_ln"], m.eps)
    logits = _mm("btd,vd->btv", x[:, :-1], params["emb"], mode) / (
        m.logits_scaling)
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
