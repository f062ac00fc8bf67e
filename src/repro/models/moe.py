"""Mixture-of-Experts FFN with top-k routing and expert parallelism.

Two dispatches.  Dropless (``capacity_factor=None``, one expert shard):
every choice runs -- the token x k choices are sorted into expert order,
the expert matmuls run as grouped matmuls over the uneven groups
(``jax.lax.ragged_dot``), and the results go back to choice order and
are combined with the gates.  Capacity (GShard-style [Lepikhin et al.
2020], adapted to shard_map): tokens are ranked within their expert via a
sort-based position count (no (N*k, E, C) one-hot tensors), scattered
into an (E, C, D) buffer, exchanged over the EP mesh axis with two
all_to_alls, and combined with router weights; choices past an expert's
capacity are dropped.

Composition with the paper (Fig. 5): expert weights are Shard(0) on the
expert dim over the EP axis, *then* RaggedShard-packed over the FSDP axes —
the (RaggedShard, Shard(0)) = StridedRaggedShard case.

The routing (the chosen experts, and the sort and counts derived from
them) is named ``ROUTING`` for ``jax.checkpoint``: a policy that saves it
(``DecoderLM.remat_policy``) lets the recomputed forward reuse the
forward's experts instead of routing again.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from .. import spans

#: checkpoint name of the integer routing residuals
ROUTING = "moe.routing"


def _positions_within_expert(flat_e, n_experts):
    """rank of each assignment among same-expert assignments (stable)."""
    m = flat_e.shape[0]
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    idx = jnp.arange(m, dtype=jnp.int32)
    is_start = jnp.concatenate(
        [jnp.ones((1,), bool), sorted_e[1:] != sorted_e[:-1]]
    )
    run_start = jax.lax.associative_scan(
        jnp.maximum, jnp.where(is_start, idx, 0)
    )
    rank_sorted = idx - run_start
    rank = jnp.zeros((m,), jnp.int32).at[order].set(rank_sorted)
    return rank


def _experts(p, prefix, h, mm):
    """The expert MLP (SwiGLU, or SiLU without ``w3``) over ``h``, with
    ``mm(h, w)`` the per-expert matmul."""
    def w(name):
        return p[prefix + name].astype(h.dtype)

    if prefix + "w3" in p:
        a = jax.nn.silu(mm(h, w("w1"))) * mm(h, w("w3"))
    else:
        a = jax.nn.silu(mm(h, w("w1")))
    return mm(a, w("w2"))


def _dropless(cfg, p, xf, top_e, gates, prefix):
    """Every choice through its expert, gate-weighted: (N, D) output."""
    N, D = xf.shape
    E, k = cfg.n_experts, cfg.top_k
    with jax.named_scope(spans.MOE_DISPATCH):
        flat_e = top_e.reshape(-1)
        order = checkpoint_name(
            jnp.argsort(flat_e, stable=True).astype(jnp.int32), ROUTING)
        sizes = checkpoint_name(
            jnp.zeros((E,), jnp.int32).at[flat_e].add(1), ROUTING)
        # the position of each choice in expert order
        back = checkpoint_name(jnp.zeros_like(order).at[order].set(
            jnp.arange(N * k, dtype=jnp.int32)), ROUTING)
        xs = xf.at[order // k].get(mode="promise_in_bounds")
    with jax.named_scope(spans.MOE_EXPERTS):
        ys = _experts(p, prefix, xs,
                      lambda h, w: lax.ragged_dot(h, w, sizes))
    with jax.named_scope(spans.MOE_DISPATCH):
        y = ys.at[back].get(mode="promise_in_bounds", unique_indices=True)
        return jnp.einsum("nkd,nk->nd", y.reshape(N, k, D),
                          gates.astype(xf.dtype))


def moe_ffn(cfg, p, x, *, ep_axis=None, ep=1, prefix="moe_"):
    """x: (B, T, D) local tokens.  Returns (out, aux_loss, routing):
    ``routing`` holds ``experts`` (int32) and ``kept`` (bool, false where
    capacity dropped the choice), each (B * T, k) in token order.

    p[f"{prefix}router"]: (D, E) replicated over EP.
    p[f"{prefix}w1"/"w2"/"w3"]: (E_local, D, F) / (E_local, F, D) / (E_local, D, F).
    """
    B, T, D = x.shape
    N = B * T
    E = cfg.n_experts
    k = cfg.top_k
    e_loc = E // ep
    if cfg.moe_aux_form not in ("switch", "mixtral"):
        raise ValueError(f"moe_aux_form {cfg.moe_aux_form!r}")
    dropless = cfg.capacity_factor is None
    if dropless and ep > 1:
        raise ValueError("dropless routing (capacity_factor=None) runs on "
                         "one expert shard; ep > 1 needs a capacity")

    xf = x.reshape(N, D)
    with jax.named_scope(spans.MOE_ROUTE):
        logits = (xf @ p[prefix + "router"].astype(x.dtype)).astype(
            jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        top_e = checkpoint_name(lax.top_k(probs, k)[1], ROUTING)  # (N, k)
        # gates read from probs at the named experts, so the backward needs
        # no second top-k
        top_p = jnp.take_along_axis(probs, top_e, axis=-1)
        top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

        # load-balance aux loss: E * sum_e f_e * P_e, f_e over the choices
        # ("switch") or over the tokens ("mixtral", k times that)
        me = probs.mean(axis=0)
        share = N * k if cfg.moe_aux_form == "switch" else N
        ce = jnp.zeros((E,), jnp.float32).at[top_e.reshape(-1)].add(1.0) / share
        aux = E * jnp.sum(me * ce) * cfg.moe_aux_coef

    if dropless:
        out = _dropless(cfg, p, xf, top_e, top_p, prefix)
        kept = jnp.ones((N, k), bool)
        return out.reshape(B, T, D), aux, {"experts": top_e, "kept": kept}

    cap = max(1, int(cfg.capacity_factor * N * k / E))
    if T == 1:
        # decode: the per-expert buffer is tiny (N = batch), so run dropless
        # -- capacity-dropping at decode would make generation depend on
        # which other requests share the batch (and diverge from prefill)
        cap = max(cap, N)
    with jax.named_scope(spans.MOE_DISPATCH):
        flat_e = top_e.reshape(-1)                    # (N*k,)
        flat_w = top_p.reshape(-1).astype(x.dtype)
        flat_tok = jnp.repeat(jnp.arange(N, dtype=jnp.int32), k)
        rank = checkpoint_name(_positions_within_expert(flat_e, E), ROUTING)
        keep = rank < cap
        slot = flat_e * cap + jnp.minimum(rank, cap - 1)  # (N*k,)

        # dispatch: (E*cap, D)
        contrib = jnp.where(keep[:, None], xf[flat_tok], 0).astype(x.dtype)
        buf = jnp.zeros((E * cap, D), x.dtype).at[
            jnp.where(keep, slot, E * cap - 1)
        ].add(jnp.where(keep[:, None], contrib, 0))

        if ep_axis is not None and ep > 1:
            # (ep, e_loc*cap, D) -> exchange -> (e_loc, ep*cap, D)
            buf = buf.reshape(ep, e_loc * cap, D)
            buf = lax.all_to_all(buf, ep_axis, split_axis=0, concat_axis=0,
                                 tiled=False)
            # buf now (ep, e_loc*cap, D) where leading dim = source device
            h = buf.reshape(ep, e_loc, cap, D).transpose(1, 0, 2, 3)
            h = h.reshape(e_loc, ep * cap, D)
        else:
            h = buf.reshape(e_loc, cap, D)

    # expert MLP batched over local experts
    with jax.named_scope(spans.MOE_EXPERTS):
        out_e = _experts(p, prefix, h,
                         lambda h, w: jnp.einsum("ecd,edf->ecf", h, w))

    with jax.named_scope(spans.MOE_DISPATCH):
        if ep_axis is not None and ep > 1:
            out_e = out_e.reshape(e_loc, ep, cap, D).transpose(1, 0, 2, 3)
            out_e = out_e.reshape(ep, e_loc * cap, D)
            out_e = lax.all_to_all(out_e, ep_axis, split_axis=0,
                                   concat_axis=0, tiled=False)
            out_flat = out_e.reshape(E * cap, D)
        else:
            out_flat = out_e.reshape(E * cap, D)

        gathered = out_flat[slot] * (flat_w * keep)[:, None]
        out = jnp.zeros((N, D), x.dtype).at[flat_tok].add(gathered)
    return out.reshape(B, T, D), aux, {"experts": top_e,
                                       "kept": keep.reshape(N, k)}
