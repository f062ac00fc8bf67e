"""Model-layer primitives shared by every architecture.

Conventions:
  * linear weights are (d_in, d_out); y = x @ w
  * attention tensors are (B, T, H, hd) at rest, (B, H, T, hd) in flight
  * ``tp_axis`` is the mesh axis for tensor parallelism or None (pure FSDP);
    collectives are no-ops when it is None
  * softmax/normalizer math runs in float32 regardless of compute dtype
  * 32k-token prefill never materializes (T x T) logits: attention is chunked
    with an online softmax (lax.scan over KV blocks)
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from ..kernels import ops

NEG_INF = -1e30


def dense(x, w):
    """``y = x @ w`` with weight-format dispatch: a plain array casts to
    the activation dtype (op-for-op the pre-helper spelling, bitwise
    neutral); a ``QuantTensor`` (gathered-but-still-int8 q8_block weight,
    serve quant mode) routes through the int8 x int8 GEMM so the dense
    weight never materializes."""
    if isinstance(w, ops.QuantTensor):
        return ops.q8_matmul(x, w.codes, w.scales, w.block)
    return x @ w.astype(x.dtype)


def to_dense(w, dtype):
    """Materialize a weight in ``dtype`` -- the fallback for call sites
    that must slice or transpose the weight itself (replicated-KV head
    slicing, tied embeddings): QuantTensors take one fused per-tensor
    dequant, plain arrays just cast."""
    if isinstance(w, ops.QuantTensor):
        k, n = w.shape
        return ops.dequantize_into(
            w.codes.reshape(-1), w.scales, w.block,
            out_dtype=dtype).reshape(k, n)
    return w.astype(dtype)


def psum(x, axis):
    return lax.psum(x, axis) if axis else x


def reduce_out(x, axis, sp: bool):
    """Row-parallel output reduction: plain psum, or (sequence parallelism)
    a fused reduce-scatter over the sequence dim -- activations between
    blocks stay seq-sharded over the TP axis."""
    if not axis:
        return x
    if sp:
        return lax.psum_scatter(x, axis, scatter_dimension=1, tiled=True)
    return lax.psum(x, axis)


def gather_seq(x, axis, sp: bool):
    """Inverse of reduce_out's scatter: all-gather the sequence dim."""
    if axis and sp:
        return lax.all_gather(x, axis, axis=1, tiled=True)
    return x


def pmax(x, axis):
    return lax.pmax(x, axis) if axis else x


def axis_index(axis):
    return lax.axis_index(axis) if axis else 0


def rms_norm(x, scale, eps: float = 1e-6):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return ((x32 * lax.rsqrt(var + eps)) * (1.0 + scale.astype(jnp.float32))).astype(dt)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope(x, positions, theta: float):
    """x: (B, H, T, hd); positions: (B, T) int32."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[:, None, :, None].astype(jnp.float32) * freqs  # (B,1,T,half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Chunked online-softmax attention (GQA, causal/window/softcap/cross)
# ---------------------------------------------------------------------------

def chunked_attention(
    q,  # (B, Hq, Tq, hd)
    k,  # (B, Hkv, Tk, hd)
    v,  # (B, Hkv, Tk, hd)
    *,
    q_pos=None,       # (B, Tq) int32 positions of queries (None -> non-causal)
    kv_pos=None,      # (B, Tk)
    kv_valid=None,    # (B, Tk) bool (e.g. cache occupancy)
    window=None,      # int | traced scalar | None
    softcap=None,
    chunk: int = 1024,
    scale=None,       # score multiplier (None -> 1 / sqrt(hd))
):
    B, Hq, Tq, hd = q.shape
    Hkv, Tk = k.shape[1], k.shape[2]
    group = Hq // Hkv
    if scale is None:
        scale = 1.0 / math.sqrt(hd)

    chunk = min(chunk, Tk)
    nc = -(-Tk // chunk)
    pad = nc * chunk - Tk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        kv_valid = (
            jnp.pad(kv_valid, ((0, 0), (0, pad)))
            if kv_valid is not None
            else jnp.pad(jnp.ones((B, Tk), bool), ((0, 0), (0, pad)))
        )
        if kv_pos is not None:
            kv_pos = jnp.pad(kv_pos, ((0, 0), (0, pad)))
    elif kv_valid is None:
        kv_valid = jnp.ones((B, nc * chunk), bool)

    qg = q.reshape(B, Hkv, group, Tq, hd)
    kc = k.reshape(B, Hkv, nc, chunk, hd).transpose(2, 0, 1, 3, 4)
    vc = v.reshape(B, Hkv, nc, chunk, hd).transpose(2, 0, 1, 3, 4)
    validc = kv_valid.reshape(B, nc, chunk).transpose(1, 0, 2)
    posc = (
        kv_pos.reshape(B, nc, chunk).transpose(1, 0, 2)
        if kv_pos is not None
        else None
    )

    m0 = jnp.full((B, Hkv, group, Tq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Hkv, group, Tq), jnp.float32)
    a0 = jnp.zeros((B, Hkv, group, Tq, hd), jnp.float32)

    def body(carry, xs):
        m, l, acc = carry
        if posc is not None:
            k_i, v_i, ok_i, pos_i = xs
        else:
            k_i, v_i, ok_i = xs
            pos_i = None
        s = jnp.einsum(
            "bhgqd,bhkd->bhgqk", qg.astype(jnp.float32),
            k_i.astype(jnp.float32),
        ) * scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        mask = ok_i[:, None, None, None, :]
        if pos_i is not None and q_pos is not None:
            qp = q_pos[:, None, None, :, None]
            kp = pos_i[:, None, None, None, :]
            mask = mask & (kp <= qp)
            if window is not None:
                mask = mask & (qp - kp < window)
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhgqk,bhkd->bhgqd", p, v_i.astype(jnp.float32)
        )
        return (m_new, l_new, acc_new), None

    xs = (kc, vc, validc) + ((posc,) if posc is not None else ())
    (m, l, acc), _ = lax.scan(body, (m0, l0, a0), xs)
    out = acc / jnp.maximum(l[..., None], 1e-20)
    return out.reshape(B, Hq, Tq, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# Attention projection block (GQA + TP over heads)
# ---------------------------------------------------------------------------

def tp_head_counts(n_heads: int, n_kv: int, tp: int) -> tuple[int, int, bool]:
    """Local (q_heads, kv_heads_in_weight, kv_replicated).

    tp <= n_kv: KV projections are Shard(1) like Q (kv_heads_in_weight =
    n_kv/tp).  tp > n_kv: KV weights are replicated over the TP axis (they
    live in the `layers_rep` group); each device *computes* only the single
    KV head its q-group needs by slicing the weight (grads recombine via the
    replicated-group psum over "model")."""
    if n_heads % tp:
        raise ValueError(f"n_heads={n_heads} not divisible by tp={tp}")
    if tp <= n_kv:
        if n_kv % tp:
            raise ValueError(f"n_kv_heads={n_kv} not divisible by tp={tp}")
        return n_heads // tp, n_kv // tp, False
    if tp % n_kv:
        raise ValueError(f"tp={tp} not divisible by n_kv_heads={n_kv}")
    return n_heads // tp, n_kv, True


def attention(
    cfg, p, x, *, q_pos, cache=None, cache_index=None, window=None,
    tp_axis=None, tp=1, prefix="", causal=True, sp=False,
):
    """Self-attention with optional ring-buffer KV cache.

    cache: None (training) or dict(k=(B,Hkv,W,hd), v=..., pos=(B,W) int32,
    init -1).  W may be < seq_len (sliding-window ring buffer -- how the
    long_500k decode shape stays sub-linear in memory).  Writes at
    ``cache_index % W``; validity/causality come from the stored positions.
    Returns (out, new_cache)."""
    B, T, D = x.shape
    hd = cfg.hd
    hq, hkv, kv_rep = tp_head_counts(cfg.n_heads, cfg.n_kv_heads, tp)
    if kv_rep:
        group_size = cfg.n_heads // cfg.n_kv_heads
        kv_head = (lax.axis_index(tp_axis) * hq) // group_size
        hkv = 1

    def proj(name, h, kv=False):
        w = p[prefix + name]
        b = (p[prefix + name + "_b"].astype(x.dtype)
             if cfg.qkv_bias and prefix + name + "_b" in p else None)
        if kv and kv_rep:
            sl = (ops.q8_slice_cols(w, kv_head * hd, hd)
                  if isinstance(w, ops.QuantTensor) else None)
            if sl is not None:
                # scale layout is column-sliceable: stay on the int8 GEMM
                y = dense(x, sl)
            else:
                wd = lax.dynamic_slice(to_dense(w, x.dtype),
                                       (0, kv_head * hd), (w.shape[0], hd))
                y = x @ wd
            if b is not None:
                b = lax.dynamic_slice(b, (kv_head * hd,), (hd,))
        else:
            y = dense(x, w)
        if b is not None:
            y = y + b
        return y.reshape(B, T, h, hd).transpose(0, 2, 1, 3)

    q = proj("wq", hq)
    k = proj("wk", hkv, kv=True)
    v = proj("wv", hkv, kv=True)

    q = rope(q, q_pos, cfg.rope_theta)
    k = rope(k, q_pos, cfg.rope_theta)

    chunk = getattr(cfg, "attn_chunk", 1024)
    scale = getattr(cfg, "attention_multiplier", None)
    if cache is None:
        out = chunked_attention(
            q, k, v, q_pos=q_pos if causal else None,
            kv_pos=q_pos if causal else None, window=window,
            softcap=cfg.attn_softcap, chunk=chunk, scale=scale,
        )
        new_cache = None
    else:
        W = cache["k"].shape[2]
        idx = jnp.asarray(cache_index, jnp.int32)
        if idx.ndim == 1:
            # per-row positions (continuous-batching decode): each batch row
            # writes its own ring slot
            slot = idx % W
            ck = jax.vmap(
                lambda c, kn, s: lax.dynamic_update_slice(c, kn, (0, s, 0))
            )(cache["k"], k.astype(cache["k"].dtype), slot)
            cv = jax.vmap(
                lambda c, vn, s: lax.dynamic_update_slice(c, vn, (0, s, 0))
            )(cache["v"], v.astype(cache["v"].dtype), slot)
            cpos = jax.vmap(
                lambda c, p, s: lax.dynamic_update_slice(c, p, (s,))
            )(cache["pos"], q_pos[:, :T].astype(jnp.int32), slot)
        else:
            slot = idx % W
            # prefill writes assume no wrap (T <= W, index 0); decode is T=1
            ck = lax.dynamic_update_slice(
                cache["k"], k.astype(cache["k"].dtype), (0, 0, slot, 0))
            cv = lax.dynamic_update_slice(
                cache["v"], v.astype(cache["v"].dtype), (0, 0, slot, 0))
            cpos = lax.dynamic_update_slice(
                cache["pos"], q_pos[:, :T].astype(jnp.int32), (0, slot))
        valid = cpos >= 0
        out = chunked_attention(
            q, ck, cv, q_pos=q_pos, kv_pos=cpos, kv_valid=valid,
            window=window, softcap=cfg.attn_softcap, chunk=chunk,
            scale=scale,
        )
        new_cache = {"k": ck, "v": cv, "pos": cpos}

    out = out.transpose(0, 2, 1, 3).reshape(B, T, hq * hd)
    out = dense(out, p[prefix + "wo"])
    return reduce_out(out, tp_axis, sp), new_cache


def cross_attention(cfg, p, x, memory, *, tp_axis=None, tp=1, prefix="x_"):
    """Cross-attention onto encoder/vision memory (B, M, D). Non-causal."""
    B, T, D = x.shape
    M = memory.shape[1]
    hd = cfg.hd
    hq, hkv, kv_rep = tp_head_counts(cfg.n_heads, cfg.n_kv_heads, tp)
    if kv_rep:
        raise ValueError("cross-attention with tp > n_kv is not supported")

    q = dense(rms_norm(x, p[prefix + "lnq"], cfg.norm_eps), p[prefix + "wq"]
              ).reshape(B, T, hq, hd).transpose(0, 2, 1, 3)
    k = dense(memory, p[prefix + "wk"]).reshape(B, M, hkv, hd).transpose(0, 2, 1, 3)
    v = dense(memory, p[prefix + "wv"]).reshape(B, M, hkv, hd).transpose(0, 2, 1, 3)
    out = chunked_attention(q, k, v)
    out = out.transpose(0, 2, 1, 3).reshape(B, T, hq * hd)
    return psum(dense(out, p[prefix + "wo"]), tp_axis)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def mlp(cfg, p, x, *, tp_axis=None, prefix="", sp=False):
    if cfg.mlp == "swiglu":
        h = jax.nn.silu(dense(x, p[prefix + "w1"])) * dense(x, p[prefix + "w3"])
    elif cfg.mlp == "geglu":
        h = (jax.nn.gelu(dense(x, p[prefix + "w1"]), approximate=True)
             * dense(x, p[prefix + "w3"]))
    elif cfg.mlp == "squared_relu":  # nemotron-4 [arXiv:2402.16819]
        h = jnp.square(jax.nn.relu(dense(x, p[prefix + "w1"])))
    else:
        raise ValueError(cfg.mlp)
    return reduce_out(dense(h, p[prefix + "w2"]), tp_axis, sp)


# ---------------------------------------------------------------------------
# Vocab-parallel embedding + cross entropy
# ---------------------------------------------------------------------------

def embed(tokens, emb_local, *, tp_axis=None, vocab_start=0):
    """emb_local: (V_local, D).  Vocab-parallel lookup with psum combine."""
    ids = tokens - vocab_start
    ok = (ids >= 0) & (ids < emb_local.shape[0])
    x = jnp.take(emb_local, jnp.clip(ids, 0, emb_local.shape[0] - 1), axis=0)
    x = jnp.where(ok[..., None], x, 0)
    return psum(x, tp_axis)


def lm_logits(x, head_local, *, softcap=None, scaling=None):
    """``x @ head``, over ``scaling`` where given (granite's
    ``logits_scaling``), then soft-capped where given."""
    logits = x @ head_local.astype(x.dtype)
    if scaling is not None:
        logits = logits / scaling
    if softcap is not None:
        logits = softcap * jnp.tanh(logits / softcap)
    return logits


def chunked_ce(x, head, labels, mask, *, vocab_chunk: int = 8192,
               softcap=None, tp_axis=None, vocab_start=0, scaling=None):
    """Cross entropy without materializing (B, T, V) logits: scan over vocab
    chunks with an online max/logsumexp (the lm-head analogue of flash
    attention).  Beyond-paper §Perf optimization: the fp32 logits buffer for
    a 152k vocab is ~2.5 GB/device at train_4k; this caps it at
    (B, T, vocab_chunk).  The head matmul is recomputed in backward
    (remat'd scan body) -- bytes traded for ~+1 forward head matmul."""
    B, T, D = x.shape
    V = head.shape[1]
    nc = -(-V // vocab_chunk)
    pad = nc * vocab_chunk - V
    if pad:
        head = jnp.pad(head, ((0, 0), (0, pad)))
    hc = head.reshape(D, nc, vocab_chunk).transpose(1, 0, 2)  # (nc, D, Vc)

    x32 = x
    ids = labels - vocab_start

    def body(carry, xs):
        m, s, picked = carry
        h_i, ci = xs
        lg = (x32 @ h_i.astype(x.dtype)).astype(jnp.float32)
        if scaling is not None:
            lg = lg / scaling
        if softcap is not None:
            lg = softcap * jnp.tanh(lg / softcap)
        base = ci * vocab_chunk
        # mask padded vocab tail
        col = jnp.arange(vocab_chunk)[None, None, :] + base
        lg = jnp.where(col < V, lg, NEG_INF)
        m_new = jnp.maximum(m, lax.stop_gradient(lg.max(-1)))
        s = s * jnp.exp(m - m_new) + jnp.exp(lg - m_new[..., None]).sum(-1)
        loc = ids - base
        ok = (loc >= 0) & (loc < vocab_chunk)
        got = jnp.take_along_axis(
            lg, jnp.clip(loc, 0, vocab_chunk - 1)[..., None], axis=-1)[..., 0]
        picked = picked + jnp.where(ok, got, 0.0)
        return (m_new, s, picked), None

    m0 = jnp.full((B, T), NEG_INF, jnp.float32)
    s0 = jnp.zeros((B, T), jnp.float32)
    p0 = jnp.zeros((B, T), jnp.float32)
    (m, s, picked), _ = lax.scan(
        jax.checkpoint(body), (m0, s0, p0),
        (hc, jnp.arange(nc, dtype=jnp.int32)))
    if tp_axis:
        # vocab-parallel composition: each rank covered its vocab shard
        m_glob = pmax(lax.stop_gradient(m), tp_axis)
        s = psum(s * jnp.exp(m - m_glob), tp_axis)
        picked = psum(picked, tp_axis)
        m = m_glob
    nll = jnp.log(s) + m - picked
    return (nll * mask).sum(), mask.sum()


def vocab_parallel_ce(logits_local, labels, mask, *, tp_axis=None,
                      vocab_start=0):
    """Cross entropy over vocab-sharded logits (B, T, V_local).

    mask: (B, T) float weights.  Returns (sum_loss, sum_weight) so the caller
    can reduce across data axes."""
    lg = logits_local.astype(jnp.float32)
    m_local = lg.max(axis=-1)
    # stabilizer only: constant shift; stop_gradient *before* pmax so the
    # JVP machinery never differentiates pmax (it has no rule)
    m_glob = pmax(lax.stop_gradient(m_local), tp_axis)
    sumexp = psum(jnp.exp(lg - m_glob[..., None]).sum(-1), tp_axis)
    ids = labels - vocab_start
    ok = (ids >= 0) & (ids < lg.shape[-1])
    picked = jnp.take_along_axis(
        lg, jnp.clip(ids, 0, lg.shape[-1] - 1)[..., None], axis=-1
    )[..., 0]
    label_logit = psum(jnp.where(ok, picked, 0.0), tp_axis)
    nll = jnp.log(sumexp) + m_glob - label_logit
    return (nll * mask).sum(), mask.sum()
