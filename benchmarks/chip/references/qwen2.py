"""Plain float32 reference of a Qwen2 decoder and of AdamW.

Written from the published description (hf ``Qwen2ForCausalLM``), with
the configuration file's values: pre-norm decoder, RMSNorm, RoPE on
half-split heads (``rotate_half``), causal GQA attention scaled by
``1 / sqrt(head_dim)`` with biases on the q, k and v projections and
none on the output, a SwiGLU MLP (``down(silu(gate(x)) * up(x))``), an
untied LM head and mean token cross entropy over the next tokens.

The RMSNorm gain is held as ``1 + scale``: Qwen2's ``weight`` is
``1 + scale``.  The two train alike, since Adam's step does not depend
on where a parameter's zero lies and weight decay touches matrices only.

Nothing here imports the program.  Every matmul runs at
``Precision.HIGHEST``; attention runs in query blocks and each layer is
rematerialised, so the reference fits on one chip.

``mode="fp8"`` is the control: every matmul's inputs and result rounded
to float8 e4m3 under a per-tensor scale (gradients straight through in
float32), the precision step below the bfloat16 the program computes
in.  ``mode="bf16"`` rounds to bfloat16 instead (a witness of what
bfloat16 alone does).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST
Q_BLOCK = 1024


class Model:
    """Shapes and math of one configuration file."""

    def __init__(self, conf: dict):
        self.D = conf["hidden_size"]
        self.H = conf["num_attention_heads"]
        self.Hkv = conf["num_key_value_heads"]
        self.hd = self.D // self.H
        self.F = conf["intermediate_size"]
        self.V = conf["vocab_size"]
        self.L = conf["num_hidden_layers"]
        self.eps = conf["rms_norm_eps"]
        self.theta = conf["rope_theta"]
        if conf["tie_word_embeddings"] or conf["hidden_act"] != "silu":
            raise ValueError("reference covers an untied head and SwiGLU")

    def shapes(self) -> dict[str, tuple[tuple[int, ...], int | None]]:
        """name -> (per-layer shape, layers or None)."""
        D, Hq, Hkv, F, L = (self.D, self.H * self.hd, self.Hkv * self.hd,
                            self.F, self.L)
        return {
            "ln1": ((D,), L), "wq": ((D, Hq), L), "wq_b": ((Hq,), L),
            "wk": ((D, Hkv), L), "wk_b": ((Hkv,), L), "wv": ((D, Hkv), L),
            "wv_b": ((Hkv,), L), "wo": ((Hq, D), L), "ln2": ((D,), L),
            "w1": ((D, F), L), "w3": ((D, F), L), "w2": ((F, D), L),
            "emb": ((self.V, D), None), "final_ln": ((D,), None),
            "head": ((D, self.V), None),
        }

    def layered(self) -> dict[str, bool]:
        return {n: L is not None for n, (_, L) in self.shapes().items()}


def _cast(x, mode):
    """A matmul operand or result rounded as ``mode`` says, gradients
    straight through in float32.  ``bf16``: 8 exponent and 7 mantissa
    bits.  ``fp8``: 4 exponent and 3 mantissa bits (float8 e4m3) under a
    per-tensor scale that maps the largest magnitude to the format's
    largest finite value, 240.  ``lax.reduce_precision`` rounds where a
    cast to a narrow type and back may be elided: XLA:TPU allows excess
    precision."""
    if mode not in ("bf16", "fp8"):
        return x
    v = lax.stop_gradient(x)
    if mode == "bf16":
        q = lax.reduce_precision(v, exponent_bits=8, mantissa_bits=7)
    else:
        s = jnp.maximum(jnp.max(jnp.abs(v)), 1e-30) / 240.0
        q = lax.reduce_precision(v / s, exponent_bits=4,
                                 mantissa_bits=3) * s
    return x + (q - v)


def _mm(spec, a, b, mode):
    """A matmul computed in ``mode``: its inputs and its result rounded,
    accumulated in float32."""
    return _cast(jnp.einsum(spec, _cast(a, mode), _cast(b, mode),
                            precision=HI), mode)


def _rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * (1.0 + scale)


def _rope(x, theta):
    """x: (B, T, H, hd), positions 0..T-1, half-split rotation."""
    T, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs  # (T, half)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(m: Model, p, h, mode):
    B, T, _ = h.shape

    def proj(name, heads):
        y = _mm("btd,dx->btx", h, p[name], mode)
        if name + "_b" in p:
            y = y + p[name + "_b"]
        return y.reshape(B, T, heads, m.hd)

    q = _rope(proj("wq", m.H), m.theta)
    k = _rope(proj("wk", m.Hkv), m.theta)
    v = proj("wv", m.Hkv)
    rep = m.H // m.Hkv  # query head i reads kv head i // rep
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    outs = []
    for lo in range(0, T, Q_BLOCK):
        hi = min(T, lo + Q_BLOCK)
        s = _mm("bqhd,bkhd->bhqk", q[:, lo:hi], k, mode) / jnp.sqrt(
            jnp.float32(m.hd))
        causal = jnp.arange(lo, hi)[:, None] >= jnp.arange(T)[None, :]
        s = jnp.where(causal[None, None], s, -jnp.inf)
        outs.append(_mm("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v,
                        mode))
    o = jnp.concatenate(outs, axis=1).reshape(B, T, m.H * m.hd)
    return _mm("btx,xd->btd", o, p["wo"], mode)


def _mlp(p, h, mode):
    act = jax.nn.silu(_mm("btd,df->btf", h, p["w1"], mode)) * _mm(
        "btd,df->btf", h, p["w3"], mode)
    return _mm("btf,fd->btd", act, p["w2"], mode)


def loss_fn(m: Model, params, tokens, weights, denom, *, mode: str = "fp32"):
    """Weighted token cross entropy over ``denom``: ``weights`` (B, T-1)
    weighs each predicted position (all ones for the cell; a fault
    zeroes some)."""
    x = params["emb"][tokens]
    layer_names = [n for n, (_, L) in m.shapes().items() if L]

    @jax.checkpoint
    def body(x, p):
        x = x + _attention(m, p, _rms(x, p["ln1"], m.eps), mode)
        return x + _mlp(p, _rms(x, p["ln2"], m.eps), mode), None

    x, _ = lax.scan(body, x, {n: params[n] for n in layer_names})
    x = _rms(x, params["final_ln"], m.eps)
    logits = _mm("btd,dv->btv", x[:, :-1], params["head"], mode)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, tokens[:, 1:, None], -1)[..., 0]
    return jnp.sum((lse - picked) * weights) / denom


def adamw_update(p, g, mu, nu, t, opt: dict, layered: dict[str, bool]):
    """Textbook AdamW with bias correction and decoupled weight decay on
    matrices; ``t`` counts steps from 0 and sets the linear warm-up."""
    lr = opt["lr"] * jnp.minimum((t + 1.0) / opt["warmup_steps"], 1.0)
    b1, b2 = opt["b1"], opt["b2"]
    out_p, out_m, out_v = {}, {}, {}
    for n in p:
        mu_n = b1 * mu[n] + (1.0 - b1) * g[n]
        nu_n = b2 * nu[n] + (1.0 - b2) * g[n] * g[n]
        mhat = mu_n / (1.0 - b1 ** (t + 1.0))
        vhat = nu_n / (1.0 - b2 ** (t + 1.0))
        matrix = p[n].ndim - (1 if layered[n] else 0) >= 2
        decay = opt["weight_decay"] if matrix else 0.0
        out_p[n] = p[n] - lr * (mhat / (jnp.sqrt(vhat) + opt["eps"])
                                + decay * p[n])
        out_m[n], out_v[n] = mu_n, nu_n
    return out_p, out_m, out_v


def leaf_norms(tree: dict, layered: dict[str, bool]) -> dict[str, jax.Array]:
    """Frobenius norm of each leaf: per layer for stacked tensors."""
    out = {}
    for n, a in tree.items():
        if layered[n]:
            out[n] = jnp.sqrt(jnp.sum(jnp.square(a.reshape(a.shape[0], -1)),
                                      axis=1))
        else:
            out[n] = jnp.sqrt(jnp.sum(jnp.square(a)))
    return out


def make_step(m: Model, opt: dict, *, mode: str):
    """jit'd ``(p, mu, nu, tokens, weights, denom, t) -> (p, mu, nu, loss,
    grad leaf norms)``."""
    layered = m.layered()

    def step(p, mu, nu, tokens, weights, denom, t):
        loss, g = jax.value_and_grad(partial(loss_fn, m, mode=mode))(
            p, tokens, weights, denom)
        p, mu, nu = adamw_update(p, g, mu, nu, t, opt, layered)
        return p, mu, nu, loss, leaf_norms(g, layered)

    return jax.jit(step, donate_argnums=(0, 1, 2))
