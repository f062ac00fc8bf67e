"""The fused causal flash attention (``kernels/flash_attention.py``) and the
dispatch in ``layers.attention`` that chooses it.

Parity runs the kernels in interpret mode at T = 256 with 128-blocks, so
the causal block skipping, the masked diagonal blocks and the clamped
index maps are all exercised: value and dq, dk, dv against
``chunked_attention`` and a naive float32 softmax.  In float32 the kernel
is the same algorithm to rounding; in bfloat16 it rounds P and dS to bf16
before their matmuls, as XLA:TPU's default precision does.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.flash_attention import block_sizes, causal_attention
from repro.models import layers as L

T = 256
BLOCKS = (128, 128)
GRANITE_SCALE = 1 / 64


def rnd(shape, seed, scale=1.0):
    return jnp.asarray(scale * np.random.default_rng(seed).normal(
        size=shape).astype(np.float32))


def naive(q, k, v, scale):
    """Causal softmax attention in float32 at the highest precision."""
    Tq, g = q.shape[2], q.shape[1] // k.shape[1]
    kk = jnp.repeat(k.astype(jnp.float32), g, axis=1)
    vv = jnp.repeat(v.astype(jnp.float32), g, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), kk,
                   precision="highest") * scale
    s = jnp.where(jnp.tril(jnp.ones((Tq, Tq), bool)), s, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), vv,
                      precision="highest")


def chunked(q, k, v, scale):
    B, Tq = q.shape[0], q.shape[2]
    pos = jnp.broadcast_to(jnp.arange(Tq, dtype=jnp.int32)[None], (B, Tq))
    return L.chunked_attention(q, k, v, q_pos=pos, kv_pos=pos, chunk=128,
                               scale=scale)


def fused(q, k, v, scale):
    return causal_attention(q, k, v, scale=scale, blocks=BLOCKS,
                            interpret=True)


@functools.partial(jax.jit, static_argnums=(0, 5))
def value_and_grads(f, q, k, v, do, scale):
    out, vjp = jax.vjp(lambda *a: f(*a, scale), q, k, v)
    return (out,) + vjp(do.astype(out.dtype))


def rel_err(got, want):
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return np.linalg.norm(got - want) / np.linalg.norm(want)


# Granite's grouping, head size and multiplier; Qwen's grouping and head
# size with the default 1/sqrt(hd); both at batch 2.  Nemotron-4's head
# size 192 is not a multiple of the 128 lanes the row statistics fill.
CASES = {
    "g2_hd64_granite_scale": (2, 4, 2, 64, GRANITE_SCALE),
    "g5_hd128_default_scale": (2, 5, 1, 128, None),
    "g2_hd192_default_scale": (1, 2, 1, 192, None),
}
# relative error (norm of the difference over the reference's) of out, dq,
# dk, dv against the float32 softmax: float32 inputs are the same algorithm
# to rounding; bf16 inputs round P and dS to bf16 before their matmuls
LIMITS = {jnp.float32: 1e-5, jnp.bfloat16: 2e-2}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_fused_attention_matches_chunked_and_naive(case, dtype):
    B, hq, hkv, hd, scale = CASES[case]
    scale = 1 / math.sqrt(hd) if scale is None else scale
    q = rnd((B, hq, T, hd), 1, 2.0).astype(dtype)
    k = rnd((B, hkv, T, hd), 2, 2.0).astype(dtype)
    v = rnd((B, hkv, T, hd), 3).astype(dtype)
    do = rnd((B, hq, T, hd), 4)
    got = value_and_grads(fused, q, k, v, do, scale)
    want = value_and_grads(naive, q, k, v, do, scale)
    scan = value_and_grads(chunked, q, k, v, do, scale)
    for name, g, w, c in zip(("out", "dq", "dk", "dv"), got, want, scan):
        assert g.shape == w.shape and g.dtype == dtype, name
        assert rel_err(g, w) < LIMITS[dtype], (name, rel_err(g, w))
        assert rel_err(g, c) < LIMITS[dtype], (name, rel_err(g, c))


def test_block_sizes_follow_the_sequence():
    assert block_sizes(4096) == (512, 512)
    assert block_sizes(256) == (256, 256)
    assert block_sizes(384) == (384, 384)
    with pytest.raises(ValueError):
        block_sizes(100)


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------

class Cfg:
    hd = 64
    n_heads = 4
    n_kv_heads = 2
    qkv_bias = False
    rope_theta = 1e4
    attn_softcap = None
    attention_multiplier = GRANITE_SCALE


class SoftcapCfg(Cfg):
    attn_softcap = 50.0


def params(cfg, D):
    hd = cfg.hd
    return {"wq": rnd((D, cfg.n_heads * hd), 11, 0.05),
            "wk": rnd((D, cfg.n_kv_heads * hd), 12, 0.05),
            "wv": rnd((D, cfg.n_kv_heads * hd), 13, 0.05),
            "wo": rnd((cfg.n_heads * hd, D), 14, 0.05)}


@pytest.fixture
def on_tpu(monkeypatch):
    """Make ``ops`` see a TPU backend; record each call of the fused
    kernel, answered by its interpret mode."""
    calls = []

    def kernel(q, k, v, *, scale):
        calls.append((q.shape, k.shape, scale))
        return causal_attention(q, k, v, scale=scale, blocks=BLOCKS,
                                interpret=True)

    monkeypatch.setattr(ops, "_interpret", lambda: False)
    monkeypatch.setattr(ops, "_causal_attention", kernel)
    return calls


def _x(B=1, T_=T, D=128):
    return rnd((B, T_, D), 21)


def test_causal_self_attention_with_index_positions_takes_the_kernel(
        on_tpu):
    x = _x()
    out, cache = L.attention(Cfg, params(Cfg, 128), x, q_pos=None)
    assert cache is None
    assert on_tpu == [((1, 4, T, 64), (1, 2, T, 64), GRANITE_SCALE)]
    # the same layer through the scan, with the positions spelled out
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (1, T))
    want, _ = L.attention(Cfg, params(Cfg, 128), x, q_pos=pos)
    assert len(on_tpu) == 1
    assert rel_err(out, want) < 1e-4


@pytest.mark.parametrize("why", ["positions", "cache", "window", "softcap",
                                 "non_causal", "untiled_length"])
def test_everything_else_keeps_the_scan(on_tpu, why):
    cfg = SoftcapCfg if why == "softcap" else Cfg
    T_ = 200 if why == "untiled_length" else T
    x = _x(T_=T_)
    kw = {"q_pos": None}
    if why == "positions":
        kw["q_pos"] = jnp.broadcast_to(
            jnp.arange(T_, dtype=jnp.int32)[None], (1, T_))
    elif why == "cache":
        kw["cache"] = {"k": jnp.zeros((1, 2, T_, 64)),
                       "v": jnp.zeros((1, 2, T_, 64)),
                       "pos": jnp.full((1, T_), -1, jnp.int32)}
        kw["cache_index"] = 0
    elif why == "window":
        kw["window"] = 64
    elif why == "non_causal":
        kw["causal"] = False
    out, _ = L.attention(cfg, params(cfg, 128), x, **kw)
    assert out.shape == x.shape
    assert on_tpu == []


def test_cross_attention_keeps_the_scan(on_tpu):
    class XCfg(Cfg):
        norm_eps = 1e-6

    p = {"x_" + n: w for n, w in params(Cfg, 128).items()}
    p["x_lnq"] = jnp.zeros((128,))
    out = L.cross_attention(XCfg, p, _x(), rnd((1, T, 128), 22))
    assert out.shape == (1, T, 128)
    assert on_tpu == []


def test_cpu_backend_keeps_the_scan(monkeypatch):
    calls = []
    monkeypatch.setattr(ops, "_causal_attention",
                        lambda *a, **k: calls.append(a))
    q = rnd((1, 4, T, 64), 23)
    assert ops.causal_attention(q, q[:, :2], q[:, :2], scale=0.125) is None
    out, _ = L.attention(Cfg, params(Cfg, 128), _x(), q_pos=None)
    assert out.shape == (1, T, 128)
    assert calls == []
