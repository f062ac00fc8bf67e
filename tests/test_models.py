"""Model-layer correctness: chunked attention vs naive softmax oracle,
sliding windows, ring cache, SSM scans vs sequential reference, MoE
dispatch invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.models import layers as L
from repro.models.moe import _positions_within_expert, moe_ffn
from repro.models.ssm import diagonal_scan


def naive_attention(q, k, v, q_pos, kv_pos, kv_valid=None, window=None,
                    softcap=None):
    B, Hq, Tq, hd = q.shape
    Hkv = k.shape[1]
    g = Hq // Hkv
    kk = jnp.repeat(k, g, axis=1)
    vv = jnp.repeat(v, g, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   kk.astype(jnp.float32)) / np.sqrt(hd)
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    mask = jnp.ones(s.shape, bool)
    if kv_valid is not None:
        mask &= kv_valid[:, None, None, :]
    if q_pos is not None:
        qp = q_pos[:, None, :, None]
        kp = kv_pos[:, None, None, :]
        mask &= kp <= qp
        if window is not None:
            mask &= qp - kp < window
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(mask.any(-1, keepdims=True), p, 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", p, vv.astype(jnp.float32))


def rnd(shape, seed):
    return jnp.asarray(
        np.random.default_rng(seed).normal(size=shape).astype(np.float32))


@pytest.mark.parametrize("Tq,Tk,chunk", [(16, 16, 4), (8, 24, 5), (1, 32, 8),
                                         (32, 32, 32)])
@pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2), (8, 1)])
def test_chunked_attention_matches_naive(Tq, Tk, chunk, hq, hkv):
    B, hd = 2, 16
    q, k, v = rnd((B, hq, Tq, hd), 1), rnd((B, hkv, Tk, hd), 2), rnd(
        (B, hkv, Tk, hd), 3)
    q_pos = jnp.broadcast_to(
        jnp.arange(Tk - Tq, Tk, dtype=jnp.int32)[None], (B, Tq))
    kv_pos = jnp.broadcast_to(jnp.arange(Tk, dtype=jnp.int32)[None], (B, Tk))
    got = L.chunked_attention(q, k, v, q_pos=q_pos, kv_pos=kv_pos,
                              chunk=chunk)
    want = naive_attention(q, k, v, q_pos, kv_pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("window", [1, 3, 8])
def test_sliding_window(window):
    B, H, T, hd = 1, 2, 24, 8
    q, k, v = rnd((B, H, T, hd), 4), rnd((B, H, T, hd), 5), rnd(
        (B, H, T, hd), 6)
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))
    got = L.chunked_attention(q, k, v, q_pos=pos, kv_pos=pos, window=window,
                              chunk=7)
    want = naive_attention(q, k, v, pos, pos, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-3,
                               atol=2e-3)


def test_softcap():
    B, H, T, hd = 1, 1, 12, 8
    q, k, v = rnd((B, H, T, hd), 7), rnd((B, H, T, hd), 8), rnd(
        (B, H, T, hd), 9)
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))
    got = L.chunked_attention(q, k, v, q_pos=pos, kv_pos=pos, softcap=5.0,
                              chunk=4)
    want = naive_attention(q, k, v, pos, pos, softcap=5.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-3,
                               atol=2e-3)


def test_ring_cache_decode_equals_full_context():
    """Decoding with a wrap-around ring cache == attention over the last W
    positions of the full sequence (the long_500k mechanism)."""
    B, H, hd, W, T = 1, 2, 8, 8, 20

    class Cfg:
        hd = 8
        n_heads = 2
        n_kv_heads = 2
        qkv_bias = False
        rope_theta = 1e4
        attn_softcap = None

    p = {
        "wq": rnd((H * hd, H * hd), 11) * 0.2,
        "wk": rnd((H * hd, H * hd), 12) * 0.2,
        "wv": rnd((H * hd, H * hd), 13) * 0.2,
        "wo": rnd((H * hd, H * hd), 14) * 0.2,
    }
    xs = rnd((B, T, H * hd), 15)
    cache = {
        "k": jnp.zeros((B, H, W, hd)), "v": jnp.zeros((B, H, W, hd)),
        "pos": jnp.full((B, W), -1, jnp.int32),
    }
    outs = []
    for t in range(T):
        q_pos = jnp.full((B, 1), t, jnp.int32)
        o, cache = L.attention(Cfg, p, xs[:, t:t + 1], q_pos=q_pos,
                               cache=cache, cache_index=t, window=W)
        outs.append(o)
    # reference: full K/V, window-masked
    ref_cache = {
        "k": jnp.zeros((B, H, T, hd)), "v": jnp.zeros((B, H, T, hd)),
        "pos": jnp.full((B, T), -1, jnp.int32),
    }
    refs = []
    for t in range(T):
        q_pos = jnp.full((B, 1), t, jnp.int32)
        o, ref_cache = L.attention(Cfg, p, xs[:, t:t + 1], q_pos=q_pos,
                                   cache=ref_cache, cache_index=t, window=W)
        refs.append(o)
    np.testing.assert_allclose(
        np.asarray(jnp.concatenate(outs, 1)),
        np.asarray(jnp.concatenate(refs, 1)), rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# SSM scans
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(st.integers(1, 40), st.integers(1, 7), st.integers(0, 1000))
def test_diagonal_scan_matches_sequential(T, chunk, seed):
    B, D = 2, 3
    rng = np.random.default_rng(seed)
    a = jnp.asarray(rng.uniform(0.5, 1.0, (B, T, D)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(B, T, D)).astype(np.float32))
    hs, h_last = diagonal_scan(a, b, chunk=chunk)
    h = np.zeros((B, D), np.float32)
    seq = []
    for t in range(T):
        h = np.asarray(a[:, t]) * h + np.asarray(b[:, t])
        seq.append(h.copy())
    np.testing.assert_allclose(np.asarray(hs), np.stack(seq, 1), rtol=1e-4,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(h_last), seq[-1], rtol=1e-4,
                               atol=1e-5)


def test_diagonal_scan_carry_composes():
    """prefill(T) then decode(1) == prefill(T+1) -- the serve-path contract."""
    B, T, D = 1, 9, 4
    a = jnp.asarray(np.random.default_rng(0).uniform(0.6, 1, (B, T + 1, D)),
                    jnp.float32)
    b = rnd((B, T + 1, D), 1)
    full, _ = diagonal_scan(a, b, chunk=4)
    part, h = diagonal_scan(a[:, :T], b[:, :T], chunk=4)
    step, _ = diagonal_scan(a[:, T:], b[:, T:], h0=h, chunk=4)
    np.testing.assert_allclose(np.asarray(step[:, 0]),
                               np.asarray(full[:, T]), rtol=1e-4, atol=1e-5)


def test_mlstm_chunk_invariance():
    """mLSTM chunked form must not depend on the chunk size."""
    from repro.models.ssm import mlstm_mix

    class Cfg:
        n_heads = 2
        norm_eps = 1e-6

    B, T, D = 1, 33, 16
    p = {f"m_{n}": rnd((D, sz), i) * 0.3 for i, (n, sz) in enumerate(
        [("wq", D), ("wk", D), ("wv", D), ("wog", D), ("wo", D)])}
    p["m_wgate"] = rnd((D, 4), 9) * 0.3
    x = rnd((B, T, D), 10)
    # monkey-run with different chunk sizes by slicing T
    out1, st1 = mlstm_mix(Cfg, p, x)
    # sequential: feed one token at a time carrying state
    st = None
    outs = []
    for t in range(T):
        o, st = mlstm_mix(Cfg, p, x[:, t:t + 1], state=st)
        outs.append(o)
    np.testing.assert_allclose(
        np.asarray(out1), np.asarray(jnp.concatenate(outs, 1)),
        rtol=5e-3, atol=5e-3)


def test_slstm_sequential_state():
    from repro.models.ssm import slstm_mix

    class Cfg:
        n_heads = 2
        norm_eps = 1e-6

    B, T, D = 2, 11, 8
    p = {"s_w_zifo": rnd((D, 4 * D), 1) * 0.4, "s_wo": rnd((D, D), 2) * 0.4}
    x = rnd((B, T, D), 3)
    full, stf = slstm_mix(Cfg, p, x)
    st = None
    outs = []
    for t in range(T):
        o, st = slstm_mix(Cfg, p, x[:, t:t + 1], state=st)
        outs.append(o)
    np.testing.assert_allclose(np.asarray(full),
                               np.asarray(jnp.concatenate(outs, 1)),
                               rtol=1e-3, atol=1e-4)
    np.testing.assert_allclose(np.asarray(stf["c"]), np.asarray(st["c"]),
                               rtol=1e-3, atol=1e-4)


# ---------------------------------------------------------------------------
# MoE
# ---------------------------------------------------------------------------

@settings(max_examples=30, deadline=None)
@given(st.integers(2, 40), st.integers(2, 8), st.integers(0, 500))
def test_positions_within_expert(n, E, seed):
    rng = np.random.default_rng(seed)
    fe = jnp.asarray(rng.integers(0, E, (n,)), jnp.int32)
    rank = np.asarray(_positions_within_expert(fe, E))
    fe_np = np.asarray(fe)
    for e in range(E):
        idx = np.nonzero(fe_np == e)[0]
        assert (np.sort(rank[idx]) == np.arange(len(idx))).all()


def test_moe_capacity_drops_and_combines():
    class Cfg:
        n_experts = 4
        top_k = 2
        capacity_factor = 1.0
        moe_aux_coef = 0.01
        moe_aux_form = "switch"
        mlp = "swiglu"

    B, T, D, F, E = 2, 8, 16, 32, 4
    p = {
        "moe_router": rnd((D, E), 1) * 0.3,
        "moe_w1": rnd((E, D, F), 2) * 0.2,
        "moe_w3": rnd((E, D, F), 3) * 0.2,
        "moe_w2": rnd((E, F, D), 4) * 0.2,
    }
    x = rnd((B, T, D), 5)
    out, aux, _ = moe_ffn(Cfg, p, x)
    assert out.shape == x.shape
    assert np.isfinite(np.asarray(out)).all()
    assert float(aux) > 0
    # zero capacity factor edge: everything dropped -> output ~0
    Cfg.capacity_factor = 1e-9
    out0, _, _ = moe_ffn(Cfg, p, x)
    # cap >= 1 always, so at most E tokens survive; most are dropped
    assert np.abs(np.asarray(out0)).mean() < np.abs(np.asarray(out)).mean()


def _dense_moe(p, x, experts, gates, E):
    """Every expert on every token, weighted by its gate or by nought."""
    N = experts.shape[0]
    combine = jnp.zeros((N, E), jnp.float32).at[
        jnp.arange(N)[:, None], experts].add(gates)
    g = jnp.einsum("nd,edf->nef", x, p["moe_w1"], precision="highest")
    u = jnp.einsum("nd,edf->nef", x, p["moe_w3"], precision="highest")
    act = jax.nn.silu(g) * u * combine[..., None]
    return jnp.einsum("nef,efd->nd", act, p["moe_w2"], precision="highest")


def test_moe_dropless_grouped_matches_dense_form():
    """The dropless path (sort into expert order, grouped matmuls over
    uneven groups, back to token order, gate-weighted combine) equals
    every expert run on every token weighted by its gate, in float32,
    value and gradient, with uneven groups and one expert chosen by no
    token."""
    class Cfg:
        n_experts = 6
        top_k = 2
        capacity_factor = None
        moe_aux_coef = 0.001
        moe_aux_form = "mixtral"

    B, T, D, F, E = 2, 12, 16, 8, Cfg.n_experts
    p = {"moe_router": rnd((D, E), 1),
         "moe_w1": rnd((E, D, F), 2) * 0.3,
         "moe_w3": rnd((E, D, F), 3) * 0.3,
         "moe_w2": rnd((E, F, D), 4) * 0.3}
    # positive activations: expert 5 is chosen by no token, expert 0 by
    # most
    p["moe_router"] = p["moe_router"].at[:, 5].set(-5.0).at[:, 0].add(0.5)
    x = jnp.abs(rnd((B, T, D), 5))

    def routed(p, x):
        out, aux, r = moe_ffn(Cfg, p, x)
        return out, aux, r

    out, aux, r = jax.jit(routed)(p, x)
    sizes = np.bincount(np.asarray(r["experts"]).ravel(), minlength=E)
    assert sizes[5] == 0 and len(set(sizes[:5].tolist())) > 1
    assert np.asarray(r["kept"]).all() and r["experts"].dtype == jnp.int32

    def dense(p, x):
        xf = x.reshape(B * T, D)
        logits = xf @ p["moe_router"]
        e = r["experts"]
        gates = jax.nn.softmax(jnp.take_along_axis(logits, e, -1), -1)
        return _dense_moe(p, xf, e, gates, E).reshape(B, T, D)

    np.testing.assert_allclose(np.asarray(out), np.asarray(dense(p, x)),
                               rtol=1e-5, atol=1e-5)
    ct = rnd((B, T, D), 7)
    g_got = jax.jit(jax.grad(lambda p, x: jnp.sum(routed(p, x)[0] * ct),
                             argnums=(0, 1)))(p, x)
    g_want = jax.jit(jax.grad(lambda p, x: jnp.sum(dense(p, x) * ct),
                              argnums=(0, 1)))(p, x)
    for a, b in zip(jax.tree.leaves(g_got), jax.tree.leaves(g_want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)
    # hf's load balance: E * sum_e P_e * f_e, f over the tokens
    probs = jax.nn.softmax(x.reshape(B * T, D) @ p["moe_router"], -1)
    want = E * np.sum(np.asarray(probs).mean(0) * sizes / (B * T)) * 0.001
    np.testing.assert_allclose(float(aux), want, rtol=1e-5)


# ---------------------------------------------------------------------------
# vocab-parallel CE vs direct
# ---------------------------------------------------------------------------

def test_ce_matches_direct():
    B, T, V = 2, 6, 37
    logits = rnd((B, T, V), 1)
    labels = jnp.asarray(np.random.default_rng(2).integers(0, V, (B, T)),
                         jnp.int32)
    mask = jnp.ones((B, T), jnp.float32)
    nll, w = L.vocab_parallel_ce(logits, labels, mask)
    lp = jax.nn.log_softmax(np.asarray(logits, np.float32), axis=-1)
    want = -np.take_along_axis(np.asarray(lp), np.asarray(labels)[..., None],
                               -1).sum()
    np.testing.assert_allclose(float(nll), float(want), rtol=1e-5)
    assert float(w) == B * T


def test_optimized_profile_training_parity():
    """The beyond-paper optimized profile (attn_chunk 512 + chunked CE) must
    be a pure performance change: losses match the baseline path closely."""
    import dataclasses

    import jax.numpy as jnp

    from repro.configs import build_model, get_config
    from repro.core.fsdp import FSDPRuntime
    from repro.launch.mesh import make_local_mesh
    from repro.optim import make_optimizer

    mesh = make_local_mesh(1, 1)

    def run(cfg):
        model = build_model(cfg)
        rt = FSDPRuntime(model, mesh)
        params = rt.init_params(0)
        opt = make_optimizer(cfg)
        state = opt.init(rt)
        fn = rt.make_train_step(opt)
        st = jnp.int32(0)
        b = {"tokens": jnp.asarray(
            np.random.default_rng(0).integers(0, cfg.vocab, (4, 32)),
            jnp.int32)}
        losses = []
        for _ in range(3):
            params, state, st, m = fn(params, state, st, b)
            losses.append(float(m["loss"]))
        return losses

    base_cfg = get_config("gemma2-2b").reduced()  # exercises final_softcap too
    opt_cfg = dataclasses.replace(base_cfg, attn_chunk=8, ce_chunk=64)
    base, opt = run(base_cfg), run(opt_cfg)
    for a, b in zip(base, opt):
        assert abs(a - b) < 2e-2, (base, opt)
