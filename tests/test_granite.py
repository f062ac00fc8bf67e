"""Granite-3.0-1B-A400M as published (registry ``granite-3.0-1b-a400m``)
through the normal path on the CPU, at toy widths with the published
routing (top-8 of 32 experts, dropless) and multipliers: the train step
reports the routing that the routed layers chose, in the batch's token
order across microbatches; the recomputed forward reuses the forward's
routing; a dense step's metrics and the multipliers' defaults leave
every other model as it was."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import build_model, get_config
from repro.core.fsdp import FSDPRuntime
from repro.launch.mesh import make_local_mesh
from repro.launch.train import build, train_loop
from repro.models import layers as L
from repro.models import transformer
from repro.optim import make_optimizer

SMALL = dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
             d_ff=32, vocab=256)


def _cfg(microbatches=1, **kw):
    cfg = dataclasses.replace(get_config("granite-3.0-1b-a400m"),
                              **dict(SMALL, **kw))
    par = dataclasses.replace(cfg.parallel, microbatches=microbatches)
    return dataclasses.replace(cfg, parallel=par)


def _tokens(cfg, rows, seq=32, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, cfg.vocab, (rows, seq)), jnp.int32)


def test_registry_entry_is_the_published_model():
    cfg = get_config("granite-3.0-1b-a400m")
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd,
            cfg.d_ff, cfg.vocab) == (24, 1024, 16, 8, 64, 512, 49155)
    assert (cfg.n_experts, cfg.top_k, cfg.capacity_factor) == (32, 8, None)
    assert (cfg.embedding_multiplier, cfg.attention_multiplier,
            cfg.residual_multiplier, cfg.logits_scaling) == (
                12.0, 0.015625, 0.22, 6.0)
    assert cfg.tie_embeddings and cfg.rope_theta == 1e4
    assert (cfg.moe_aux_coef, cfg.moe_aux_form) == (0.001, "mixtral")
    # the earlier variant stays as it was, for the tiny routed test cell
    old = get_config("granite-moe-1b-a400m")
    assert (old.capacity_factor, old.moe_aux_coef, old.rope_theta,
            old.tie_embeddings, old.embedding_multiplier) == (
                1.25, 0.01, 1e6, False, None)


def test_runs_through_build_and_train_loop():
    """Top-8 of 32 experts, the four multipliers and the tied head
    through ``build``, ``make_train_step`` and ``train_loop``: every
    choice kept, the loss finite and falling on a repeated batch."""
    cfg = _cfg()
    runtime, optimizer = build(cfg, make_local_mesh(1, 1))
    assert "head" not in {s.name for g in runtime.model.groups().values()
                          for s in g.specs}
    params = runtime.init_params(0)
    opt_state = optimizer.init(runtime)
    batch = {"tokens": _tokens(cfg, 2)}
    seen = []

    def on_step(i, params, opt_state, metrics):
        r = metrics["routing"]
        assert r["experts"].shape == (2, 2 * 32, 8)
        e = np.asarray(r["experts"])
        assert ((0 <= e) & (e < 32)).all()
        assert np.asarray(r["kept"]).all()
        # each token's 8 choices are 8 different experts
        assert (np.sort(e, axis=-1)[..., 1:] != np.sort(e, axis=-1)[..., :-1]
                ).all()
        seen.append(float(metrics["loss"]))

    train_loop(runtime, runtime.make_train_step(optimizer), params,
               opt_state, lambda i: batch, 4, on_step=on_step)
    assert np.isfinite(seen).all() and seen[-1] < seen[0]


def _recording(monkeypatch):
    """``transformer.moe_ffn`` wrapped so that each call sends its own
    ``experts`` and ``kept`` to the host."""
    calls = []
    ffn = transformer.moe_ffn

    def record(cfg, p, x, **kw):
        out, aux, r = ffn(cfg, p, x, **kw)
        jax.debug.callback(
            lambda e, k: calls.append((np.asarray(e), np.asarray(k))),
            r["experts"], r["kept"], ordered=True)
        return out, aux, r

    monkeypatch.setattr(transformer, "moe_ffn", record)
    return calls


def _step_routing(cfg, tokens):
    rt = FSDPRuntime(build_model(cfg), make_local_mesh(1, 1))
    opt = make_optimizer(cfg)
    fn = rt.make_train_step(opt)
    _, _, _, m = fn(rt.init_params(0), opt.init(rt), jnp.int32(0),
                    {"tokens": tokens})
    jax.effects_barrier()
    return {k: np.asarray(v) for k, v in m["routing"].items()}


def test_reported_routing_is_each_layers_own(monkeypatch):
    """On one input the step reports, layer by layer, the ``experts`` and
    ``kept`` that ``moe_ffn`` chose in the forward pass."""
    calls = _recording(monkeypatch)
    cfg = _cfg()
    got = _step_routing(cfg, _tokens(cfg, 2))
    fwd = calls[:cfg.n_layers]
    want_e = np.stack([c[0] for c in fwd])
    want_k = np.stack([c[1] for c in fwd])
    np.testing.assert_array_equal(got["experts"], want_e)
    np.testing.assert_array_equal(got["kept"], want_k)
    assert got["experts"].dtype == np.int32 and got["kept"].dtype == bool


def test_routing_of_two_microbatches_is_in_batch_order():
    """Two microbatches of two rows report what one batch of four rows
    reports: each microbatch's tokens at its rows' place."""
    cfg = _cfg()
    tokens = _tokens(cfg, 4, seed=3)
    whole = _step_routing(cfg, tokens)
    split = _step_routing(_cfg(microbatches=2), tokens)
    assert split["experts"].shape == (2, 4 * 32, 8)
    np.testing.assert_array_equal(split["kept"], whole["kept"])
    same = np.mean(split["experts"] == whole["experts"])
    assert same == 1.0, same
    n = 2 * 32
    swapped = np.concatenate([split["experts"][:, n:],
                              split["experts"][:, :n]], axis=1)
    assert np.mean(swapped == whole["experts"]) < 0.5


@pytest.mark.parametrize("registry", ["granite-3.0-1b-a400m",
                                      "granite-moe-1b-a400m"])
def test_backward_reuses_the_forwards_routing(registry):
    """The step's jaxpr holds one top-k and one sort of the routed layers,
    in the forward scan: the recomputed forward of the backward (the
    checkpoint's ``remat`` call, ``checkpoint/rematted_computation`` in the
    HLO) takes the experts, their order and counts from the forward
    (``moe.ROUTING``), dropless or with a capacity."""
    from repro.analysis.jaxpr import iter_eqns

    cfg = dataclasses.replace(get_config(registry), **SMALL)
    rt = FSDPRuntime(build_model(cfg), make_local_mesh(1, 1))
    opt = make_optimizer(cfg)
    jx = jax.make_jaxpr(rt.make_train_step(opt))(
        rt.init_params(0), opt.init(rt), jnp.int32(0),
        {"tokens": _tokens(cfg, 2)})
    found = [(e.primitive.name, path) for e, _, path in iter_eqns(jx)
             if e.primitive.name in ("top_k", "sort")]
    assert sorted(n for n, _ in found) == ["sort", "top_k"], found
    assert [p for _, p in found if "remat" in p] == []


def test_dense_step_metrics_are_unchanged():
    cfg = get_config("qwen2.5-14b").reduced()
    rt = FSDPRuntime(build_model(cfg), make_local_mesh(1, 1))
    opt = make_optimizer(cfg)
    _, _, _, m = rt.make_train_step(opt)(
        rt.init_params(0), opt.init(rt), jnp.int32(0),
        {"tokens": _tokens(cfg, 2, seq=16)})
    assert set(m) == {"loss", "tokens", "grad_norm"}
    assert not rt.model.reports_routing
    assert rt.model.remat_policy is None


@pytest.mark.parametrize("scale", [None, 0.125])
def test_attention_multiplier_replaces_the_score_scale(scale):
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.standard_normal((1, 2, 8, 16)), jnp.float32)
               for _ in range(3))
    pos = jnp.arange(8)[None]
    got = L.chunked_attention(q, k, v, q_pos=pos, kv_pos=pos, chunk=4,
                              scale=scale)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * (
        0.25 if scale is None else scale)
    s = jnp.where(pos[0][:, None] >= pos[0][None, :], s, -jnp.inf)
    want = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_logits_scaling_divides_the_logits():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((1, 4, 8)), jnp.float32)
    head = jnp.asarray(rng.standard_normal((8, 32)), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(L.lm_logits(x, head, scaling=6.0)),
        np.asarray(L.lm_logits(x, head)) / 6.0, rtol=1e-6)
    labels = jnp.asarray(rng.integers(0, 32, (1, 4)), jnp.int32)
    mask = jnp.ones((1, 4), jnp.float32)
    want, _ = L.vocab_parallel_ce(L.lm_logits(x, head, scaling=6.0), labels,
                                  mask)
    got, _ = L.chunked_ce(x, head, labels, mask, vocab_chunk=8, scaling=6.0)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
