"""ParamStore storage formats (core.store) and reduce modes.

Guarantees under test:
  * fp32 store: explicit ``param_store="fp32"`` is bitwise-identical to the
    default schedule (the pre-store runtime's format).
  * q8_block store: training runs on 1 and 8 devices, for xla and ring
    gather modes with and without prefetch, and all four are bitwise-
    identical to each other at a fixed device count (pure comm-path
    reorderings of the same quantized payload); the dequantized weights
    stay within the per-block int8 bound of the fp32 master; the codes are
    always the exact requantization of the master.
  * ring_acc reduce-scatter: allclose (not bitwise) parity with the
    order-exact reduce over 8-way FSDP, at n-1 chunk-hops wire cost.
  * gather_wire_bytes: the q8 wire is ~4x smaller than an fp32 wire.
"""
import dataclasses
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import build_model, get_config
from repro.core.fsdp import FSDPRuntime
from repro.core.schedule import (APPROX_VARIANTS, GROUP_OVERRIDE_KEYS,
                                 CommSchedule, resolve_group_schedules)
from repro.core.store import ParamStore
from repro.launch.mesh import make_local_mesh
from repro.optim import make_optimizer
from repro.quant.blockwise import dequantize_blockwise, quantize_blockwise

MESH = make_local_mesh(1, 1)

Q8 = CommSchedule(param_store="q8_block")


def _build(schedule, arch="qwen2.5-14b", n_layers=None, optimizer=None,
           group_schedules=None):
    cfg = get_config(arch).reduced()
    if n_layers is not None:
        cfg = dataclasses.replace(cfg, n_layers=n_layers)
    if optimizer is not None:
        cfg = dataclasses.replace(cfg, optimizer=optimizer)
    rt = FSDPRuntime(build_model(cfg), MESH, schedule=schedule, donate=False,
                     group_schedules=group_schedules)
    return cfg, rt


def _train(schedule, steps=3, **kw):
    cfg, rt = _build(schedule, **kw)
    params = rt.init_params(0)
    opt = make_optimizer(cfg)
    state = opt.init(rt)
    fn = rt.make_train_step(opt)
    st = jnp.int32(0)
    rng = np.random.default_rng(0)
    losses = []
    for _ in range(steps):
        batch = {"tokens": jnp.asarray(
            rng.integers(0, cfg.vocab, (4, 16)), jnp.int32)}
        params, state, st, m = fn(params, state, st, batch)
        losses.append(float(m["loss"]))
    finals = {k: jax.tree.map(np.asarray, v) for k, v in params.items()}
    return losses, finals, rt


def _assert_trees_equal(a, b, msg):
    eq = jax.tree.map(np.array_equal, a, b)
    assert jax.tree.all(eq), (msg, eq)


# --------------------------------------------------------------------------- #
# fp32 regression + structure
# --------------------------------------------------------------------------- #

def test_fp32_store_explicit_is_default_bitwise():
    ref = _train(CommSchedule.default())
    tst = _train(CommSchedule(param_store="fp32"))
    assert ref[0] == tst[0]
    _assert_trees_equal(ref[1], tst[1], "fp32 store != default")
    # fp32 states are bare arrays: the seed's param format, unchanged
    assert all(isinstance(v, np.ndarray) for v in ref[1].values())


def test_q8_state_structure():
    _, rt = _build(Q8)
    params = rt.init_params(0)
    shapes = rt.param_shapes()
    for name, lo in rt.layouts.items():
        st = params[name]
        assert set(st) == {"codes", "master", "scales"}
        assert st["codes"].dtype == jnp.int8
        assert st["master"].dtype == jnp.float32
        assert st["master"].shape == lo.global_shape()
        assert st["scales"].shape[-1] * lo.store.block == lo.global_shape()[-1]
        assert {k: v.shape for k, v in shapes[name].items()} == {
            k: v.shape for k, v in st.items()}
        # the planner's align guarantee, extended to quantized stores:
        # shard size a multiple of the quant block, tensor starts aligned
        assert lo.plan.shard_size % lo.store.block == 0
        for pl in lo.plan.placements:
            assert pl.offset % lo.store.block == 0


def test_q8_codes_track_master_through_training():
    """After any number of fused update+requantize passes, the stored codes
    must equal the exact requantization of the stored master, and the
    dequantized weights must sit within the per-block int8 bound."""
    cfg, rt = _build(Q8)
    _, finals, _ = _train(Q8, steps=3)
    for name, st in finals.items():
        block = rt.layouts[name].store.block
        codes, scales = quantize_blockwise(
            jnp.asarray(st["master"]), block)
        np.testing.assert_array_equal(np.asarray(codes), st["codes"],
                                      err_msg=f"{name}: stale codes")
        deq = np.asarray(dequantize_blockwise(
            jnp.asarray(st["codes"]), jnp.asarray(st["scales"]), block))
        err = np.abs(deq - st["master"]).reshape(-1, block)
        sc = st["scales"].reshape(-1, 1)
        slack = 4 * np.finfo(np.float32).eps * np.abs(
            st["master"]).reshape(-1, block)
        assert (err <= sc / 2 + slack + 1e-7).all(), name


@pytest.mark.parametrize("name,sched", [
    ("ring", dataclasses.replace(Q8, gather_mode="ring")),
    ("prefetch", dataclasses.replace(Q8, prefetch=True)),
    ("ring_prefetch", APPROX_VARIANTS["q8_ring_prefetch"]),
    ("keep_last", dataclasses.replace(Q8, keep_last_gathered=True,
                                      prefetch=True)),
])
def test_q8_comm_variants_bitwise_consistent(name, sched):
    """xla/ring x prefetch/sequential move the same quantized payload in a
    different order: trajectories must agree bitwise at a fixed device
    count (the q8 twin of the fp32 parity suite)."""
    ref = _train(Q8, n_layers=3)
    tst = _train(sched, n_layers=3)
    assert ref[0] == tst[0], (name, ref[0], tst[0])
    _assert_trees_equal(ref[1], tst[1], f"q8:{name}")


def test_q8_tracks_fp32_loss():
    """Quantized-weight training follows the fp32 trajectory at int8
    resolution (QSDP's convergence claim at repro scale)."""
    ref, _, _ = _train(CommSchedule.default())
    q8, _, _ = _train(Q8)
    for r, q in zip(ref, q8):
        assert abs(r - q) < 0.05 * max(1.0, abs(r)), (ref, q8)
    assert all(np.isfinite(q8))


def test_q8_with_adam8bit_and_bf16_store():
    """q8 weights compose with int8 optimizer state (both block-quantized
    pipelines in one step); bf16 store trains and halves storage."""
    q8, _, _ = _train(Q8, optimizer="adam8bit", steps=2)
    assert all(np.isfinite(q8))
    ref, _, _ = _train(CommSchedule.default(), steps=2)
    bf, finals, rt = _train(CommSchedule(param_store="bf16"), steps=2)
    assert all(isinstance(v, np.ndarray) and v.dtype == jnp.bfloat16
               for v in finals.values())
    for r, b in zip(ref, bf):
        assert abs(r - b) < 0.05 * max(1.0, abs(r)), (ref, bf)


def test_q8_group_override_mixed_stores():
    """Per-group param_store: only the layer stack quantized, globals stay
    fp32 flat buffers."""
    losses, finals, rt = _train(
        CommSchedule.default(), steps=2,
        group_schedules={"layers": {"param_store": "q8_block"}})
    assert all(np.isfinite(losses))
    assert isinstance(finals["layers"], dict)
    assert isinstance(finals["globals"], np.ndarray)
    assert rt.layouts["layers"].store.quantized
    assert not rt.layouts["globals"].store.quantized


def test_q8_prefill_smoke():
    """The serve path gathers through the same store layer: prefill on a
    quantized store produces finite logits."""
    cfg, rt = _build(Q8)
    params = rt.init_params(0)
    cache = rt.model.init_cache(2, 16)
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab, (2, 8)), jnp.int32)
    logits, cache = rt.make_prefill_step()(params, {"tokens": tokens}, cache)
    assert np.isfinite(np.asarray(logits, np.float32)).all()


# --------------------------------------------------------------------------- #
# wire accounting
# --------------------------------------------------------------------------- #

def test_gather_wire_bytes_q8_vs_fp32():
    _, rt32 = _build(CommSchedule(gather_dtype="fp32"))
    _, rtq8 = _build(Q8)
    w32, wq8 = rt32.gather_wire_bytes(), rtq8.gather_wire_bytes()
    # exact formula: 4 B/elt fp32 vs 1 B/elt of codes + 4 B/block of scales
    expected = sum(
        (lo.plan.total + lo.plan.total // lo.store.block * 4)
        * (lo.n_layers or 1)
        for lo in rtq8.layouts.values() if lo.fsdp_axes)
    assert wq8 == expected
    ratio = w32 / wq8
    assert ratio > 3.5, f"q8 wire only {ratio:.2f}x smaller than fp32"
    # default (bf16 wire) sits in between
    _, rtbf = _build(CommSchedule.default())
    assert wq8 < rtbf.gather_wire_bytes() < w32


# --------------------------------------------------------------------------- #
# validation
# --------------------------------------------------------------------------- #

def test_store_validation():
    with pytest.raises(ValueError):
        CommSchedule(param_store="int4")
    with pytest.raises(ValueError):
        ParamStore("int4")
    with pytest.raises(ValueError):
        ParamStore("q8_block", 0)
    # q8 fixes the wire payload: a gather_dtype is contradictory
    with pytest.raises(ValueError):
        CommSchedule(param_store="q8_block",
                     gather_dtype="fp32").validate_for(jnp.bfloat16)
    CommSchedule(param_store="q8_block").validate_for(jnp.bfloat16)
    with pytest.raises(ValueError):
        CommSchedule(reduce_mode="tree")
    # param_store and reduce_mode are per-group overridable
    assert {"param_store", "reduce_mode"} <= GROUP_OVERRIDE_KEYS
    got = resolve_group_schedules(
        CommSchedule.default(), {"layers": {"param_store": "q8_block"}})
    assert got["layers"].param_store == "q8_block"


def test_q8_rejects_unaligned_baseline_planner():
    """Baseline planners don't honor align; quantized stores must fail
    loudly instead of producing straddling blocks."""
    cfg = get_config("qwen2.5-14b").reduced()
    try:
        rt = FSDPRuntime(build_model(cfg), MESH, planner="fsdp2",
                         schedule=Q8, donate=False)
    except ValueError:
        return  # unaligned shard size rejected at init: the guarantee
    # if the shard size happened to align, the plan must actually be valid
    for lo in rt.layouts.values():
        assert lo.plan.shard_size % lo.store.block == 0


# --------------------------------------------------------------------------- #
# 8-device: q8 over real shards, ring_acc parity, q8 checkpoint round-trip
# --------------------------------------------------------------------------- #

_DRIVER_8DEV = textwrap.dedent("""
    import os, sys, json, dataclasses, tempfile
    # FMA-free XLA:CPU, as in conftest.py: the comparison is bitwise
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                               "--xla_cpu_max_isa=AVX")
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_config, build_model
    from repro.configs.base import ParallelConfig
    from repro.core.fsdp import FSDPRuntime
    from repro.core.schedule import CommSchedule
    from repro.checkpoint import ckpt
    from repro.optim import make_optimizer
    from repro.launch.mesh import make_local_mesh

    MESH8 = make_local_mesh(8, 1)
    Q8 = CommSchedule(param_store="q8_block")

    def train(schedule, steps=2, mesh=MESH8):
        cfg = get_config("qwen2.5-14b").reduced()
        cfg = dataclasses.replace(cfg, n_layers=3,
                                  parallel=ParallelConfig(("data",), ("data",)))
        model = build_model(cfg)
        rt = FSDPRuntime(model, mesh, schedule=schedule, donate=False)
        params = rt.init_params(0)
        opt = make_optimizer(cfg)
        state = opt.init(rt)
        fn = rt.make_train_step(opt)
        st = jnp.int32(0)
        rng = np.random.default_rng(0)
        losses = []
        for i in range(steps):
            batch = {"tokens": jnp.asarray(
                rng.integers(0, cfg.vocab, (8, 16)), jnp.int32)}
            params, state, st, m = fn(params, state, st, batch)
            losses.append(float(m["loss"]))
        finals = {k: jax.tree.map(np.asarray, v) for k, v in params.items()}
        return losses, finals, (rt, params, state, opt)

    out = {}

    # q8 comm variants over 8-way FSDP: all bitwise-identical
    ref_l, ref_p, (rt, live_params, live_state, opt) = train(Q8)
    out["q8_finite"] = bool(np.isfinite(ref_l).all())
    bad = []
    for name, sched in {
        "ring": dataclasses.replace(Q8, gather_mode="ring"),
        "prefetch": dataclasses.replace(Q8, prefetch=True),
        "ring_prefetch": dataclasses.replace(Q8, gather_mode="ring",
                                             prefetch=True),
    }.items():
        l, p, _ = train(sched)
        if l != ref_l or not jax.tree.all(
                jax.tree.map(np.array_equal, ref_p, p)):
            bad.append(name)
    out["q8_bad_variants"] = bad

    # vs 1 device: same tolerance as the rest of the multidevice suite
    one_l, _, _ = train(Q8, mesh=make_local_mesh(1, 1))
    out["q8_vs_1dev"] = max(abs(a - b) / max(1.0, abs(a))
                            for a, b in zip(one_l, ref_l))

    # q8 checkpoint round-trip on real 8-way shards: master and codes
    # bitwise-preserved
    with tempfile.TemporaryDirectory() as d:
        ckpt.save(d, rt, live_params, live_state, step=2)
        p2, step, s2 = ckpt.load(d, rt, opt.init(rt))
        rt2 = None
        ok = step == 2
        for name in ref_p:
            for leaf in ("codes", "master", "scales"):
                ok = ok and np.array_equal(
                    np.asarray(live_params[name][leaf]),
                    np.asarray(p2[name][leaf]))
        out["ckpt_bitwise"] = bool(ok)

    # ring_acc reduce-scatter: allclose parity with the order-exact reduce
    d_l, d_p, _ = train(CommSchedule(reduce_dtype="fp32"))
    a_l, a_p, _ = train(CommSchedule(gather_mode="ring",
                                     reduce_mode="ring_acc",
                                     reduce_dtype="fp32"))
    close = jax.tree.all(jax.tree.map(
        lambda a, b: np.allclose(np.asarray(a, np.float32),
                                 np.asarray(b, np.float32),
                                 rtol=2e-2, atol=1e-4), d_p, a_p))
    out["ring_acc_losses"] = [d_l, a_l]
    out["ring_acc_allclose"] = bool(close)

    print(json.dumps(out))
""")


@pytest.mark.slow
def test_store_8dev_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _DRIVER_8DEV],
                         capture_output=True, text=True, env=env,
                         timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    data = json.loads(out.stdout.strip().splitlines()[-1])
    assert data["q8_finite"]
    assert data["q8_bad_variants"] == [], data
    assert data["q8_vs_1dev"] < 0.05, data
    assert data["ckpt_bitwise"], "q8 checkpoint not bitwise on 8 devices"
    assert data["ring_acc_allclose"], data["ring_acc_losses"]
    da, aa = data["ring_acc_losses"]
    for r, t in zip(da, aa):
        assert abs(r - t) < 0.05 * max(1.0, abs(r)), data["ring_acc_losses"]
