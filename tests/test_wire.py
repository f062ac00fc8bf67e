"""WireCodec layer (core.wire) and the quantized gradient reduce-scatter.

Guarantees under test:
  * codec units: cast codecs are pure ``astype`` round-trips; the q8_block
    codec's decode error is within the per-block int8 bound; wire-byte
    accounting matches the codec formulas.
  * lowering: ``reduce_wire="fp32"/"bf16"`` is bitwise-identical to the
    legacy ``reduce_dtype`` spelling (cast codecs ARE the legacy path) --
    on top of the unchanged test_schedule parity suite, which pins the
    whole refactor to the pre-codec trajectories.
  * q8_block reduce wire (QSDP): training stays finite and tracks the
    fp32-wire trajectory within 2%; the error-feedback residual lives in
    the param state tree, is nonzero after a step, updates exactly to
    ``compensated - decode(encode(compensated))``, and checkpoints /
    restores bitwise; xla and ring gather modes move the same quantized
    payload (bitwise-identical trajectories); ring_acc composes.
  * per-group ``reduce_wire`` overrides through group_schedules and
    PolicyRule; accounting: the q8 reduce wire is >= 3x smaller than an
    fp32 reduce wire.
  * validation: reduce_wire + reduce_dtype is rejected; q8 reduce on an
    unsharded group is rejected; unknown formats are rejected.  Microbatch
    accumulation with EF runs the DEFERRED path (one encode + reduce-
    scatter at the accumulation boundary) and tracks the microbatches=1
    trajectory.
  * fp8 plumbing (satellite): when the installed JAX has float8 dtypes,
    they are legal wire formats end to end without call-site changes.

The 8-device twin of this file is the subprocess scenario at the bottom
(slow marker), mirroring test_store's driver.
"""
import dataclasses
import json
import os
import subprocess
import sys
import tempfile
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compat
from repro.checkpoint import ckpt
from repro.configs import build_model, get_config
from repro.core.fsdp import FSDPRuntime
from repro.core.policy import (CostModel, PolicyRule, PolicySet,
                               ShardingPolicy, make_plan)
from repro.core.schedule import (APPROX_VARIANTS, GROUP_OVERRIDE_KEYS,
                                 CommSchedule, resolve_group_schedules)
from repro.core.store import EF_KEY, ParamStore
from repro.core.wire import (CAST_FORMATS, WIRE_FORMATS, WireCodec,
                             fmt_of_dtype)
from repro.launch.mesh import make_local_mesh
from repro.optim import make_optimizer
from repro.quant.blockwise import dequantize_blockwise, quantize_blockwise

MESH = make_local_mesh(1, 1)

Q8R = CommSchedule(reduce_wire="q8_block")


def _build(schedule, arch="qwen2.5-14b", n_layers=None, optimizer=None,
           group_schedules=None, policies=None):
    cfg = get_config(arch).reduced()
    if n_layers is not None:
        cfg = dataclasses.replace(cfg, n_layers=n_layers)
    if optimizer is not None:
        cfg = dataclasses.replace(cfg, optimizer=optimizer)
    rt = FSDPRuntime(build_model(cfg), MESH, schedule=schedule, donate=False,
                     group_schedules=group_schedules, policies=policies)
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
# codec units
# --------------------------------------------------------------------------- #

def test_cast_codec_roundtrip_and_bytes():
    x = jnp.asarray(np.random.default_rng(0).normal(size=256), jnp.float32)
    for fmt in ("fp32", "bf16"):
        c = WireCodec(fmt)
        y = c.decode(c.encode(x), jnp.float32)
        np.testing.assert_array_equal(
            np.asarray(y), np.asarray(x.astype(c.dtype).astype(jnp.float32)))
        assert c.wire_bytes(256) == 256 * c.dtype.itemsize
    assert fmt_of_dtype(jnp.bfloat16) == "bf16"
    assert fmt_of_dtype(jnp.float32) == "fp32"


def test_q8_codec_error_bound_and_bytes():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=512) * 10, jnp.float32)
    c = WireCodec("q8_block", 64)
    payload = c.encode(x)
    assert set(payload) == {"codes", "scales"}
    assert payload["codes"].dtype == jnp.int8
    y = np.asarray(c.decode(payload, jnp.float32))
    err = np.abs(y - np.asarray(x)).reshape(-1, 64)
    sc = np.asarray(payload["scales"]).reshape(-1, 1)
    assert (err <= sc / 2 + 1e-6).all()
    assert c.wire_bytes(512) == 512 + (512 // 64) * 4
    # q8 vs fp32: >= 3x fewer bytes even at the reduced block size of 64
    assert WireCodec("fp32").wire_bytes(512) / c.wire_bytes(512) >= 3.0
    with pytest.raises(ValueError):
        WireCodec("int4")
    with pytest.raises(ValueError):
        WireCodec("q8_block").dtype


# --------------------------------------------------------------------------- #
# lowering: cast reduce wires == legacy reduce_dtype, bitwise
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("fmt", ["fp32", "bf16"])
def test_cast_reduce_wire_is_reduce_dtype_bitwise(fmt):
    ref = _train(CommSchedule(reduce_dtype=fmt), steps=2)
    tst = _train(CommSchedule(reduce_wire=fmt), steps=2)
    assert ref[0] == tst[0], (fmt, ref[0], tst[0])
    _assert_trees_equal(ref[1], tst[1], f"reduce_wire={fmt}")


def test_reduce_wire_resolution():
    cd = jnp.dtype(jnp.bfloat16)
    s = CommSchedule(reduce_wire="fp32")
    assert s.accum_dtype(cd) == jnp.float32
    assert s.reduce_codec(cd).fmt == "fp32"
    s = CommSchedule(reduce_wire="q8_block")
    assert s.accum_dtype(cd) == jnp.float32  # dequant-accumulate in fp32
    assert s.reduce_codec(cd, 64) == WireCodec("q8_block", 64)
    assert s.ef_enabled
    # legacy default: reduce codec is the accum dtype's cast codec
    s = CommSchedule()
    assert s.reduce_codec(cd).fmt == "bf16"
    assert not s.ef_enabled


def test_reduce_wire_validation():
    with pytest.raises(ValueError):
        CommSchedule(reduce_wire="int4")
    with pytest.raises(ValueError):  # legacy + new spelling conflict
        CommSchedule(reduce_wire="fp32", reduce_dtype="fp32")
    with pytest.raises(ValueError):  # nothing to quantize when replicated
        CommSchedule(reduce_wire="q8_block",
                     sharded=False).validate_for(jnp.bfloat16)
    CommSchedule(reduce_wire="q8_block").validate_for(jnp.bfloat16)
    assert "reduce_wire" in GROUP_OVERRIDE_KEYS
    got = resolve_group_schedules(
        CommSchedule.default(), {"layers": {"reduce_wire": "q8_block"}})
    assert got["layers"].reduce_wire == "q8_block"
    # the two reduce spellings are one knob: a per-group override of one
    # displaces the base's other (no spurious both-set error)
    got = resolve_group_schedules(
        CommSchedule(reduce_dtype="fp32"),
        {"layers": {"reduce_wire": "q8_block"}})
    assert (got["layers"].reduce_wire == "q8_block"
            and got["layers"].reduce_dtype is None)
    got = resolve_group_schedules(
        CommSchedule(reduce_wire="q8_block"),
        {"globals": {"reduce_dtype": "fp32"}})
    assert (got["globals"].reduce_dtype == "fp32"
            and got["globals"].reduce_wire is None)


def test_microbatch_accumulation_with_ef_matches_single_batch():
    """Deferred EF: with microbatches > 1 the runtime accumulates fp32
    cotangents across micro-steps and runs ONE quantized reduce-scatter +
    error-feedback update at the accumulation boundary.  Because the mean
    over micro-slices of per-slice cotangents equals the full-batch
    cotangent, the deferred path must produce the same loss trajectory as
    microbatches=1 on the same global batch (up to bf16 activation
    accumulation order)."""
    from repro.configs.base import ParallelConfig

    def run(micro, steps=3):
        cfg = get_config("qwen2.5-14b").reduced()
        cfg = dataclasses.replace(cfg, parallel=ParallelConfig(
            ("data",), ("data",), microbatches=micro))
        rt = FSDPRuntime(build_model(cfg), MESH, schedule=Q8R, donate=False)
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
        assert all(np.isfinite(losses))
        return losses

    ref, acc = run(1), run(2)
    np.testing.assert_allclose(acc, ref, rtol=2e-2)


def test_replica_grad_axes_rejected_with_ef():
    """HSDP (pod replica) grads are psum'd across replicas AFTER the
    reduce-scatter, so each replica would compute a different EF residual
    under a state pspec that claims replication -- the runtime must
    reject the combination (quantized replica reductions are future
    work), and the auto planner must never emit it."""
    from repro.compat import make_mesh
    from repro.configs.base import ParallelConfig
    from repro.core.policy import auto_policies

    mesh = make_mesh((1, 1), ("pod", "data"))
    cfg = get_config("qwen2.5-14b").reduced()
    cfg = dataclasses.replace(cfg, parallel=ParallelConfig(
        ("data",), ("data",)))
    rt = FSDPRuntime(build_model(cfg), mesh, schedule=Q8R, donate=False)
    with pytest.raises(ValueError, match="replica"):
        rt.make_train_step(make_optimizer(cfg))
    # pod_fsdp extends ZeRO-3 over pods: no replica axis, EF is legal
    cfg_pf = dataclasses.replace(cfg, parallel=ParallelConfig(
        ("data",), ("data",), pod_fsdp=True))
    rt2 = FSDPRuntime(build_model(cfg_pf), mesh, schedule=Q8R, donate=False)
    rt2.make_train_step(make_optimizer(cfg_pf))
    # auto on an HSDP mesh keeps the exact wire
    pset = auto_policies(build_model(cfg), {"pod": 2, "data": 64})
    assert pset.default.reduce_wire is None
    assert all(r.policy.reduce_wire is None for r in pset.rules)


# --------------------------------------------------------------------------- #
# q8 gradient wire: training, EF residual semantics, state structure
# --------------------------------------------------------------------------- #

def test_q8_reduce_state_structure_and_align():
    _, rt = _build(Q8R)
    params = rt.init_params(0)
    shapes = rt.param_shapes()
    for name, lo in rt.layouts.items():
        st = params[name]
        assert lo.store.has_ef and lo.store.ef_m >= 1
        assert set(st) >= {"master", EF_KEY}
        assert st[EF_KEY].dtype == jnp.float32
        # the residual is m shard-lengths: the local gradient contribution
        assert (st[EF_KEY].shape[-1]
                == lo.global_shape()[-1] * lo.store.ef_m)
        assert np.all(np.asarray(st[EF_KEY]) == 0.0)  # fresh history
        assert {k: v.shape for k, v in shapes[name].items()} == {
            k: v.shape for k, v in st.items()}
        # the planner align guarantee, extended to the reduce wire:
        # reduce-scatter chunks (= shards) are block multiples
        assert lo.plan.shard_size % lo.store.block == 0


def test_q8_reduce_tracks_fp32_wire_loss():
    """The acceptance smoke: q8 gradient wire + error feedback reaches
    every step's loss within 2% of the fp32-wire trajectory."""
    ref, _, _ = _train(CommSchedule(), steps=5)
    q8, finals, _ = _train(Q8R, steps=5)
    assert all(np.isfinite(q8))
    for r, q in zip(ref, q8):
        assert abs(r - q) < 0.02 * max(1.0, abs(r)), (ref, q8)
    # EF is live: residuals are nonzero after training steps
    assert any(np.abs(finals[n][EF_KEY]).max() > 0 for n in finals)


def test_ef_residual_is_exact_quantization_error():
    """The reduce-combine rule's EF contract, checked on the codec
    directly: the new residual is exactly ``comp - decode(encode(comp))``
    for the compensated cotangent, and the shard is the decoded payload
    (m == 1 degenerates to the local quantize/dequantize round-trip).

    The expectation is composed UNDER JIT (kernels.ref.encode_ef_ref is
    the op-for-op unfused sequence): XLA contracts ``comp - codes*scale``
    into an fma on every backend, so a jitted residual differs from the
    eagerly-composed one by the fma's single rounding -- sub-ulp, and
    identical between the fused kernel and the jitted unfused path, which
    is the regime every training step runs in (DESIGN.md, parity-class
    convention)."""
    rng = np.random.default_rng(3)
    ct = jnp.asarray(rng.normal(size=256), jnp.float32)
    ef0 = jnp.asarray(rng.normal(size=256) * 0.01, jnp.float32)
    codec = WireCodec("q8_block", 64)
    from repro.core.wire import codec_reduce_scatter
    from repro.kernels.ref import encode_ef_ref

    want_codes, want_scales, want_ef = jax.jit(
        lambda c, e: encode_ef_ref(c, e, 64))(ct, ef0)
    shard, new_ef = codec_reduce_scatter(
        ct, ef0, codec, (), (), "xla", "match", jnp.dtype(jnp.float32))
    np.testing.assert_array_equal(np.asarray(new_ef), np.asarray(want_ef))
    np.testing.assert_array_equal(
        np.asarray(shard),
        np.asarray(codec.decode(
            {"codes": want_codes, "scales": want_scales}, jnp.float32)))


@pytest.mark.parametrize("name,sched", [
    ("ring", dataclasses.replace(Q8R, gather_mode="ring")),
    ("prefetch", dataclasses.replace(Q8R, prefetch=True)),
    ("keep_last", dataclasses.replace(Q8R, prefetch=True,
                                      keep_last_gathered=True)),
    ("q8_both", APPROX_VARIANTS["q8_both_wires"]),
])
def test_q8_reduce_comm_variants_consistent(name, sched):
    """Comm-path reorderings of the same quantized gradient payload are
    bitwise-identical at a fixed device count (q8_both additionally
    quantizes the store -- compared against its own xla/sequential
    twin)."""
    base = (APPROX_VARIANTS["q8_both_wires"] if name == "q8_both"
            else Q8R)
    tw = (dataclasses.replace(base, gather_mode="ring", prefetch=True)
          if name == "q8_both" else sched)
    ref = _train(base, n_layers=3, steps=2)
    tst = _train(tw, n_layers=3, steps=2)
    assert ref[0] == tst[0], (name, ref[0], tst[0])
    _assert_trees_equal(ref[1], tst[1], f"q8_reduce:{name}")


def test_q8_reduce_ring_acc_allclose():
    """ring_acc + q8 reduce wire (per-hop requantizing ring) on one device
    degenerates to the same quantize/dequantize round-trip -- bitwise here;
    the 8-device scenario asserts allclose."""
    ref = _train(Q8R, steps=2)
    tst = _train(APPROX_VARIANTS["q8_reduce_ring_acc"], steps=2)
    assert ref[0] == tst[0]
    _assert_trees_equal(ref[1], tst[1], "q8_reduce_ring_acc@1dev")


def test_q8_reduce_group_override_and_policy_rule():
    """Per-group reduce_wire: only the layer stack quantizes its gradient
    wire; globals keep the legacy dtype wire (bare-array state).  The
    PolicyRule spelling resolves to the same plan JSON."""
    losses, finals, rt = _train(
        CommSchedule.default(), steps=2,
        group_schedules={"layers": {"reduce_wire": "q8_block"}})
    assert all(np.isfinite(losses))
    assert isinstance(finals["layers"], dict) and EF_KEY in finals["layers"]
    assert isinstance(finals["globals"], np.ndarray)
    assert rt.layouts["layers"].store.has_ef
    assert not rt.layouts["globals"].store.has_ef

    pset = PolicySet(
        rules=(PolicyRule(match="layers",
                          policy=ShardingPolicy(reduce_wire="q8_block")),))
    cfg = get_config("qwen2.5-14b").reduced()
    p1 = make_plan(build_model(cfg), MESH, pset)
    assert p1.dumps() == rt.plan.dumps(), p1.diff(rt.plan)


def test_q8_reduce_with_optimizers_and_stores():
    """EF composes with the quantized store + int8 optimizer state (every
    block-quantized pipeline in one step) and with the bf16 store."""
    for kw in ({"optimizer": "adam8bit"},):
        losses, _, _ = _train(APPROX_VARIANTS["q8_both_wires"], steps=2,
                              **kw)
        assert all(np.isfinite(losses))
    losses, finals, _ = _train(
        CommSchedule(param_store="bf16", reduce_wire="q8_block"), steps=2)
    assert all(np.isfinite(losses))
    assert finals["layers"]["master"].dtype == jnp.bfloat16


# --------------------------------------------------------------------------- #
# checkpoints: EF residual round-trip
# --------------------------------------------------------------------------- #

def test_ef_checkpoint_roundtrip_and_cross_format():
    cfg, rt = _build(Q8R)
    params = rt.init_params(0)
    opt = make_optimizer(cfg)
    state = opt.init(rt)
    fn = rt.make_train_step(opt)
    rng = np.random.default_rng(0)
    st = jnp.int32(0)
    for _ in range(2):
        batch = {"tokens": jnp.asarray(
            rng.integers(0, cfg.vocab, (4, 16)), jnp.int32)}
        params, state, st, _ = fn(params, state, st, batch)
    with tempfile.TemporaryDirectory() as d:
        ckpt.save(d, rt, params, state, step=2)
        p2, step, s2 = ckpt.load(d, rt, opt.init(rt))
        assert step == 2
        for name in params:
            for leaf in ("master", EF_KEY):
                np.testing.assert_array_equal(
                    np.asarray(params[name][leaf]),
                    np.asarray(p2[name][leaf]),
                    err_msg=f"{name}.{leaf} not bitwise through ckpt")
        # cross-format restore: an fp32-wire runtime reads the EF
        # checkpoint through the master rebuild path (no residual leaf)
        _, rt32 = _build(CommSchedule())
        p3, _ = ckpt.load(d, rt32)
        for name in p3:
            assert not isinstance(p3[name], dict)
            np.testing.assert_array_equal(
                np.asarray(p3[name]),
                np.asarray(params[name]["master"]),
                err_msg=f"{name}: master lost in cross-format restore")
        # and the reverse: the EF runtime restores a plain checkpoint with
        # a fresh zero residual
        with tempfile.TemporaryDirectory() as d2:
            params32 = rt32.init_params(0)
            ckpt.save(d2, rt32, params32, step=0)
            p4, _ = ckpt.load(d2, rt)
            for name in p4:
                assert np.all(np.asarray(p4[name][EF_KEY]) == 0.0)


# --------------------------------------------------------------------------- #
# accounting + auto planner reduce pricing
# --------------------------------------------------------------------------- #

def test_reduce_wire_accounting():
    _, rt32 = _build(CommSchedule(reduce_dtype="fp32"))
    _, rtq8 = _build(Q8R)
    w32, wq8 = rt32.reduce_wire_bytes(), rtq8.reduce_wire_bytes()
    expected = sum(
        (lo.plan.total + lo.plan.total // lo.store.block * 4)
        * (lo.n_layers or 1)
        for lo in rtq8.layouts.values() if lo.fsdp_axes)
    assert wq8 == expected
    assert w32 / wq8 >= 3.0, f"q8 reduce wire only {w32 / wq8:.2f}x smaller"
    # default (bf16 accum) sits in between
    _, rtbf = _build(CommSchedule.default())
    assert wq8 < rtbf.reduce_wire_bytes() < w32
    # the plan json and describe() carry the reduce wire
    j = rtq8.plan.to_json()
    assert all(g["reduce_wire_mb"] > 0 for g in j["groups"].values()
               if g["fsdp_axes"])
    assert "reduce_wire_mb" in rtq8.plan.describe()
    assert "q8_block" in rtq8.plan.groups["layers"].policy.describe()


def test_cost_model_prices_reduce_direction():
    cm = CostModel(ici_bw=1e11, hbm_bw=1e12, peak_flops=1e15)
    # m=1: no wire at all -> the exact dtype wire wins (ties break exact)
    assert cm.choose_reduce_wire(1 << 20, 32, 1, 1024, 2) is None
    # bandwidth-bound stack at scale: the q8 gradient wire wins
    slow = CostModel(ici_bw=1e9, hbm_bw=1e12, peak_flops=1e15)
    assert slow.choose_reduce_wire(1 << 22, 32, 64, 1024, 2) == "q8_block"
    # and the auto planner threads it into policies on a big mesh
    cfg = get_config("qwen2.5-14b").reduced()
    pset = make_plan(build_model(cfg), {"data": 64}, "auto",
                     cost_model=slow).policy_set()
    pols = list({r.match: r.policy for r in pset.rules}.values()) + [
        pset.default]
    q8r = [p for p in pols if p.reduce_wire == "q8_block"]
    assert q8r
    # auto pairs the q8 gradient wire with the accumulate-in-flight ring
    # (the route the cost model's (m-1)/m volume is true of; match-mode
    # q8 ships (m-1)/2 x the payload)
    assert all(p.reduce_mode == "ring_acc" for p in q8r)
    # ...but never for an accumulating config: the EF wire does not
    # compose with microbatches, so auto must only score legal candidates
    from repro.configs.base import ParallelConfig

    cfg_mb = dataclasses.replace(cfg, parallel=ParallelConfig(
        ("data",), ("data",), microbatches=2))
    pset_mb = make_plan(build_model(cfg_mb), {"data": 64}, "auto",
                        cost_model=slow).policy_set()
    assert pset_mb.default.reduce_wire is None
    assert all(r.policy.reduce_wire is None for r in pset_mb.rules)


# --------------------------------------------------------------------------- #
# fp8 plumbing
# --------------------------------------------------------------------------- #

def test_fp8_dtypes_guarded():
    fp8 = compat.float8_dtypes()
    # fp8 names are legal cast wire formats end to end without call-site
    # changes
    assert set(fp8) == {"fp8_e4m3", "fp8_e5m2"}
    for name, dt in fp8.items():
        assert name in CAST_FORMATS and name in WIRE_FORMATS
        c = WireCodec(name)
        assert c.dtype == dt
        assert c.wire_bytes(128) == 128  # 1 byte/element
        assert fmt_of_dtype(dt) == name
        x = jnp.asarray([0.5, -1.0, 2.0], jnp.float32)
        y = c.decode(c.encode(x), jnp.float32)
        assert np.isfinite(np.asarray(y)).all()
    # schedule-level: fp8 is a legal gather wire dtype name...
    CommSchedule(gather_dtype="fp8_e4m3").validate_for(jnp.bfloat16)
    # ...and a legal cast reduce wire
    s = CommSchedule(reduce_wire="fp8_e5m2")
    assert s.reduce_codec(jnp.dtype(jnp.bfloat16)).fmt == "fp8_e5m2"
    # ...and, since the fused update kernels landed, a ParamStore format
    # too (fp8 codes + fp32 master; tests/test_fp8_store.py owns it)
    st = ParamStore("fp8_e4m3")
    assert st.fp8 and st.align() == 1


def test_fp8_gather_wire_train_smoke():
    losses, _, _ = _train(CommSchedule(gather_dtype="fp8_e4m3",
                                       reduce_dtype="fp32"), steps=2)
    assert all(np.isfinite(losses))


# --------------------------------------------------------------------------- #
# 8-device: q8 reduce over real shards (xla==ring bitwise, ring_acc
# allclose, fp32-wire tracking, EF checkpoint round-trip)
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
    from repro.core.store import EF_KEY
    from repro.checkpoint import ckpt
    from repro.optim import make_optimizer
    from repro.launch.mesh import make_local_mesh

    MESH8 = make_local_mesh(8, 1)
    Q8R = CommSchedule(reduce_wire="q8_block")

    def train(schedule, steps=2, mesh=MESH8, group_schedules=None):
        cfg = get_config("qwen2.5-14b").reduced()
        cfg = dataclasses.replace(cfg, n_layers=3,
                                  parallel=ParallelConfig(("data",), ("data",)))
        model = build_model(cfg)
        rt = FSDPRuntime(model, mesh, schedule=schedule, donate=False,
                         group_schedules=group_schedules)
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

    # q8 gradient wire over real 8-way FSDP
    ref_l, ref_p, (rt, live_params, live_state, opt) = train(Q8R)
    out["finite"] = bool(np.isfinite(ref_l).all())
    out["ef_nonzero"] = bool(
        max(np.abs(p[EF_KEY]).max() for p in ref_p.values()) > 0)

    # xla vs ring gather modes move the same once-encoded payload and
    # accumulate in absolute device order: bitwise-identical
    bad = []
    for name, sched in {
        "ring": dataclasses.replace(Q8R, gather_mode="ring"),
        "prefetch": dataclasses.replace(Q8R, prefetch=True),
        "ring_prefetch": dataclasses.replace(Q8R, gather_mode="ring",
                                             prefetch=True),
    }.items():
        l, p, _ = train(sched)
        if l != ref_l or not jax.tree.all(
                jax.tree.map(np.array_equal, ref_p, p)):
            bad.append(name)
    out["bad_variants"] = bad

    # allclose tracking vs the fp32 reduce wire (QSDP's convergence claim)
    f32_l, _, _ = train(CommSchedule(reduce_dtype="fp32"))
    out["vs_fp32_wire"] = max(abs(a - b) / max(1.0, abs(a))
                              for a, b in zip(f32_l, ref_l))

    # ring_acc (per-hop requantizing accumulate-in-flight ring): allclose
    a_l, a_p, _ = train(CommSchedule(gather_mode="ring",
                                     reduce_mode="ring_acc",
                                     reduce_wire="q8_block"))
    out["ring_acc_rel"] = max(abs(a - b) / max(1.0, abs(a))
                              for a, b in zip(ref_l, a_l))
    out["ring_acc_allclose"] = bool(all(
        np.allclose(np.asarray(ref_p[n]["master"], np.float32),
                    np.asarray(a_p[n]["master"], np.float32),
                    rtol=2e-2, atol=1e-3)
        for n in ref_p))

    # per-group override on real shards: layers quantized, globals legacy
    g_l, g_p, _ = train(CommSchedule(),
                        group_schedules={"layers":
                                         {"reduce_wire": "q8_block"}})
    out["override_finite"] = bool(np.isfinite(g_l).all())
    out["override_shapes_ok"] = bool(
        isinstance(g_p["layers"], dict) and EF_KEY in g_p["layers"]
        and not isinstance(g_p["globals"], dict))

    # EF residual checkpoint round-trip on real 8-way shards
    with tempfile.TemporaryDirectory() as d:
        ckpt.save(d, rt, live_params, live_state, step=2)
        p2, step, s2 = ckpt.load(d, rt, opt.init(rt))
        ok = step == 2
        for name in ref_p:
            for leaf in ("master", EF_KEY):
                ok = ok and np.array_equal(
                    np.asarray(live_params[name][leaf]),
                    np.asarray(p2[name][leaf]))
        out["ckpt_bitwise"] = bool(ok)

    # reduce-wire accounting on the 8-way plan: the >=3x q8 win holds on
    # the bandwidth-optimal (ring_acc) route; the order-exact match-mode
    # q8 route honestly reports its m/2 un-reduced-chunk multiplier
    cfg32 = dataclasses.replace(
        get_config("qwen2.5-14b").reduced(), n_layers=3,
        parallel=ParallelConfig(("data",), ("data",), reduce_dtype="fp32"))
    rt32 = FSDPRuntime(build_model(cfg32), MESH8, donate=False)
    cfg_acc = dataclasses.replace(
        get_config("qwen2.5-14b").reduced(), n_layers=3,
        parallel=ParallelConfig(("data",), ("data",),
                                reduce_wire="q8_block",
                                reduce_mode="ring_acc"))
    rt_acc = FSDPRuntime(build_model(cfg_acc), MESH8, donate=False)
    out["wire_ratio"] = rt32.reduce_wire_bytes() / rt_acc.reduce_wire_bytes()
    out["match_q8_times_m_over_2"] = (
        rt.reduce_wire_bytes() == rt_acc.reduce_wire_bytes() * 8 // 2)

    print(json.dumps(out))
""")


@pytest.mark.slow
def test_wire_8dev_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _DRIVER_8DEV],
                         capture_output=True, text=True, env=env,
                         timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    data = json.loads(out.stdout.strip().splitlines()[-1])
    assert data["finite"] and data["ef_nonzero"]
    assert data["bad_variants"] == [], data
    assert data["vs_fp32_wire"] < 0.02, data
    assert data["ring_acc_rel"] < 0.05, data
    assert data["ring_acc_allclose"], data
    assert data["override_finite"] and data["override_shapes_ok"], data
    assert data["ckpt_bitwise"], "EF residual not bitwise through ckpt"
    assert data["wire_ratio"] >= 3.0, data
    assert data["match_q8_times_m_over_2"], data
