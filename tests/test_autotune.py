"""The measured-cost autotuner (ISSUE 8): comm-profile schema round-trips,
``CostModel.from_profile`` pricing, per-mode latency crossover, ring-chunk
selection, plan provenance (profile name + content hash) and plan-JSON
reproducibility from the recorded profile, builtin-vs-measured decision
divergence, and the bitwise neutrality of ``ring_chunk_elems`` on real
8-device shards (subprocess twin, CI's chunking parity suite)."""
import dataclasses
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.configs import build_model, get_config
from repro.core.policy import CostModel, make_plan
from repro.core.profile import (BUILTIN_NAME, CommProfile, CommSample,
                                SCHEMA, builtin_profile, load_profile)
from repro.core.schedule import GROUP_OVERRIDE_KEYS, CommSchedule
from repro.core.wire import _snap_chunk


def _model(arch="qwen2.5-14b"):
    return build_model(get_config(arch).reduced())


def _samples(direction, fmt, mode, per_elem_ns, sizes=(1 << 16, 1 << 20)):
    return [CommSample(direction=direction, fmt=fmt, mode=mode,
                       elems=e, chunk_elems=e, time_us=e * per_elem_ns * 1e-3)
            for e in sizes]


def _measured_profile(name="measured-test", world=8, sweep=()):
    """A deterministic 'measured' profile with the OPPOSITE economics of
    the builtin roofline: cast wires cheap (bf16 ring cheapest), q8 wires
    expensive (this backend's quant kernels are slow) -- the CPU truth the
    calibrated BENCH_comm.json also reports."""
    ns = {("gather", "fp32", "xla"): 4.0, ("gather", "fp32", "ring"): 4.0,
          ("gather", "bf16", "xla"): 2.0, ("gather", "bf16", "ring"): 0.5,
          ("gather", "q8_block", "xla"): 50.0,
          ("gather", "q8_block", "ring"): 50.0,
          ("reduce", "fp32", "xla"): 4.0, ("reduce", "fp32", "ring"): 4.0,
          ("reduce", "fp32", "ring_acc"): 4.0,
          ("reduce", "bf16", "xla"): 2.0, ("reduce", "bf16", "ring"): 0.5,
          ("reduce", "bf16", "ring_acc"): 0.5,
          ("reduce", "q8_block", "xla"): 100.0,
          ("reduce", "q8_block", "ring"): 100.0,
          ("reduce", "q8_block", "ring_acc"): 100.0}
    entries = []
    for (d, f, m), v in ns.items():
        entries.extend(_samples(d, f, m, v))
    entries.extend(sweep)
    return CommProfile(name=name, entries=tuple(entries), backend="cpu",
                       world=world, builtin=False, end_to_end=True,
                       quick=True)


_SWEEP = (
    # gather bf16 ring chunk sweep at 1<<20 (shard 131072 at world 8):
    # 16384-elem messages beat the shard-sized default (0.5 ns/elem)
    CommSample("gather", "bf16", "ring", 1 << 20, 65536,
               (1 << 20) * 0.45e-3),
    CommSample("gather", "bf16", "ring", 1 << 20, 16384,
               (1 << 20) * 0.4e-3),
)


# --------------------------------------------------------------------------- #
# schema + fitted curves
# --------------------------------------------------------------------------- #

def test_profile_round_trip_and_hash_stability():
    prof = _measured_profile(sweep=_SWEEP)
    again = CommProfile.from_json(json.loads(json.dumps(prof.to_json())))
    assert again == prof
    assert again.content_hash() == prof.content_hash()
    # the hash covers content: any entry change changes it
    other = _measured_profile(name="measured-test-2", sweep=_SWEEP)
    assert other.content_hash() != prof.content_hash()


def test_profile_schema_rejects_malformed():
    with pytest.raises(ValueError, match="ring_acc is a reduce-only"):
        CommProfile(name="x", entries=(CommSample(
            "gather", "fp32", "ring_acc", 8, 8, 1.0),))
    with pytest.raises(ValueError, match="chunk_elems"):
        CommProfile(name="x", entries=(CommSample(
            "gather", "fp32", "ring", 8, 16, 1.0),))
    with pytest.raises(ValueError, match="direction"):
        CommProfile(name="x", entries=(CommSample(
            "sideways", "fp32", "ring", 8, 8, 1.0),))
    with pytest.raises(ValueError, match="schema"):
        CommProfile.from_json({"schema": "comm-profile/v0", "name": "x",
                               "entries": []})


def test_profile_validator_cli(tmp_path):
    from repro.core import profile as profile_mod

    good = tmp_path / "ok.json"
    _measured_profile().save(good)
    assert profile_mod.main([str(good)]) == 0
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"schema": SCHEMA, "name": "x",
                               "entries": [{"direction": "gather"}]}))
    assert profile_mod.main([str(bad)]) == 1


def test_linear_fit_recovers_latency_and_slope():
    lat_s, ns = 2e-5, 3.0
    entries = tuple(CommSample("gather", "fp32", "xla", e, e,
                               (lat_s + e * ns * 1e-9) * 1e6)
                    for e in (1 << 14, 1 << 18, 1 << 20))
    prof = CommProfile(name="fit", entries=entries, world=4)
    lat, slope = prof.linear("gather", "fp32", "xla")
    assert lat == pytest.approx(lat_s, rel=1e-6)
    assert slope == pytest.approx(ns * 1e-9, rel=1e-6)
    # one point degenerates to pure slope; missing key raises
    one = CommProfile(name="one", entries=entries[:1])
    assert one.linear("gather", "fp32", "xla")[0] == 0.0
    with pytest.raises(KeyError):
        one.linear("reduce", "fp32", "xla")


def test_best_ring_chunk_search():
    prof = _measured_profile(sweep=_SWEEP)
    assert prof.best_ring_chunk("gather", "bf16") == 16384
    # no sweep for this key -> None; default-wins sweep -> None
    assert prof.best_ring_chunk("gather", "fp32") is None
    losing = (CommSample("gather", "fp32", "ring", 1 << 20, 16384,
                         (1 << 20) * 9.0e-3),)
    assert _measured_profile(sweep=losing).best_ring_chunk(
        "gather", "fp32") is None


def test_builtin_profile_fit_recovers_roofline_constants():
    prof = builtin_profile(ici_bw=50e9, latency_s=5e-6)
    assert prof.name == BUILTIN_NAME and prof.builtin
    lat, slope = prof.linear("gather", "fp32", "xla")
    assert lat == pytest.approx(5e-6, rel=1e-9)
    assert slope == pytest.approx(4.0 / 50e9, rel=1e-9)


# --------------------------------------------------------------------------- #
# CostModel: per-mode latency (satellite) + measured pricing
# --------------------------------------------------------------------------- #

def test_per_mode_latency_crossover():
    cm = CostModel(ici_bw=1e11, hbm_bw=1e12, peak_flops=1e15,
                   xla_latency_s=1e-3, ring_hop_latency_s=1e-3)
    # no collective at m=1: modes price identically
    assert cm._latency("xla", 1) == cm._latency("ring", 1) == 0.0
    assert cm.gather_time("fp32", 1 << 10, 1, 1, 1024, 4, mode="xla") == \
        cm.gather_time("fp32", 1 << 10, 1, 1, 1024, 4, mode="ring")
    # at m>=2 the ring pays m-1 hops vs one xla issue: same wire volume,
    # so the builtin roofline never picks ring
    assert cm._latency("ring", 8) == 7 * cm._latency("xla", 8)
    assert cm.choose_gather(1 << 20, 4, 8, 1024, 2)[1] == "xla"
    # measured curves CAN cross: a high-latency/low-slope xla curve vs a
    # low-latency/high-slope ring curve -- latency dominates tiny buffers
    # (ring wins), bandwidth dominates big ones (xla wins back)
    def pts(mode, lat_s, ns):
        return tuple(CommSample("gather", "fp32", mode, e, e,
                                (lat_s + e * ns * 1e-9) * 1e6)
                     for e in (1 << 16, 1 << 20))
    prof = CommProfile(name="xover", world=8,
                       entries=pts("xla", 1e-3, 1.0) + pts("ring", 1e-5, 4.0))
    mcm = CostModel.from_profile(prof)

    def t(mode, elems):
        return mcm.gather_time("fp32", elems, 1, 8, 1024, 4, mode=mode)
    assert t("ring", 1 << 14) < t("xla", 1 << 14)
    assert t("xla", 1 << 22) < t("ring", 1 << 22)


def test_auto_latency_dominated_group_replicates():
    # the replicate threshold is the planner-level expression of the
    # latency crossover: a tiny unstacked group's per-step gather latency
    # outweighs the shard's memory win, so auto keeps it replicated
    model = _model()
    p = make_plan(model, {"data": 8}, "auto")
    assert not p.groups["globals"].policy.sharded
    cm0 = dataclasses.replace(CostModel.default(), replicate_bytes=0)
    p0 = make_plan(model, {"data": 8}, "auto", cost_model=cm0)
    assert p0.groups["globals"].policy.sharded


def test_measured_time_rescales_ring_volume():
    prof = _measured_profile(world=8)
    cm = CostModel.from_profile(prof)
    t8 = cm._measured_time("gather", "fp32", "xla", 1 << 20, 8)
    t2 = cm._measured_time("gather", "fp32", "xla", 1 << 20, 2)
    # (m-1)/m volume: m=2 ships (1/2)/(7/8) of the world-8 measurement
    assert t2 == pytest.approx(t8 * (1 / 2) / (7 / 8), rel=1e-9)
    assert cm._measured_time("gather", "fp32", "xla", 1 << 20, 1) == \
        pytest.approx(0.0, abs=1e-12)
    # keys the profile lacks fall back to the builtin roofline (None)
    assert cm._measured_time("gather", "missing", "xla", 1 << 20, 8) is None


def test_from_profile_back_derives_bandwidth():
    cm = CostModel.from_profile(_measured_profile())
    # fp32 gather xla curve: 4 ns/elem = 4 B / 1e9 B/s
    assert cm.ici_bw == pytest.approx(1e9, rel=1e-6)
    assert cm.measured
    assert not CostModel.default().measured
    assert CostModel.default().provenance_profile().name == BUILTIN_NAME


# --------------------------------------------------------------------------- #
# the tentpole: measured profile drives planning + ring chunking
# --------------------------------------------------------------------------- #

def test_auto_decision_diverges_builtin_vs_measured():
    model = _model()
    mesh = {"data": 8}
    p_b = make_plan(model, mesh, "auto")
    prof = _measured_profile(sweep=_SWEEP)
    p_m = make_plan(model, mesh, "auto",
                    cost_model=CostModel.from_profile(prof))

    pol_b = p_b.groups["layers"].policy
    pol_m = p_m.groups["layers"].policy
    # builtin roofline: bandwidth-bound stack -> q8_block over xla
    assert (pol_b.store, pol_b.gather_mode) == ("q8_block", "xla")
    assert pol_b.ring_chunk_elems is None
    # measured (q8 codecs expensive, bf16 ring cheap, chunk sweep winner):
    # format AND route AND chunking all flip
    assert (pol_m.store, pol_m.gather_mode) == ("bf16", "ring")
    assert pol_m.ring_chunk_elems == 16384

    # the decision is visible: provenance + both pricings in describe()
    d_b, d_m = p_b.describe(), p_m.describe()
    assert f"profile={BUILTIN_NAME}@{p_b.profile_hash}" in d_b
    assert f"profile=measured-test@{prof.content_hash()}" in d_m
    for d in (d_b, d_m):
        assert "auto_ms" in d and "builtin_ms" in d
    assert "chunk=16384" in d_m
    # measured plan prices its own choice below the builtin roofline's
    # pricing of it; the builtin plan agrees with itself
    pr = p_m.pricing["layers"]
    assert pr["auto_ms"] != pr["builtin_ms"]
    assert p_b.pricing["layers"]["auto_ms"] == \
        p_b.pricing["layers"]["builtin_ms"]


@pytest.mark.parametrize("axes", [{"data": 1}, {"data": 8}])
def test_plan_reproducible_from_recorded_profile(axes, tmp_path):
    model = _model()
    path = tmp_path / "BENCH_comm.json"
    _measured_profile(sweep=_SWEEP).save(path)
    prof = load_profile(path)
    p1 = make_plan(model, axes, "auto",
                   cost_model=CostModel.from_profile(prof))
    assert p1.profile_name == prof.name
    assert p1.profile_hash == prof.content_hash()
    # re-planning from the recorded profile is plan-JSON-equal
    p2 = make_plan(model, axes, "auto",
                   cost_model=CostModel.from_profile(load_profile(path)))
    assert p1.dumps() == p2.dumps()
    # ... and a builtin re-plan records ITS provenance, distinct hash
    p3 = make_plan(model, axes, "auto")
    assert p3.profile_name == BUILTIN_NAME
    assert p3.profile_hash != p1.profile_hash
    # round-trip preserves provenance, pricing, and the chunk knob
    from repro.core.policy import ShardingPlan

    back = ShardingPlan.from_json(json.loads(p1.dumps()))
    assert back.dumps() == p1.dumps()
    assert back.profile_hash == p1.profile_hash
    assert back.groups["layers"].policy.ring_chunk_elems == \
        p1.groups["layers"].policy.ring_chunk_elems


def test_checkpointed_profile_artifact_prices_plan(tmp_path):
    # the calibrated-artifact workflow end to end: save a profile, load it
    # from an arbitrary path, plan, and confirm the plan says so
    path = tmp_path / "anywhere" / "profile.json"
    path.parent.mkdir()
    prof = _measured_profile()
    prof.save(path)
    cm = CostModel.from_profile(str(path))
    p = make_plan(_model(), {"data": 8}, "auto", cost_model=cm)
    assert p.profile_name == "measured-test"
    assert p.profile_hash == prof.content_hash()


# --------------------------------------------------------------------------- #
# the ring_chunk_elems knob (schedule-level)
# --------------------------------------------------------------------------- #

def test_ring_chunk_schedule_validation():
    s = CommSchedule(gather_mode="ring", ring_chunk_elems=4096)
    assert "chunk=4096" in s.describe()
    assert "ring_chunk_elems" in GROUP_OVERRIDE_KEYS
    with pytest.raises(ValueError, match="ring_chunk_elems"):
        CommSchedule(gather_mode="ring", ring_chunk_elems=0)
    with pytest.raises(ValueError, match="ring_chunk_elems"):
        CommSchedule(gather_mode="ring", ring_chunk_elems=True)
    with pytest.raises(ValueError, match="manual ring"):
        CommSchedule(ring_chunk_elems=4096)  # xla/match: knob is inert
    # legal wherever a manual ring actually runs
    CommSchedule(reduce_mode="ring_acc", ring_chunk_elems=64)
    CommSchedule(reduce_wire="q8_block", ring_chunk_elems=1024)


def test_snap_chunk_divisor_rule():
    assert _snap_chunk(1024, None) == 1024
    assert _snap_chunk(1024, 2048) == 1024      # >= rows: no split
    assert _snap_chunk(1024, 256) == 256        # exact divisor
    assert _snap_chunk(1024, 300) == 256        # snaps down to a divisor
    assert _snap_chunk(1000, 300) == 250
    assert _snap_chunk(1024, 1) == 1
    # unit alignment (q8 codes: chunk must hold whole quant blocks)
    assert _snap_chunk(4096, 1500, unit=1024) == 1024
    assert _snap_chunk(4096, 5, unit=1024) == 1024
    assert _snap_chunk(4100, 1024, unit=1024) == 4100  # rows not aligned


# --------------------------------------------------------------------------- #
# 8-device: chunked rings are bitwise-neutral at the wire layer on real
# meshes -- every route, forward and VJP, including non-divisor snaps --
# and a chunked train step keeps the loss stream (the CI chunking parity
# suite; subprocess so the device count is per-test).  DESIGN.md
# SS Autotuning documents why the e2e pin is loss parity rather than
# end-state bit equality: enabling chunking recompiles the whole-step
# program and XLA:CPU drifts a few ULPs in gradients even though every
# wire call is bitwise in isolation.
# --------------------------------------------------------------------------- #

_DRIVER_CHUNK_8DEV = textwrap.dedent("""
    import os, sys, json, dataclasses
    # FMA-free XLA:CPU, as in conftest.py: the comparison is bitwise
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                               "--xla_cpu_max_isa=AVX")
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from jax.experimental.shard_map import shard_map
    from repro.configs import get_config, build_model
    from repro.configs.base import ParallelConfig
    from repro.core.fsdp import FSDPRuntime
    from repro.core.schedule import CommSchedule
    from repro.core.wire import (WireCodec, _snap_chunk, codec_gather,
                                 codec_reduce_scatter)
    from repro.launch.mesh import make_local_mesh
    from repro.optim import make_optimizer

    MESH8 = make_local_mesh(8, 1)
    AXES, SIZES = ("data",), (8,)
    # 32768 divides neither tested shard-row count, so every route also
    # exercises the snap-to-divisor path (82176 -> 27392, 32896 -> 16448,
    # both whole multiples of the quant block)
    CHUNK = 32768
    out = {}

    # ---- wire layer: chunked == unchunked, bit for bit, per route ---- #
    rng = np.random.default_rng(0)
    bf16 = jnp.dtype(jnp.bfloat16)

    def gather_pair(shard, gc, rcc, mode, rmode, chunk):
        x = jnp.asarray(rng.standard_normal(shard * 8), jnp.float32)
        ct = jnp.asarray(rng.standard_normal(shard * 8),
                         jnp.float32).astype(bf16)
        def body(xs, c):
            y, vjp = jax.vjp(lambda v: codec_gather(
                v, AXES, SIZES, gc, rcc, bf16, jnp.float32, mode, rmode,
                chunk), xs)
            (g,) = vjp(c)
            return y, g
        f = shard_map(body, mesh=MESH8, in_specs=(P("data"), P(None)),
                      out_specs=(P(None), P("data")), check_rep=False)
        y, g = jax.jit(f)(x, ct)
        return np.asarray(y), np.asarray(g)

    def reduce_pair(shard, codec, mode, rmode, chunk, with_ef=False):
        ct = jnp.asarray(rng.standard_normal(shard * 8),
                         jnp.float32).astype(bf16)
        ef = (jnp.asarray(rng.standard_normal(shard * 8), jnp.float32)
              if with_ef else None)
        def body(c, *e):
            g, nef = codec_reduce_scatter(c, e[0] if e else None, codec,
                                          AXES, SIZES, mode, rmode,
                                          jnp.float32, chunk)
            return (g, nef) if e else (g,)
        ins = (P(None), P(None)) if with_ef else (P(None),)
        outs = (P("data"), P(None)) if with_ef else (P("data"),)
        f = shard_map(body, mesh=MESH8, in_specs=ins, out_specs=outs,
                      check_rep=False)
        args = (ct, ef) if with_ef else (ct,)
        return tuple(np.asarray(a) for a in jax.jit(f)(*args))

    for shard in (82176, 32896):
        tag = f"{shard}"
        snapped = _snap_chunk(shard, CHUNK)
        out[f"snap_{tag}"] = bool(0 < snapped < shard and snapped != CHUNK)
        gc = rcc = WireCodec("bf16")
        seed = rng.bit_generator.state
        a = gather_pair(shard, gc, rcc, "ring", "match", None)
        rng.bit_generator.state = seed
        b = gather_pair(shard, gc, rcc, "ring", "match", CHUNK)
        out[f"gather_vjp_bitwise_{tag}"] = bool(
            np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1]))
        for name, codec, rmode, ef in (
                ("reduce_ring", WireCodec("fp32"), "match", False),
                ("reduce_ring_acc", WireCodec("fp32"), "ring_acc", False),
                ("q8_route", WireCodec("q8_block", 64), "match", True),
                ("q8_ring_acc", WireCodec("q8_block", 64), "ring_acc",
                 True)):
            seed = rng.bit_generator.state
            a = reduce_pair(shard, codec, "ring", rmode, None, ef)
            rng.bit_generator.state = seed
            b = reduce_pair(shard, codec, "ring", rmode, CHUNK, ef)
            out[f"{name}_bitwise_{tag}"] = bool(all(
                np.array_equal(x, y) for x, y in zip(a, b)))

    # ---- e2e: a fully chunked train step keeps the loss stream ---- #
    def train(schedule, steps=2):
        cfg = get_config("qwen2.5-14b").reduced()
        cfg = dataclasses.replace(cfg, n_layers=2,
                                  parallel=ParallelConfig(("data",), ("data",)))
        model = build_model(cfg)
        rt = FSDPRuntime(model, MESH8, schedule=schedule, donate=False)
        params = rt.init_params(0)
        opt = make_optimizer(cfg)
        state = opt.init(rt)
        fn = rt.make_train_step(opt)
        st = jnp.int32(0)
        r = np.random.default_rng(0)
        losses = []
        for i in range(steps):
            batch = {"tokens": jnp.asarray(
                r.integers(0, cfg.vocab, (8, 16)), jnp.int32)}
            params, state, st, m = fn(params, state, st, batch)
            losses.append(float(m["loss"]))
        return losses, {k: jax.tree.map(np.asarray, v)
                        for k, v in params.items()}

    base = CommSchedule(gather_mode="ring")
    rl, rp = train(base)
    cl, cp = train(dataclasses.replace(base, ring_chunk_elems=CHUNK))
    out["e2e_loss_close"] = bool(all(
        abs(a - b) <= 1e-3 * max(1.0, abs(a)) for a, b in zip(rl, cl)))
    out["e2e_params_allclose"] = bool(jax.tree.all(jax.tree.map(
        lambda a, b: np.allclose(np.asarray(a, np.float32),
                                 np.asarray(b, np.float32),
                                 rtol=2e-2, atol=1e-4), rp, cp)))
    print(json.dumps(out))
""")


@pytest.mark.slow
def test_ring_chunk_bitwise_8dev_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _DRIVER_CHUNK_8DEV],
                         capture_output=True, text=True, env=env,
                         timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    data = json.loads(out.stdout.strip().splitlines()[-1])
    bad = {k: v for k, v in data.items() if not v}
    assert not bad, (bad, data)
