"""fp8 ParamStore formats (fp8_e4m3 / fp8_e5m2): float8 codes + fp32
master shard.

Guarantees under test:

  * state structure: ``{"codes", "master"}``, codes always the exact fp8
    cast of the master (create, rebuild, and through real training);
    scale-free, so no planner alignment requirement (``align() == 1``).
  * schedule plumbing: ``gather_dtype`` is rejected alongside an fp8
    ``param_store`` (the codes ARE the wire payload); the fp8
    APPROX_VARIANTS exist; wire_bytes is 1 B/element.
  * training: an fp8 group trains end to end on 1 device (loss
    decreases, codes track the master bitwise) and under the ring+
    prefetch schedule (same payload, reordered comm -- bitwise equal).
  * checkpoints: a same-layout restore is bitwise on codes AND master
    (codes round-trip through the fp32-widened .npy via _savable);
    cross-format restores re-derive the codes from the master.
  * policy: the builtin roofline never nominates fp8 (its analytic
    fp8-over-q8 gap is pure scales overhead -- 4/quant_block B/elem --
    not evidence of a faster fused cast), so historical auto decisions
    are pinned at every block size; only a *measured* profile with a
    genuinely faster fp8 gather curve, clearing FP8_NEAR_TIE_RTOL,
    flips the choice.  Plans over fp8 groups declare fp8 wire legs
    and the src_dtype-carrying no_f32_dequant invariant, and pass the
    static verifier.

The 8-device subprocess at the bottom drives the acceptance scenario:
an fp8 group trains on 8-way shards, checkpoints, and restores onto a
4-way mesh (elastic reshard) with the master bit-preserved.
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

from repro import compat
from repro.checkpoint import ckpt
from repro.configs import build_model, get_config
from repro.core.fsdp import FSDPRuntime
from repro.core.policy import CostModel, make_plan
from repro.core.schedule import APPROX_VARIANTS, CommSchedule
from repro.core.store import ParamStore
from repro.launch.mesh import make_local_mesh
from repro.optim import make_optimizer

MESH = make_local_mesh(1, 1)

FP8_FMTS = sorted(compat.float8_dtypes())


def _u8(a):
    """Bitpattern view -- fp8 NaN-safe equality."""
    return np.asarray(a).view(np.uint8)


def _build(schedule, arch="qwen2.5-14b", optimizer=None):
    cfg = get_config(arch).reduced()
    if optimizer is not None:
        cfg = dataclasses.replace(cfg, optimizer=optimizer)
    rt = FSDPRuntime(build_model(cfg), MESH, schedule=schedule, donate=False)
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


# --------------------------------------------------------------------------- #
# store structure
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("fmt", FP8_FMTS)
def test_fp8_state_structure(fmt):
    s = ParamStore(fmt)
    assert s.fp8 and not s.quantized
    assert s.state_keys() == ("codes", "master")
    assert s.leaf_dtype("codes") == s.fp8_dtype
    assert s.leaf_dtype("master") == jnp.float32
    assert s.align() == 1           # scale-free: no block requirement
    assert s.wire_bytes(1000, np.float32) == 1000  # 1 B/element

    master = np.linspace(-2, 2, 640, dtype=np.float32)
    state = s.create(master)
    assert set(state) == {"codes", "master"}
    np.testing.assert_array_equal(state["master"], master)
    np.testing.assert_array_equal(
        _u8(state["codes"]),
        _u8(jnp.asarray(master).astype(s.fp8_dtype)))

    # trainable/frozen/combine round-trip
    tr, fz = s.trainable(state), s.frozen(state)
    np.testing.assert_array_equal(np.asarray(tr), master)
    assert set(fz) == {"codes"}
    back = s.combine(tr, fz)
    np.testing.assert_array_equal(_u8(back["codes"]), _u8(state["codes"]))

    # rebuild re-derives the codes from the new master in the same pass
    new = jnp.asarray(master * 0.5)
    reb = s.rebuild(new)
    np.testing.assert_array_equal(
        _u8(reb["codes"]), _u8(new.astype(s.fp8_dtype)))
    np.testing.assert_array_equal(np.asarray(reb["master"]), np.asarray(new))


def test_fp8_dtype_guarded():
    with pytest.raises(ValueError):
        ParamStore("fp32").fp8_dtype


def test_fp8_schedule_validation():
    cfg = get_config("qwen2.5-14b").reduced()
    model = build_model(cfg)
    with pytest.raises(ValueError, match="gather_dtype"):
        FSDPRuntime(model, MESH, schedule=CommSchedule(
            param_store="fp8_e4m3", gather_dtype="bf16"), donate=False)
    for name in ("fp8_store", "fp8_e5m2_store", "fp8_ring_prefetch"):
        assert name in APPROX_VARIANTS, name


# --------------------------------------------------------------------------- #
# training end to end (1 device)
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("fmt", FP8_FMTS)
def test_fp8_trains_and_codes_track_master(fmt):
    ref, _, _ = _train(CommSchedule(), steps=4)
    losses, finals, rt = _train(CommSchedule(param_store=fmt), steps=4)
    assert all(np.isfinite(losses))
    # the fp8 forward tracks the fp32 run (measured: e4m3 ~0.5%, e5m2
    # ~2.5% max step deviation); a broken grad proxy diverges by whole
    # units within a step or two
    for a, b in zip(losses, ref):
        assert abs(a - b) < 0.10 * max(1.0, abs(b)), (losses, ref)
    dt = jnp.dtype(compat.float8_dtypes()[fmt])
    for name, state in finals.items():
        assert set(state) == {"codes", "master"}
        assert state["master"].dtype == np.float32
        np.testing.assert_array_equal(
            _u8(state["codes"]),
            _u8(jnp.asarray(state["master"]).astype(dt)),
            err_msg=f"{name}: codes are not the exact fp8 cast")


def test_fp8_ring_prefetch_bitwise_matches_xla():
    """Comm-path reorderings of the same fp8 payload are bitwise equal."""
    _, a, _ = _train(CommSchedule(param_store="fp8_e4m3"), steps=2)
    _, b, _ = _train(APPROX_VARIANTS["fp8_ring_prefetch"], steps=2)
    for name in a:
        for leaf in a[name]:
            np.testing.assert_array_equal(
                _u8(a[name][leaf]), _u8(b[name][leaf]),
                err_msg=f"{name}/{leaf}")


def test_fp8_with_adam8bit():
    losses, finals, _ = _train(CommSchedule(param_store="fp8_e4m3"),
                               steps=4, optimizer="adam8bit")
    assert all(np.isfinite(losses)), losses
    for state in finals.values():
        assert set(state) == {"codes", "master"}


# --------------------------------------------------------------------------- #
# checkpoints
# --------------------------------------------------------------------------- #

def test_fp8_checkpoint_roundtrip_bitwise(tmp_path):
    sched = CommSchedule(param_store="fp8_e4m3")
    cfg, rt = _build(sched)
    params = rt.init_params(0)
    opt = make_optimizer(cfg)
    state = opt.init(rt)
    fn = rt.make_train_step(opt)
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab, (4, 16)), jnp.int32)}
    params, state, _, _ = fn(params, state, jnp.int32(0), batch)

    ckpt.save(tmp_path / "c", rt, params, state, step=1)
    _, rt2 = _build(sched)
    p2, step, s2 = ckpt.load(tmp_path / "c", rt2, opt.init(rt2))
    assert step == 1
    for name in params:
        np.testing.assert_array_equal(
            _u8(params[name]["codes"]), _u8(p2[name]["codes"]),
            err_msg=f"{name}: codes not bitwise through save/load")
        np.testing.assert_array_equal(
            np.asarray(params[name]["master"]),
            np.asarray(p2[name]["master"]))


def test_fp8_cross_format_restore(tmp_path):
    """fp32 ckpt -> fp8 runtime re-derives codes from the master; fp8
    ckpt -> fp32 runtime keeps the master bit for bit."""
    _, rt32 = _build(CommSchedule())
    params = rt32.init_params(0)
    ckpt.save(tmp_path / "a", rt32, params, step=1)

    _, rt8 = _build(CommSchedule(param_store="fp8_e4m3"))
    p8, _ = ckpt.load(tmp_path / "a", rt8)
    dt = jnp.dtype(compat.float8_dtypes()["fp8_e4m3"])
    for name in p8:
        np.testing.assert_array_equal(
            np.asarray(p8[name]["master"]), np.asarray(params[name]))
        np.testing.assert_array_equal(
            _u8(p8[name]["codes"]),
            _u8(jnp.asarray(p8[name]["master"]).astype(dt)))

    ckpt.save(tmp_path / "b", rt8, p8, step=2)
    back, _ = ckpt.load(tmp_path / "b", rt32)
    for name in back:
        np.testing.assert_array_equal(
            np.asarray(back[name]), np.asarray(params[name]))


# --------------------------------------------------------------------------- #
# policy: near-tie pricing + invariants + static verify
# --------------------------------------------------------------------------- #

def test_fp8_builtin_pricing_is_pinned_by_near_tie_band():
    """The builtin roofline never nominates fp8: its analytic fp8-over-q8
    "win" is just the per-block scales overhead (4/quant_block B/elem),
    not measured evidence of a faster fused cast -- so auto keeps its
    historical q8_block/fp32 decisions at every block size, even block 64
    where the apparent gap (~4%) exceeds FP8_NEAR_TIE_RTOL."""
    cm = CostModel.default()
    kw = dict(elems_per_layer=1 << 20, n_layers=3, m=8, quant_block=1024,
              compute_itemsize=2)
    assert cm.choose_store(**kw) == "q8_block"
    t_q8 = cm.gather_time("q8_block", **kw)
    t_f8 = cm.gather_time("fp8_e4m3", **kw)
    assert t_f8 < t_q8          # fp8's analytic time is genuinely smaller...
    # ...by exactly the scales overhead, within the band at block 1024
    assert t_f8 > t_q8 * (1 - cm.FP8_NEAR_TIE_RTOL)
    # at block 64 the apparent gap exceeds the band, yet without a
    # measured fp8 curve the incumbent still holds (the PR-10 regression:
    # the reduced qwen2.5-14b config quantizes at block 64)
    kw64 = {**kw, "quant_block": 64}
    t_q8_64 = cm.gather_time("q8_block", **kw64)
    t_f8_64 = cm.gather_time("fp8_e4m3", **kw64)
    assert t_f8_64 < t_q8_64 * (1 - cm.FP8_NEAR_TIE_RTOL)
    assert cm.choose_store(**kw64) == "q8_block"
    assert cm.choose_store(**{**kw, "m": 1}) == "fp32"


def test_fp8_measured_profile_flips_choice():
    """A measured profile whose fp8 gather curve beats every incumbent by
    more than the near-tie band selects the fp8 store."""
    from test_autotune import _measured_profile, _samples

    sweep = tuple(_samples("gather", "fp8_e4m3", "xla", 0.05))
    cm = CostModel.from_profile(_measured_profile(sweep=sweep))
    got = cm.choose_store(elems_per_layer=1 << 20, n_layers=3, m=8,
                          quant_block=1024, compute_itemsize=2)
    assert got == "fp8_e4m3", got


def test_fp8_plan_invariants_and_static_verify():
    from repro.analysis.verify import verify_plan_static

    model = build_model(get_config("qwen2.5-14b").reduced())
    plan = make_plan(model, {"data": 8}, CommSchedule(param_store="fp8_e4m3"))
    invs = plan.invariants()
    legs = [i for i in invs if i["name"] == "wire_dtype"]
    assert legs, invs
    fp8_name = str(jnp.dtype(compat.float8_dtypes()["fp8_e4m3"]))
    assert any(fp8_name in json.dumps(i) for i in legs), legs
    nd = [i for i in invs if i["name"] == "no_f32_dequant"]
    assert nd and all(i.get("src_dtype") == fp8_name for i in nd), nd
    assert verify_plan_static(plan).ok


# --------------------------------------------------------------------------- #
# 8-device acceptance: train + checkpoint + elastic reshard
# --------------------------------------------------------------------------- #

_DRIVER_8DEV = textwrap.dedent("""
    import os, json
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.checkpoint import ckpt
    from repro.configs import get_config, build_model
    from repro.core.fsdp import FSDPRuntime
    from repro.core.schedule import CommSchedule
    from repro.optim import make_optimizer
    from repro.launch.mesh import make_local_mesh

    cfg = get_config("qwen2.5-14b").reduced()
    model = build_model(cfg)
    sched = CommSchedule(param_store="fp8_e4m3")
    out = {}

    rt8 = FSDPRuntime(model, make_local_mesh(8, 1), schedule=sched,
                      donate=False)
    params = rt8.init_params(0)
    opt = make_optimizer(cfg)
    state = opt.init(rt8)
    fn = rt8.make_train_step(opt)
    st = jnp.int32(0)
    rng = np.random.default_rng(0)
    losses = []
    for _ in range(3):
        batch = {"tokens": jnp.asarray(
            rng.integers(0, cfg.vocab, (8, 16)), jnp.int32)}
        params, state, st, m = fn(params, state, st, batch)
        losses.append(float(m["loss"]))
    out["finite"] = bool(np.isfinite(losses).all())

    ckpt.save("/tmp/fp8_ck", rt8, params, state, step=3)

    # elastic: restore the 8-way checkpoint onto a 4-way mesh, re-save,
    # and restore THAT back onto an 8-way runtime -- if the 4-way hop
    # lost a bit anywhere, the same-layout comparison at the end shows it
    rt4 = FSDPRuntime(model, make_local_mesh(4, 1), schedule=sched,
                      donate=False)
    p4, step, s4 = ckpt.load("/tmp/fp8_ck", rt4, opt.init(rt4))
    ckpt.save("/tmp/fp8_ck2", rt4, p4, s4, step=step)
    rt8b = FSDPRuntime(model, make_local_mesh(8, 1), schedule=sched,
                       donate=False)
    p8b, _, _ = ckpt.load("/tmp/fp8_ck2", rt8b, opt.init(rt8b))
    ok = True
    for name in params:
        for leaf in ("codes", "master"):
            ok &= bool(np.array_equal(
                np.asarray(params[name][leaf]).view(np.uint8),
                np.asarray(p8b[name][leaf]).view(np.uint8)))
    out["reshard_bitwise"] = ok

    # and training continues on the resharded params
    fn4 = rt4.make_train_step(opt)
    batch = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab, (8, 16)), jnp.int32)}
    _, _, _, m4 = fn4(p4, s4, jnp.int32(step), batch)
    out["resumed_finite"] = bool(np.isfinite(float(m4["loss"])))
    print(json.dumps(out))
""")


@pytest.mark.slow
def test_fp8_8dev_train_ckpt_reshard_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _DRIVER_8DEV],
                         capture_output=True, text=True, env=env,
                         timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    data = json.loads(out.stdout.strip().splitlines()[-1])
    assert data["finite"], data
    assert data["reshard_bitwise"], data
    assert data["resumed_finite"], data
