"""CommSchedule correctness: every schedule variant is a pure reordering /
re-materialization of the same collectives, so on one device all variants
must produce bitwise-identical training trajectories; the manual ring
(ppermute) gather mode must match the xla collectives bitwise on any device
count; and prefetch's two-slot double buffer must never place a gathered
layer buffer in a scan carry (the per-layer retention bug)."""
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
from repro.core.schedule import (GROUP_OVERRIDE_KEYS, VARIANTS, CommSchedule,
                                 resolve_group_schedules, sharded_gather)
from repro.launch.mesh import make_local_mesh
from repro.optim import make_optimizer

MESH = make_local_mesh(1, 1)


def _build_runtime(schedule, arch="qwen2.5-14b", planner="ragged",
                   n_layers=None, group_schedules=None):
    cfg = get_config(arch).reduced()  # 2 layers: exercises keep_last split
    if n_layers is not None:
        cfg = dataclasses.replace(cfg, n_layers=n_layers)
    model = build_model(cfg)
    rt = FSDPRuntime(model, MESH, planner=planner, schedule=schedule,
                     donate=False, group_schedules=group_schedules)
    return cfg, rt


def _train(schedule, steps=3, arch="qwen2.5-14b", planner="ragged",
           n_layers=None, group_schedules=None):
    cfg, rt = _build_runtime(schedule, arch=arch, planner=planner,
                             n_layers=n_layers,
                             group_schedules=group_schedules)
    params = rt.init_params(0)
    opt = make_optimizer(cfg)
    state = opt.init(rt)
    fn = rt.make_train_step(opt)
    st = jnp.int32(0)
    rng = np.random.default_rng(0)
    out = []
    for i in range(steps):
        batch = {"tokens": jnp.asarray(
            rng.integers(0, cfg.vocab, (4, 16)), jnp.int32)}
        params, state, st, m = fn(params, state, st, batch)
        out.append((float(m["loss"]), float(m["grad_norm"])))
    finals = {k: np.asarray(v) for k, v in params.items()}
    return out, finals


def _assert_same(ref, tst, msg):
    ref_metrics, ref_params = ref
    metrics, params = tst
    for (rl, rg), (tl, tg) in zip(ref_metrics, metrics):
        assert np.float32(rl).tobytes() == np.float32(tl).tobytes(), (
            msg, ref_metrics, metrics)
        assert np.float32(rg).tobytes() == np.float32(tg).tobytes(), (
            msg, ref_metrics, metrics)
    for k in ref_params:
        np.testing.assert_array_equal(ref_params[k], params[k], err_msg=(
            f"{msg}: params[{k}] diverged"))


@pytest.fixture(scope="module")
def reference():
    return _train(CommSchedule.default())


@pytest.mark.parametrize("name", [k for k in VARIANTS if k != "default"])
def test_schedule_parity_bitwise(name, reference):
    """Prefetch / reshard / keep-last / dtype / ring variants:
    bitwise-identical loss, grad-norm, and final params vs. the default
    schedule."""
    _assert_same(reference, _train(VARIANTS[name]), name)


@pytest.mark.parametrize("name", [k for k in VARIANTS
                                  if VARIANTS[k].gather_mode == "xla"])
def test_ring_twin_parity_bitwise(name, reference):
    """The ring twin of every xla variant stays bitwise-identical."""
    ring = dataclasses.replace(VARIANTS[name], gather_mode="ring")
    _assert_same(reference, _train(ring), f"ring:{name}")


@pytest.mark.parametrize("n", [1, 2, 3, 5])
def test_prefetch_keep_last_edge_layer_counts(n):
    """Small-n fallbacks (LayerPlan): n=1 runs keep_last's un-rematted
    path with an empty main scan; n=2+keep_last leaves one main layer (no
    pairing); n=3 pairs without a tail; n=5 pairs with a tail.  All must
    stay bitwise-identical to the sequential default."""
    ref = _train(CommSchedule.default(), steps=2, n_layers=n)
    tst = _train(VARIANTS["overlap_all"], steps=2, n_layers=n)
    _assert_same(ref, tst, f"overlap_all n={n}")


def test_schedule_parity_fsdp2_planner():
    """Schedule variants stay exact under the FSDP2 (interleaved) layout."""
    ref = _train(CommSchedule.default(), planner="fsdp2")
    tst = _train(VARIANTS["ring_overlap"], planner="fsdp2")
    _assert_same(ref, tst, "fsdp2:ring_overlap")


def test_group_schedule_overrides_parity(reference):
    """Per-group overrides (unsharded globals, fp32-reduce + ring layers)
    are pure comm-path changes: bitwise-identical on one device."""
    tst = _train(CommSchedule.default(), group_schedules={
        "globals": {"sharded": False},
        "layers": {"reduce_dtype": "fp32", "gather_mode": "ring"},
    })
    _assert_same(reference, tst, "group_overrides")


def test_layer_plan_edges():
    s = CommSchedule(prefetch=True, keep_last_gathered=True)
    p = s.plan_layers(1)
    assert (p.main, p.split_last, p.prefetch) == (0, True, False)
    p = s.plan_layers(2)
    assert (p.main, p.split_last, p.prefetch) == (1, True, False)
    p = s.plan_layers(3)
    assert (p.main, p.pairs, p.tail, p.split_last) == (2, 1, 0, True)
    p = s.plan_layers(6)
    assert (p.main, p.pairs, p.tail) == (5, 2, 1)
    # keep_last needs remat (+reshard): without it the main scan keeps all
    p = s.plan_layers(4, remat=False)
    assert (p.main, p.split_last, p.pairs) == (4, False, 2)
    p = CommSchedule(prefetch=True).plan_layers(2)
    assert (p.main, p.pairs, p.tail, p.split_last) == (2, 1, 0, False)
    p = CommSchedule(prefetch=True,
                     reshard_after_forward=False).plan_layers(3)
    assert (p.split_last, p.prefetch) == (False, True)


def test_schedule_validation():
    with pytest.raises(ValueError):
        CommSchedule(gather_mode="nccl")
    with pytest.raises(ValueError):
        CommSchedule(gather_dtype="fp16")
    base = CommSchedule.default()
    with pytest.raises(ValueError):
        resolve_group_schedules(base, {"globals": {"prefetch": True}})
    assert "prefetch" not in GROUP_OVERRIDE_KEYS
    # whole CommSchedule instances would smuggle structure knobs through
    with pytest.raises(ValueError):
        resolve_group_schedules(base, {"globals": CommSchedule(prefetch=True)})
    got = resolve_group_schedules(base, {"globals": {"sharded": False}})
    assert got["globals"].sharded is False and got["globals"].prefetch is False
    # overrides naming groups the model doesn't have fail at runtime init
    cfg = get_config("qwen2.5-14b").reduced()
    with pytest.raises(ValueError):
        FSDPRuntime(build_model(cfg), MESH,
                    group_schedules={"global": {"sharded": False}})


def test_validate_for_compute_dtype():
    """A None gather_dtype inherits the compute dtype; an unsupported
    compute dtype must fail at runtime construction, not at trace time."""
    with pytest.raises(ValueError):
        CommSchedule().validate_for(jnp.float16)
    CommSchedule().validate_for(jnp.bfloat16)
    CommSchedule(gather_dtype="bf16").validate_for(jnp.float16)  # pinned: ok
    cfg = get_config("qwen2.5-14b").reduced()
    with pytest.raises(ValueError):
        FSDPRuntime(build_model(cfg), MESH, compute_dtype=jnp.float16)


def test_default_schedule_from_config():
    cfg = get_config("qwen2.5-14b").reduced()
    assert CommSchedule.from_config(cfg) == CommSchedule.default()
    par = dataclasses.replace(cfg.parallel, prefetch=True,
                              reduce_dtype="fp32", gather_mode="ring")
    cfg = dataclasses.replace(cfg, parallel=par)
    sched = CommSchedule.from_config(cfg)
    assert sched.prefetch and sched.reduce_dtype == "fp32"
    assert sched.gather_mode == "ring"


def test_wire_and_accum_dtype_resolution():
    cd = jnp.dtype(jnp.bfloat16)
    s = CommSchedule()
    assert s.wire_dtype(cd) == jnp.bfloat16
    assert s.accum_dtype(cd) == jnp.bfloat16
    s = CommSchedule(gather_dtype="fp32")
    assert s.wire_dtype(cd) == jnp.float32
    assert s.accum_dtype(cd) == jnp.float32  # reduce follows wire
    s = CommSchedule(reduce_dtype="fp32")
    assert s.wire_dtype(cd) == jnp.bfloat16
    assert s.accum_dtype(cd) == jnp.float32
    with pytest.raises(ValueError):
        CommSchedule(gather_dtype="fp16")


def test_sharded_gather_identity_without_axes():
    x = jnp.arange(8, dtype=jnp.float32)
    args = ((), (), jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32),
            jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32), "xla", "match")
    y = sharded_gather(x, *args)
    np.testing.assert_array_equal(
        np.asarray(y), np.asarray(x.astype(jnp.bfloat16)))
    g = jax.grad(lambda v: sharded_gather(v, *args).sum())(x)
    assert g.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(g), np.ones(8, np.float32))


def test_gathered_peak_bytes_accounting():
    """The analytic gathered-buffer peak the two-slot prefetch bounds:
    1 slot sequential, 2 with prefetch (+1 split-out last layer), n_layers
    with resharding off -- independent of depth when prefetching."""
    def peak(schedule, n_layers):
        _, rt = _build_runtime(schedule, n_layers=n_layers)
        return rt.gathered_peak_bytes()

    per_layer = peak(CommSchedule(), 4)
    assert per_layer > 0
    assert peak(CommSchedule(prefetch=True), 4) == 2 * per_layer
    assert peak(CommSchedule(prefetch=True), 32) == 2 * per_layer
    assert peak(CommSchedule(prefetch=True, keep_last_gathered=True),
                4) == 3 * per_layer
    assert peak(CommSchedule(reshard_after_forward=False), 4) == 4 * per_layer
    # n=1 + keep_last: empty main scan, only the split-out layer is live
    assert peak(CommSchedule(keep_last_gathered=True), 1) == per_layer


# --------------------------------------------------------------------------- #
# regression: prefetch must not store gathered layer buffers in scan carries
# --------------------------------------------------------------------------- #

from repro.analysis import iter_eqns, scan_carry_avals


def _step_jaxpr(schedule, n_layers=5):
    cfg, rt = _build_runtime(schedule, n_layers=n_layers)
    params = rt.init_params(0)
    opt = make_optimizer(cfg)
    state = opt.init(rt)
    fn = rt.make_train_step(opt)
    batch = {"tokens": jnp.zeros((4, 16), jnp.int32)}
    return rt, jax.make_jaxpr(fn)(params, state, jnp.int32(0), batch)


def test_prefetch_scan_carry_has_no_gathered_buffers():
    """The retention bug regression: the first prefetch cut threaded the
    next layer's gathered buffer through the checkpointed scan carry, so
    backward retained one gathered buffer per layer.  The two-slot pair
    scan must keep every scan carry free of gathered-layer-sized arrays --
    its carry signature is a subset of the sequential schedule's."""
    rt, pre = _step_jaxpr(VARIANTS["overlap_all"])
    _, ref = _step_jaxpr(CommSchedule.default())
    pre_carries = set(scan_carry_avals(pre))
    ref_carries = set(scan_carry_avals(ref))
    assert pre_carries <= ref_carries, (
        "prefetch added scan carry entries", pre_carries - ref_carries)
    # and explicitly: no carry anywhere is a gathered layer flat buffer
    gathered = {((lo.sharded_dim,), str(jnp.dtype(rt.compute_dtype)))
                for lo in rt.layouts.values() if lo.n_layers}
    assert not (gathered & (pre_carries | ref_carries)), (
        "gathered layer buffer rides a scan carry", gathered)


def _pair_barrier_eqns(closed_jaxpr, gathered_avals):
    """optimization_barrier eqns whose operands include >= 2 gathered layer
    buffers -- the explicit two-slot issue-order pin in the pair scan."""
    found = []
    for eqn, _, _ in iter_eqns(closed_jaxpr):
        if eqn.primitive.name != "optimization_barrier":
            continue
        hits = sum(
            (tuple(v.aval.shape), str(v.aval.dtype)) in gathered_avals
            for v in eqn.invars)
        if hits >= 2:
            found.append(eqn)
    return found


def test_pair_prefetch_issue_order_is_explicit_in_backward():
    """ROADMAP "schedule work remaining": the backward re-gather issue
    order of the pair scan must be explicit, mirroring the forward's
    two-slot order, instead of left to XLA's scheduler.  The pin is an
    optimization_barrier over BOTH slots' gathered buffers; because remat
    replays it, it must appear at least twice in the full train-step jaxpr
    (the forward pair scan and the backward scan's recompute).  The default
    sequential schedule has no such pair barrier."""
    rt, pre = _step_jaxpr(VARIANTS["overlap_all"], n_layers=6)
    gathered = {((lo.sharded_dim,), str(jnp.dtype(rt.compute_dtype)))
                for lo in rt.layouts.values() if lo.n_layers}
    pins = _pair_barrier_eqns(pre, gathered)
    assert len(pins) >= 2, (
        "pair scan's two-slot gather issue order is not pinned in both "
        f"forward and backward (found {len(pins)} pair barriers)")
    _, ref = _step_jaxpr(CommSchedule.default(), n_layers=6)
    assert not _pair_barrier_eqns(ref, gathered), (
        "sequential schedule unexpectedly contains a pair gather barrier")


# --------------------------------------------------------------------------- #
# 8-device ring parity (subprocess: jax fixes the device count at first init)
# --------------------------------------------------------------------------- #

_RING_DRIVER = textwrap.dedent("""
    import os, sys, json, dataclasses
    # FMA-free XLA:CPU, as in conftest.py: the comparison is bitwise
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                               "--xla_cpu_max_isa=AVX")
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_config, build_model
    from repro.configs.base import ParallelConfig
    from repro.core.fsdp import FSDPRuntime
    from repro.core.schedule import VARIANTS
    from repro.optim import make_optimizer
    from repro.launch.mesh import make_local_mesh

    MESH = make_local_mesh(8, 1)

    def train(schedule, steps=2):
        cfg = get_config("qwen2.5-14b").reduced()
        # 3 layers: prefetch pair + keep_last split both active
        cfg = dataclasses.replace(cfg, n_layers=3,
                                  parallel=ParallelConfig(("data",), ("data",)))
        model = build_model(cfg)
        rt = FSDPRuntime(model, MESH, schedule=schedule, donate=False)
        params = rt.init_params(0)
        opt = make_optimizer(cfg)
        state = opt.init(rt)
        fn = rt.make_train_step(opt)
        st = jnp.int32(0)
        rng = np.random.default_rng(0)
        ms = []
        for i in range(steps):
            batch = {"tokens": jnp.asarray(
                rng.integers(0, cfg.vocab, (8, 16)), jnp.int32)}
            params, state, st, m = fn(params, state, st, batch)
            ms.append((np.float32(m["loss"]).tobytes().hex(),
                       np.float32(m["grad_norm"]).tobytes().hex()))
        return ms, {k: np.asarray(v) for k, v in params.items()}

    bad = []
    for name, sched in VARIANTS.items():
        if sched.gather_mode != "xla":
            continue
        xm, xp = train(sched)
        rm, rp = train(dataclasses.replace(sched, gather_mode="ring"))
        if xm != rm or any(not np.array_equal(xp[k], rp[k]) for k in xp):
            bad.append(name)
    print(json.dumps({"bad": bad}))
""")


@pytest.mark.slow
def test_ring_matches_xla_bitwise_8dev():
    """Every xla variant and its ring twin produce bitwise-identical
    2-step trajectories over 8-way FSDP: the ring all-gather is pure data
    movement and the ring reduce-scatter reduces in XLA's own
    (linear-device-order, fp32-accumulate) order."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _RING_DRIVER],
                         capture_output=True, text=True, env=env,
                         timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    data = json.loads(out.stdout.strip().splitlines()[-1])
    assert data["bad"] == [], f"ring != xla for variants: {data['bad']}"
