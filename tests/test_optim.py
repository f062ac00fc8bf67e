"""Optimizer correctness on flat DBuffer shards: AdamW math, 8-bit Adam
tracks fp32 Adam, Muon Newton-Schulz orthogonalization, wd masks."""
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

from repro.compat import shard_map
from repro.configs import build_model, get_config
from repro.core.fsdp import FSDPRuntime
from repro.launch.mesh import make_local_mesh
from repro.optim import make_optimizer
from repro.optim.muon import newton_schulz

MESH = make_local_mesh(1, 1)


def _setup(arch="qwen2.5-14b", optimizer=None):
    cfg = get_config(arch).reduced()
    if optimizer:
        cfg = dataclasses.replace(cfg, optimizer=optimizer)
    model = build_model(cfg)
    rt = FSDPRuntime(model, MESH)
    return cfg, model, rt


def _batch(cfg, seed=0):
    rng = np.random.default_rng(seed)
    return {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (4, 16)),
                                  jnp.int32)}


@pytest.mark.parametrize("optname", ["adamw", "sgd", "adam8bit", "muon", "shampoo"])
def test_optimizers_reduce_loss(optname):
    cfg, model, rt = _setup(optimizer=optname)
    params = rt.init_params(0)
    opt = make_optimizer(cfg)
    state = opt.init(rt)
    fn = rt.make_train_step(opt)
    st = jnp.int32(0)
    losses = []
    b = _batch(cfg)
    for i in range(8):
        params, state, st, m = fn(params, state, st, b)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.1, (optname, losses)
    assert all(np.isfinite(l) for l in losses)


def test_adam8bit_tracks_adamw():
    """Quantized moments track fp32 Adam closely over a few steps (same
    data, same init)."""
    cfg8, model8, rt8 = _setup(optimizer="adam8bit")
    cfg32, model32, rt32 = _setup(optimizer="adamw")
    p8, p32 = rt8.init_params(0), rt32.init_params(0)
    o8 = make_optimizer(cfg8)
    o32 = make_optimizer(cfg32)
    s8, s32 = o8.init(rt8), o32.init(rt32)
    f8, f32 = rt8.make_train_step(o8), rt32.make_train_step(o32)
    st8 = st32 = jnp.int32(0)
    for i in range(5):
        b = _batch(cfg8, seed=i)
        p8, s8, st8, m8 = f8(p8, s8, st8, b)
        p32, s32, st32, m32 = f32(p32, s32, st32, b)
    assert abs(float(m8["loss"]) - float(m32["loss"])) < 0.1
    for name in p8:
        a, b_ = np.asarray(p8[name]), np.asarray(p32[name])
        # parameters stay close elementwise; int8 moment noise is largest on
        # the sparse-gradient embedding rows (paper Fig. 10: loss curves
        # "track closely, with occasional spikes")
        assert np.max(np.abs(a - b_)) < 2e-2, name
        assert np.mean(np.abs(a - b_)) < 1e-3, name


def test_newton_schulz_orthogonalizes():
    rng = np.random.default_rng(0)
    for shape in [(16, 16), (8, 32), (48, 12)]:
        G = jnp.asarray(rng.normal(size=shape).astype(np.float32))
        X = newton_schulz(G)
        a, b = shape
        k = min(a, b)
        M = np.asarray(X @ X.T if a <= b else X.T @ X)
        # singular values pushed toward 1: X X^T ~ I
        err = np.abs(M - np.eye(k)).max()
        assert err < 0.35, (shape, err)
        # sign agreement with G's polar factor: <X, G> > 0
        assert float(jnp.sum(X * G)) > 0


def test_muon_applies_ns_only_to_matrices():
    cfg, model, rt = _setup(optimizer="muon")
    opt = make_optimizer(cfg)
    lo = rt.layouts["layers"]
    assert any(len(p.spec.shape) == 2 for p in lo.plan.placements)
    # globals (embed) fall back to adamw: no NS path for unstacked groups
    assert rt.layouts["globals"].n_layers is None


def test_wd_mask_matches_plan():
    from repro.optim.common import matrix_mask_local

    cfg, model, rt = _setup()
    lo = rt.layouts["layers"]

    def get_mask():
        return matrix_mask_local(rt, lo, (lo.plan.shard_size,))

    mask = np.asarray(
        shard_map(get_mask, mesh=rt.mesh, in_specs=(),
                  out_specs=jax.sharding.PartitionSpec(None))())
    # host oracle
    want = np.zeros(lo.plan.shard_size, np.float32)
    for p in lo.plan.placements:
        if len(p.spec.shape) >= 2:
            want[p.offset:p.end] = 1.0  # single device: shard == global
    np.testing.assert_array_equal(mask, want[:lo.plan.shard_size])


# --------------------------------------------------------------------------- #
# the weight-decay mask on every shard of a multi-device mesh
# --------------------------------------------------------------------------- #

# (name, shape, offset) per tensor.  Offsets are not multiples of 128; w0
# and w1 are adjacent matrices (one run), w2 sits between two vectors, w2,
# w3 and w4 straddle shard boundaries (w3 covers whole shards).
_PIECES = [("b0", (5,), 0), ("w0", (3, 7), 5), ("w1", (4, 50), 26),
           ("b1", (10,), 300), ("w2", (10, 33), 310), ("n0", (7,), 640),
           ("w3", (20, 100), 647), ("n1", (3,), 2647), ("w4", (9, 37), 2650),
           ("b2", (13,), 2983)]
# name -> (mesh shape, mesh axes, FSDP axes, shard size S, tensors)
_MASK_CASES = {
    "straddle": ((8,), ("data",), ("data",), 384, _PIECES),
    "two_axes": ((2, 4), ("data", "model"), ("data", "model"), 384,
                 _PIECES),
    "outer_axis": ((4, 2), ("data", "model"), ("data",), 768, _PIECES),
    # a baseline planner's shard size need not be a multiple of 128
    "unaligned_s": ((8,), ("data",), ("data",), 375, _PIECES),
    "no_matrices": ((8,), ("data",), ("data",), 384,
                    [p for p in _PIECES if len(p[1]) == 1]),
}
_LAYERS = 3

_MASK_SCRIPT = textwrap.dedent("""
    import json, os, sys, types
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses
    import jax, numpy as np
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.compat import shard_map
    from repro.configs import build_model, get_config
    from repro.configs.base import ParallelConfig
    from repro.core.fsdp import FSDPRuntime
    from repro.core.ragged import GroupPlan, Placement, TensorSpec
    from repro.launch.mesh import make_local_mesh
    from repro.optim.common import matrix_mask_local

    cases, layers, out = json.loads(sys.argv[1]), int(sys.argv[2]), sys.argv[3]

    def masks(rt, lo, L):
        # (m, *local_shape): shard d's mask at index d
        S, axes = lo.plan.shard_size, lo.fsdp_axes
        local = (L, S) if L else (S,)
        spec = P(None, axes) if L else P(axes)
        got = np.asarray(shard_map(
            lambda: matrix_mask_local(rt, lo, local), mesh=rt.mesh,
            in_specs=(), out_specs=spec)())
        m = got.shape[-1] // S
        return got.reshape(L, m, S).transpose(1, 0, 2) if L else \\
            got.reshape(m, S)

    def record(key, rt, lo, L):
        res[key] = masks(rt, lo, L)
        meta[key] = [[p.offset, p.end, len(p.spec.shape)]
                     for p in lo.plan.placements]

    res, meta = {}, {}
    for name, (shape, names, axes, S, pieces) in cases.items():
        mesh = Mesh(np.array(jax.devices()).reshape(shape), tuple(names))
        m = int(np.prod([dict(zip(names, shape))[a] for a in axes]))
        plan = GroupPlan(tuple(Placement(TensorSpec(n, tuple(s)), off)
                               for n, s, off in pieces), S, m)
        rt = types.SimpleNamespace(mesh=mesh)
        lo = types.SimpleNamespace(plan=plan, fsdp_axes=tuple(axes))
        for L in (0, layers):
            record(f"{name}-{'layered' if L else 'flat'}", rt, lo, L)
    cfg = get_config("qwen2.5-14b").reduced()
    cfg = dataclasses.replace(
        cfg, parallel=ParallelConfig(("data",), ("data",)))
    rt = FSDPRuntime(build_model(cfg), make_local_mesh(8, 1))
    for g, lo in rt.layouts.items():
        record(f"qwen_runtime-{g}", rt, lo, lo.n_layers or 0)
    np.savez(out, **res)
    print(json.dumps(meta))
""")


@pytest.fixture(scope="module")
def eight_device_masks(tmp_path_factory):
    """Every case's mask on all 8 shards, from one 8-device subprocess
    (JAX fixes the device count at its first use)."""
    out = tmp_path_factory.mktemp("masks") / "masks.npz"
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-c", _MASK_SCRIPT, json.dumps(_MASK_CASES),
         str(_LAYERS), str(out)],
        capture_output=True, text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    meta = json.loads(proc.stdout.strip().splitlines()[-1])
    return dict(np.load(out)), meta


@pytest.mark.parametrize("key", [
    f"{c}-{s}" for c in _MASK_CASES for s in ("flat", "layered")] + [
    "qwen_runtime-layers", "qwen_runtime-globals"])
def test_wd_mask_every_device(eight_device_masks, key):
    """Each shard's mask equals the host oracle: ones over every >= 2-D
    placement of the global buffer, sliced to the shard."""
    got, meta = eight_device_masks
    mask = got[key]
    m, S = mask.shape[0], mask.shape[-1]
    want = np.zeros(m * S, np.float32)
    for off, end, rank in meta[key]:
        if rank >= 2:
            want[off:end] = 1.0
    want = want.reshape(m, S)
    if key.endswith("-flat"):
        assert mask.shape == (m, S)
    for d in range(m):
        np.testing.assert_array_equal(
            mask[d], np.broadcast_to(want[d], mask.shape[1:]),
            err_msg=f"{key}: shard {d}")


def test_matrix_runs_past_int32():
    """Run table of a plan whose global offsets pass 2^31, with 2^20
    elements on each of 4096 shards: clipped, shifted, merged, padded."""
    from repro.core.ragged import Placement, TensorSpec
    from repro.optim.common import matrix_runs

    S, m, base = 1 << 20, 4096, (1 << 31) + 100
    specs = [TensorSpec("v0", (base,)), TensorSpec("w0", (3, S)),
             TensorSpec("w1", (5, 1000)), TensorSpec("v1", (7,)),
             TensorSpec("w2", (2, 64))]
    placements, off = [], 0
    for s in specs:
        placements.append(Placement(s, off))
        off += s.size
    table = matrix_runs(placements, S, m)
    assert table.shape == (m, 2, 2) and table.dtype == np.int32
    # w0 + w1 are one run from shard 2048 (local 100) to 2051 (local 5100);
    # w2 is a run of its own after the vector v1
    want = np.zeros_like(table)
    want[2048, 0] = (100, S)
    want[2049, 0] = want[2050, 0] = (0, S)
    want[2051] = [(0, 5100), (5107, 5235)]
    np.testing.assert_array_equal(table, want)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_matrix_runs_matches_dense_oracle(seed):
    """Random plans: the runs of each shard, drawn as a mask, equal the
    dense oracle's slice of that shard."""
    from repro.core.ragged import Placement, TensorSpec
    from repro.optim.common import matrix_runs

    rng = np.random.default_rng(seed)
    placements, off = [], 0
    for i in range(40):
        off += int(rng.integers(0, 3)) * int(rng.integers(1, 50))
        rank = int(rng.integers(1, 4))
        shape = tuple(int(x) for x in rng.integers(1, 9, rank))
        placements.append(Placement(TensorSpec(f"t{i}", shape), off))
        off += int(np.prod(shape))
    m = int(rng.integers(1, 9))
    S = -(-off // m) + int(rng.integers(0, 5))
    want = np.zeros(m * S, bool)
    for p in placements:
        if len(p.spec.shape) >= 2:
            want[p.offset:p.end] = True
    table = matrix_runs(placements, S, m)
    for d in range(m):
        got = np.zeros(S, bool)
        for lo, hi in table[d]:
            assert 0 <= lo <= hi <= S
            got[lo:hi] = True
        np.testing.assert_array_equal(got, want[d * S:(d + 1) * S])
    # merged: within a shard's row no run ends where the next begins
    for row in table:
        ends, starts = row[:-1, 1], row[1:, 0]
        assert not np.any((ends == starts) & (row[1:, 1] > starts))
