"""Fused optimizer-update + store-rebuild kernels
(``ops.adamw_store_update`` / ``ops.adam8bit_store_update``).

Parity doctrine (DESIGN.md §Kernels): adamw is BITWISE against the
jitted unfused reference (``ref.adamw_store_update_ref``) for every
store format; adam8bit is ALLCLOSE at few-ulp integer-view distance (<= 4) --
the log-space second-moment decode's ``exp`` compiles differently
inside the pallas interpreter than in the fused reference graph
(verified: 40/40 random seeds drift by a last-ulp step or two on the
weight, 0/40 for adamw).  The scalars (lr, betas, eps, wd,
bias-correction terms) ride as TRACED f32 arguments on BOTH sides --
closing the reference over python floats would fold ``1 - b1`` in f64
and shift the coefficients by ulps, which is exactly the class of
drift the contract exists to catch.

The jaxpr regressions prove the fusion claim structurally: the fused q8
path shows strictly fewer full-size f32 intermediates than the unfused
update-then-requantize composition (the ``store.rebuild`` second pass is
gone), using the same ``repro.analysis`` walker the plan verifier runs.
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.analysis import count_full_f32
from repro.compat import float8_dtypes
from repro.kernels import ops, ref


def rnd(shape, seed=0, scale=1.0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(0, scale, shape).astype(np.float32))


def special_blocks(nblocks, block, seed, dtype=jnp.float32):
    """Random data with the adversarial blocks the sweeps require: block 0
    all zeros, block 1 denormal absmax (the requantize epilogue's
    1/max(scale, eps) guard)."""
    x = np.array(rnd((nblocks * block,), seed=seed))
    x[:block] = 0.0
    if nblocks > 1:
        x[block:2 * block] *= 1e-42
    return jnp.asarray(x).astype(dtype)


ALL_FMTS = ["fp32", "bf16", "q8_block"] + sorted(float8_dtypes())
FLAT_FMTS = [f for f in ALL_FMTS if f != "q8_block"]

# traced-f32 hyperparameters: lr, b1, b2, eps, wd, c1, c2
SCALARS = tuple(jnp.float32(x)
                for x in (1e-3, 0.9, 0.95, 1e-8, 0.1, 0.5, 0.25))


def _adamw_inputs(n, seed=0, w_dtype=jnp.float32, block=1024):
    nb = -(-n // block)
    w = special_blocks(nb, block, seed=seed)[:n].astype(w_dtype)
    g = rnd((n,), seed=seed + 1)
    m = rnd((n,), seed=seed + 2) * 0.1
    v = jnp.abs(rnd((n,), seed=seed + 3)) * 0.01
    rng = np.random.default_rng(seed + 4)
    mask = jnp.asarray(rng.integers(0, 2, (n,)).astype(np.float32))
    return w, g, m, v, mask


def _assert_bitwise(got, want, msg=""):
    ga = jax.tree_util.tree_leaves(got)
    wa = jax.tree_util.tree_leaves(want)
    assert len(ga) == len(wa)
    for a, b in zip(ga, wa):
        assert a.dtype == b.dtype, (msg, a.dtype, b.dtype)
        np.testing.assert_array_equal(
            np.asarray(a).view(np.uint8), np.asarray(b).view(np.uint8),
            err_msg=msg)


_INT_VIEW = {1: np.int8, 2: np.int16, 4: np.int32}


def _assert_ulp(got, want, msg="", max_ulp=4):
    """Integer-representation distance <= max_ulp on every leaf
    (subsumes bitwise; the adam8bit contract -- see module docstring)."""
    ga = jax.tree_util.tree_leaves(got)
    wa = jax.tree_util.tree_leaves(want)
    assert len(ga) == len(wa)
    for a, b in zip(ga, wa):
        assert a.dtype == b.dtype, (msg, a.dtype, b.dtype)
        ai = np.asarray(a).view(_INT_VIEW[jnp.dtype(a.dtype).itemsize])
        bi = np.asarray(b).view(_INT_VIEW[jnp.dtype(b.dtype).itemsize])
        d = np.abs(ai.astype(np.int64) - bi.astype(np.int64))
        assert d.max(initial=0) <= max_ulp, (msg, a.dtype, int(d.max()),
                                             int((d > 0).sum()))


# --------------------------------------------------------------------------- #
# adamw: bitwise parity across every store format
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("fmt", ALL_FMTS)
def test_adamw_store_update_bitwise(fmt):
    n, block = 8 * 1024, 1024
    w_dtype = jnp.bfloat16 if fmt == "bf16" else jnp.float32
    w, g, m, v, mask = _adamw_inputs(n, w_dtype=w_dtype, block=block)
    got = ops.adamw_store_update(
        w, g, m, v, mask, lr=SCALARS[0], b1=SCALARS[1], b2=SCALARS[2],
        eps=SCALARS[3], wd=SCALARS[4], c1=SCALARS[5], c2=SCALARS[6],
        fmt=fmt, block=block)
    want = jax.jit(ref.adamw_store_update_ref, static_argnums=(12, 13))(
        w, g, m, v, mask, *SCALARS, fmt, block)
    _assert_bitwise(got, want, f"adamw fmt={fmt}")


@pytest.mark.parametrize("fmt", FLAT_FMTS)
def test_adamw_flat_overhang(fmt):
    """Flat formats take the (rows, 128)-tile path with inert zero pad --
    an n that is a multiple of neither the lane width nor the quant block
    must still match the reference exactly."""
    n = 100
    w_dtype = jnp.bfloat16 if fmt == "bf16" else jnp.float32
    w, g, m, v, mask = _adamw_inputs(n, w_dtype=w_dtype)
    got = ops.adamw_store_update(
        w, g, m, v, mask, lr=SCALARS[0], b1=SCALARS[1], b2=SCALARS[2],
        eps=SCALARS[3], wd=SCALARS[4], c1=SCALARS[5], c2=SCALARS[6],
        fmt=fmt, block=1024)
    want = jax.jit(ref.adamw_store_update_ref, static_argnums=(12, 13))(
        w, g, m, v, mask, *SCALARS, fmt, 1024)
    _assert_bitwise(got, want, f"adamw overhang fmt={fmt}")
    leaves = jax.tree_util.tree_leaves(got)
    assert all(a.shape == (n,) for a in leaves if a.ndim == 1)


def test_adamw_q8_misaligned_raises():
    w, g, m, v, mask = _adamw_inputs(100)
    with pytest.raises(ValueError, match="align"):
        ops.adamw_store_update(
            w, g, m, v, mask, lr=SCALARS[0], b1=SCALARS[1], b2=SCALARS[2],
            eps=SCALARS[3], wd=SCALARS[4], c1=SCALARS[5], c2=SCALARS[6],
            fmt="q8_block", block=1024)


def test_adamw_unknown_fmt_raises():
    w, g, m, v, mask = _adamw_inputs(1024)
    with pytest.raises(ValueError, match="fmt"):
        ops.adamw_store_update(
            w, g, m, v, mask, lr=SCALARS[0], b1=SCALARS[1], b2=SCALARS[2],
            eps=SCALARS[3], wd=SCALARS[4], c1=SCALARS[5], c2=SCALARS[6],
            fmt="int4", block=1024)


@settings(max_examples=20, deadline=None)
@given(st.sampled_from(ALL_FMTS), st.sampled_from([128, 1024]),
       st.integers(1, 8), st.integers(0, 10_000))
def test_adamw_store_update_property(fmt, block, nblocks, seed):
    n = nblocks * block
    w_dtype = jnp.bfloat16 if fmt == "bf16" else jnp.float32
    w, g, m, v, mask = _adamw_inputs(n, seed=seed, w_dtype=w_dtype,
                                     block=block)
    got = ops.adamw_store_update(
        w, g, m, v, mask, lr=SCALARS[0], b1=SCALARS[1], b2=SCALARS[2],
        eps=SCALARS[3], wd=SCALARS[4], c1=SCALARS[5], c2=SCALARS[6],
        fmt=fmt, block=block)
    want = jax.jit(ref.adamw_store_update_ref, static_argnums=(12, 13))(
        w, g, m, v, mask, *SCALARS, fmt, block)
    _assert_bitwise(got, want, f"property fmt={fmt} block={block} "
                               f"nblocks={nblocks} seed={seed}")


# --------------------------------------------------------------------------- #
# adam8bit: few-ulp parity (block layout pinned by the quantized moments)
# --------------------------------------------------------------------------- #

def _adam8_inputs(n, seed=0, w_dtype=jnp.float32, block=1024):
    w, g, m, v, mask = _adamw_inputs(n, seed=seed, w_dtype=w_dtype,
                                     block=block)
    m8, ms = ref.quantize_ref(np.asarray(m, np.float32), block)
    v8, vs = ref.quantize_ref(np.abs(np.asarray(v, np.float32)), block)
    return w, g, m8, v8, ms, vs, mask


@pytest.mark.parametrize("fmt", ALL_FMTS)
def test_adam8bit_store_update_ulp(fmt):
    n, block = 8 * 1024, 1024
    w_dtype = jnp.bfloat16 if fmt == "bf16" else jnp.float32
    w, g, m8, v8, ms, vs, mask = _adam8_inputs(n, w_dtype=w_dtype,
                                               block=block)
    got = ops.adam8bit_store_update(
        w, g, m8, v8, ms, vs, mask, lr=SCALARS[0], b1=SCALARS[1],
        b2=SCALARS[2], eps=SCALARS[3], wd=SCALARS[4], c1=SCALARS[5],
        c2=SCALARS[6], fmt=fmt, block=block)
    want = jax.jit(ref.adam8bit_store_update_ref, static_argnums=(14, 15))(
        w, g, m8, v8, ms, vs, mask, *SCALARS, fmt, block)
    _assert_ulp(got, want, f"adam8bit fmt={fmt}")


def test_adam8bit_misaligned_raises():
    w, g, m8, v8, ms, vs, mask = _adam8_inputs(1024)
    with pytest.raises(ValueError, match="align"):
        ops.adam8bit_store_update(
            w[:100], g[:100], m8, v8, ms, vs, mask[:100], lr=SCALARS[0],
            b1=SCALARS[1], b2=SCALARS[2], eps=SCALARS[3], wd=SCALARS[4],
            c1=SCALARS[5], c2=SCALARS[6], fmt="fp32", block=1024)


@settings(max_examples=20, deadline=None)
@given(st.sampled_from(ALL_FMTS), st.sampled_from([128, 1024]),
       st.integers(1, 8), st.integers(0, 10_000))
def test_adam8bit_store_update_property(fmt, block, nblocks, seed):
    n = nblocks * block
    w_dtype = jnp.bfloat16 if fmt == "bf16" else jnp.float32
    w, g, m8, v8, ms, vs, mask = _adam8_inputs(n, seed=seed,
                                               w_dtype=w_dtype, block=block)
    got = ops.adam8bit_store_update(
        w, g, m8, v8, ms, vs, mask, lr=SCALARS[0], b1=SCALARS[1],
        b2=SCALARS[2], eps=SCALARS[3], wd=SCALARS[4], c1=SCALARS[5],
        c2=SCALARS[6], fmt=fmt, block=block)
    want = jax.jit(ref.adam8bit_store_update_ref, static_argnums=(14, 15))(
        w, g, m8, v8, ms, vs, mask, *SCALARS, fmt, block)
    _assert_ulp(got, want, f"property fmt={fmt} block={block} "
                            f"nblocks={nblocks} seed={seed}")


# --------------------------------------------------------------------------- #
# jaxpr regression: the fusion claim, structurally
# --------------------------------------------------------------------------- #

def test_fused_q8_update_fewer_f32_streams():
    """The unfused composition runs the update (w2 materialized f32) and
    then store.rebuild as a second full-size pass; the fused kernel's
    requantize epilogue writes codes/scales from registers.  Count the
    full-size f32 intermediates outside pallas bodies -- fused must be
    strictly lower."""
    n, block = 8 * 1024, 1024
    w, g, m, v, mask = _adamw_inputs(n, block=block)

    def fused(w, g, m, v, mask, *sc):
        return ops.adamw_store_update(
            w, g, m, v, mask, lr=sc[0], b1=sc[1], b2=sc[2], eps=sc[3],
            wd=sc[4], c1=sc[5], c2=sc[6], fmt="q8_block", block=block)

    def unfused(w, g, m, v, mask, *sc):
        return ref.adamw_store_update_ref(w, g, m, v, mask, *sc,
                                          "q8_block", block)

    cf = count_full_f32(fused, w, g, m, v, mask, *SCALARS, n=n)
    cu = count_full_f32(unfused, w, g, m, v, mask, *SCALARS, n=n)
    assert cf < cu, (cf, cu)


def test_fused_fp8_update_fewer_f32_streams():
    n = 8 * 1024
    w, g, m, v, mask = _adamw_inputs(n)

    def fused(w, g, m, v, mask, *sc):
        return ops.adamw_store_update(
            w, g, m, v, mask, lr=sc[0], b1=sc[1], b2=sc[2], eps=sc[3],
            wd=sc[4], c1=sc[5], c2=sc[6], fmt="fp8_e4m3", block=1024)

    def unfused(w, g, m, v, mask, *sc):
        return ref.adamw_store_update_ref(w, g, m, v, mask, *sc,
                                          "fp8_e4m3", 1024)

    cf = count_full_f32(fused, w, g, m, v, mask, *SCALARS, n=n)
    cu = count_full_f32(unfused, w, g, m, v, mask, *SCALARS, n=n)
    assert cf < cu, (cf, cu)


# --------------------------------------------------------------------------- #
# 8-device: the kernel under shard_map, per-shard bitwise vs the reference
# --------------------------------------------------------------------------- #

_DRIVER_8DEV = textwrap.dedent("""
    import os, json, functools
    # FMA-free XLA:CPU, as in conftest.py: the comparison is bitwise
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                               "--xla_cpu_max_isa=AVX")
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.compat import shard_map
    from repro.kernels import ops, ref
    from repro.launch.mesh import make_local_mesh

    mesh = make_local_mesh(8, 1)
    axis = mesh.axis_names[0]
    block, shard = 1024, 4 * 1024
    n = 8 * shard
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(size=n).astype(np.float32))
    g = jnp.asarray(rng.normal(size=n).astype(np.float32))
    m = jnp.asarray(rng.normal(size=n).astype(np.float32)) * 0.1
    v = jnp.abs(jnp.asarray(rng.normal(size=n).astype(np.float32))) * 0.01
    mask = jnp.asarray(rng.integers(0, 2, (n,)).astype(np.float32))
    sc = tuple(jnp.float32(x) for x in (1e-3, 0.9, 0.95, 1e-8, 0.1,
                                        0.5, 0.25))

    def upd(w, g, m, v, mask, *sc):
        return ops.adamw_store_update(
            w, g, m, v, mask, lr=sc[0], b1=sc[1], b2=sc[2], eps=sc[3],
            wd=sc[4], c1=sc[5], c2=sc[6], fmt="q8_block", block=block)

    sharded = shard_map(
        upd, mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P(axis), P(axis),
                  *([P()] * 7)),
        out_specs=({"codes": P(axis), "master": P(axis),
                    "scales": P(axis)}, P(axis), P(axis)))
    store8, m8, v8 = jax.jit(sharded)(w, g, m, v, mask, *sc)

    r = jax.jit(ref.adamw_store_update_ref, static_argnums=(12, 13))
    ok = True
    for i in range(8):
        s = slice(i * shard, (i + 1) * shard)
        want_store, wm, wv = r(w[s], g[s], m[s], v[s], mask[s], *sc,
                               "q8_block", block)
        sb = slice(i * (shard // block), (i + 1) * (shard // block))
        for leaf, wl in (("codes", want_store["codes"]),
                         ("master", want_store["master"]),
                         ("scales", want_store["scales"])):
            gl = store8[leaf][sb if leaf == "scales" else s]
            ok &= bool(np.array_equal(
                np.asarray(gl).view(np.uint8),
                np.asarray(wl).view(np.uint8)))
        ok &= bool(np.array_equal(np.asarray(m8[s]), np.asarray(wm)))
        ok &= bool(np.array_equal(np.asarray(v8[s]), np.asarray(wv)))
    print(json.dumps({"bitwise": ok}))
""")


@pytest.mark.slow
def test_adamw_store_update_shard_map_8dev():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _DRIVER_8DEV],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    data = json.loads(out.stdout.strip().splitlines()[-1])
    assert data["bitwise"], data
