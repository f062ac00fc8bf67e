"""Fused quant hot-path kernels: the contracts the dispatch layer
(repro.kernels.ops) guarantees to its call sites.

  * jaxpr regression: the gather-path fused decode (``dequantize_into``)
    never materializes a full-size fp32 buffer OUTSIDE the kernel body --
    the unfused reference provably does, so the test has teeth.
  * the reduce-path fused encode + error feedback is BITWISE against the
    JITTED reference composition (the regime training actually runs: XLA
    contracts ``comp - codes*scale`` into an FMA under jit on every
    backend, so the eager two-step composition differs sub-ulp and is NOT
    the contract).
  * the serve-path int8 GEMM is ALLCLOSE against the dense semantic
    oracle (activation row-quantization is new error by design) and
    BITWISE against its own jnp op-sequence equivalent.
  * partial tiles: explicit ``tile_blocks`` overrides that leave a cdiv
    overhang (grid padding on the last tile) change nothing.
  * kernel wrappers raise the reference's ValueError contract
    (_check_blocking/_check_scales), differing only in the callee name.
  * property sweeps (hypothesis when installed, fixed-seed otherwise):
    fp32/bf16 cotangents, all-zero blocks, denormal-absmax blocks,
    block in {128, 1024}.

The 8-device subprocess scenario at the bottom drives the two new wired
paths on real shards: deferred-EF microbatch accumulation and the serve
quant-matmul schedule.
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

from repro.analysis import count_full_f32, has_full_f32
from repro.kernels import ops, ref
from repro.kernels.blockwise_quant import dequantize_into as deq_into_raw
from repro.kernels.blockwise_quant import quantize as quantize_raw
from repro.kernels.encode_ef import encode_ef as encode_ef_raw
from repro.quant.blockwise import dequantize_blockwise, quantize_blockwise


def rnd(shape, seed=0, scale=1.0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(0, scale, shape).astype(np.float32))


def special_blocks(nblocks, block, seed, dtype=jnp.float32):
    """Random data with the adversarial blocks the sweeps require: block 0
    all zeros (scale == 0 -> inv == 0 path), block 1 denormal absmax
    (exercises the 1e-30 guard in 1/max(scale, 1e-30))."""
    x = np.array(rnd((nblocks * block,), seed=seed))
    x[:block] = 0.0
    if nblocks > 1:
        x[block:2 * block] *= 1e-42
    return jnp.asarray(x).astype(dtype)


# ---------------------------------------------------------------------------
# jaxpr regression: no full-size fp32 materialization on the gather path
# (the walker lives in repro.analysis -- the same machinery the plan
# verifier's no_f32_dequant invariant runs on full train steps)
# ---------------------------------------------------------------------------

def test_dequantize_into_no_f32_materialization():
    n, block = 8 * 1024, 1024
    codes = jnp.zeros((n,), jnp.int8)
    scales = jnp.ones((n // block,), jnp.float32)

    fused = lambda c, s: ops.dequantize_into(c, s, block,
                                             out_dtype=jnp.bfloat16)
    assert not has_full_f32(fused, codes, scales, n=n), (
        "fused gather decode materialized a full-size fp32 buffer")

    # the unfused composition DOES materialize one -- proves the walker
    # actually sees full-size f32 intermediates when they exist
    unfused = lambda c, s: ref.dequantize_into_ref(c, s, block, jnp.bfloat16)
    assert has_full_f32(unfused, codes, scales, n=n)


def test_encode_ef_no_extra_f32_buffers():
    """The fused encode+EF's only full-size fp32 values outside the kernel
    body are the ef input's reshape view and the new_ef output (3 avals:
    the pjit result, one reshape in, one reshape out); the unfused
    composition threads a dozen-plus full-size fp32 temporaries (comp,
    blocked views, products, the dequant buffer) through XLA."""
    n, block = 8 * 1024, 1024
    ct = jnp.zeros((n,), jnp.bfloat16)
    ef = jnp.zeros((n,), jnp.float32)

    fused = lambda c, e: ops.encode_ef(c, e, block)
    unfused = lambda c, e: ref.encode_ef_ref(c, e, block)
    assert count_full_f32(fused, ct, ef, n=n) <= 3
    assert count_full_f32(unfused, ct, ef, n=n) >= 10


# ---------------------------------------------------------------------------
# fused encode + error feedback: bitwise vs the JITTED reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("block", [128, 1024])
def test_encode_ef_bitwise_vs_jitted_ref(dtype, block):
    ct = special_blocks(6, block, seed=11, dtype=dtype)
    ef = rnd((6 * block,), seed=12, scale=1e-3)
    codes, scales, new_ef = ops.encode_ef(ct, ef, block)
    wc, ws, we = jax.jit(ref.encode_ef_ref, static_argnums=2)(ct, ef, block)
    np.testing.assert_array_equal(np.asarray(codes), np.asarray(wc))
    np.testing.assert_array_equal(np.asarray(scales), np.asarray(ws))
    np.testing.assert_array_equal(np.asarray(new_ef), np.asarray(we))
    assert new_ef.dtype == jnp.float32


def test_encode_ef_residual_is_quantization_error():
    """Semantics, not just parity: new_ef == comp - decode(encode(comp))
    computed within the jitted regime."""
    block = 64
    ct = rnd((512,), seed=1)
    ef = rnd((512,), seed=2, scale=1e-2)
    codes, scales, new_ef = ops.encode_ef(ct, ef, block)

    @jax.jit
    def expect(ct, ef):
        comp = ct.astype(jnp.float32) + ef
        return comp - dequantize_blockwise(
            *quantize_blockwise(comp, block), block)

    np.testing.assert_array_equal(np.asarray(new_ef),
                                  np.asarray(expect(ct, ef)))


# ---------------------------------------------------------------------------
# serve-path int8 GEMM
# ---------------------------------------------------------------------------

def _q8mm_jnp(x, codes, scales, block):
    """Op-for-op jnp spelling of the kernel (per output-column group):
    the bitwise twin, not the semantic oracle."""
    k, n = codes.shape
    s2 = ops.fold_scales(scales, k, n, block)
    nj = s2.shape[0]
    ncols = n // nj
    outs = []
    for j in range(nj):
        a = x.astype(jnp.float32) * s2[j][None, :]
        rmax = jnp.max(jnp.abs(a), axis=1)
        rs = rmax / 127.0
        inv = jnp.where(rs > 0, 1.0 / jnp.maximum(rs, 1e-30), 0.0)
        a8 = jnp.clip(jnp.round(a * inv[:, None]), -127, 127).astype(jnp.int8)
        acc = jax.lax.dot_general(
            a8, codes[:, j * ncols:(j + 1) * ncols],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32)
        outs.append((acc.astype(jnp.float32) * rs[:, None]).astype(x.dtype))
    return jnp.concatenate(outs, axis=1)


@pytest.mark.parametrize("k,n,block", [
    (128, 512, 128),   # case A: N % block == 0 (nj = 4)
    (256, 64, 128),    # case B: block % N == 0 (one block spans 2 rows)
    (64, 64, 64),      # both cases degenerate to nj = 1
])
def test_q8_matmul_matches_oracle_and_jnp_twin(k, n, block):
    w = rnd((k, n), seed=k + n, scale=0.05)
    codes, scales = ops.quantize(w.reshape(-1), block)
    codes = codes.reshape(k, n)
    x = rnd((8, k), seed=3)

    got = ops.q8_matmul(x, codes, scales, block)
    # ALLCLOSE class vs the dense semantic oracle: activation row
    # quantization adds bounded new error
    want = ref.q8_matmul_ref(x, codes, scales, block)
    denom = max(np.abs(np.asarray(want)).mean(), 1e-6)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() / denom < 0.05
    # BITWISE vs the jitted jnp op-sequence twin
    twin = jax.jit(_q8mm_jnp, static_argnums=3)(x, codes, scales, block)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(twin))


def test_q8_matmul_leading_dims_and_out_dtype():
    k, n, block = 64, 128, 64
    w = rnd((k, n), seed=5, scale=0.05)
    codes, scales = ops.quantize(w.reshape(-1), block)
    codes = codes.reshape(k, n)
    x = rnd((2, 3, k), seed=6).astype(jnp.bfloat16)
    y = ops.q8_matmul(x, codes, scales, block)
    assert y.shape == (2, 3, n) and y.dtype == jnp.bfloat16
    y32 = ops.q8_matmul(x, codes, scales, block, out_dtype=jnp.float32)
    assert y32.dtype == jnp.float32


def test_quant_eligible_contract():
    assert ops.quant_eligible((128, 512), 128)       # case A
    assert ops.quant_eligible((256, 64), 128)        # case B
    assert not ops.quant_eligible((256,), 128)       # 1-D
    assert not ops.quant_eligible((100, 96), 128)    # partial blocks
    assert not ops.quant_eligible((128, 192), 128)   # inseparable scales


# ---------------------------------------------------------------------------
# partial tiles: cdiv overhang on explicit tile overrides
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("nblocks,tile", [(5, 2), (7, 4), (3, 8)])
@pytest.mark.parametrize("block", [128, 1024])
def test_partial_tile_parity(nblocks, tile, block):
    """grid = cdiv(nblocks, tile) leaves an overhang tile; Pallas pads
    reads and clips writes, and per-row absmax makes padding inert -- the
    overhang result is bitwise the single-tile result for every kernel."""
    x = special_blocks(nblocks, block, seed=nblocks * 31 + tile)
    ck, cs = quantize_raw(x, block=block, interpret=True, tile_blocks=tile)
    wk, ws = ops.quantize(x, block)
    np.testing.assert_array_equal(np.asarray(ck), np.asarray(wk))
    np.testing.assert_array_equal(np.asarray(cs), np.asarray(ws))

    back = deq_into_raw(ck, cs, block=block, out_dtype=jnp.bfloat16,
                        interpret=True, tile_blocks=tile)
    wback = ops.dequantize_into(wk, ws, block, out_dtype=jnp.bfloat16)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(wback))

    ef = rnd((nblocks * block,), seed=9, scale=1e-3)
    got = encode_ef_raw(x, ef, block=block, interpret=True, tile_blocks=tile)
    want = ops.encode_ef(x, ef, block)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


# ---------------------------------------------------------------------------
# ValueError contract: kernel == reference, modulo the callee name
# ---------------------------------------------------------------------------

def _msg_body(err, who):
    s = str(err.value)
    assert s.startswith(who + ": "), s
    return s[len(who) + 2:]


def test_shape_errors_match_reference():
    x = rnd((100,), seed=0)  # 100 % 64 != 0
    with pytest.raises(ValueError) as k:
        ops.quantize(x, 64)
    with pytest.raises(ValueError) as r:
        quantize_blockwise(x, 64)
    assert _msg_body(k, "quantize") == _msg_body(r, "quantize_blockwise")

    codes = jnp.zeros((128,), jnp.int8)
    bad_scales = jnp.ones((3,), jnp.float32)  # want 2 blocks
    with pytest.raises(ValueError) as k:
        ops.dequantize_into(codes, bad_scales, 64, out_dtype=jnp.bfloat16)
    with pytest.raises(ValueError) as r:
        dequantize_blockwise(codes, bad_scales, 64)
    assert _msg_body(k, "dequantize") == _msg_body(
        r, "dequantize_blockwise")

    with pytest.raises(ValueError) as k:
        ops.quantize(x, 0)
    with pytest.raises(ValueError) as r:
        quantize_blockwise(x, 0)
    assert _msg_body(k, "quantize") == _msg_body(r, "quantize_blockwise")

    # encode_ef adds one contract of its own: ef must be ct-shaped f32
    ct = rnd((128,), seed=1)
    with pytest.raises(ValueError):
        ops.encode_ef(ct, rnd((64,), seed=2), 64)
    # q8_matmul shares both checks
    with pytest.raises(ValueError):
        ops.q8_matmul(rnd((4, 100), seed=3), jnp.zeros((100, 3), jnp.int8),
                      jnp.ones((1,)), 64)


# ---------------------------------------------------------------------------
# property sweeps
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(st.sampled_from([jnp.float32, jnp.bfloat16]),
       st.sampled_from([128, 1024]), st.integers(1, 8),
       st.integers(0, 10_000))
def test_encode_ef_property(dtype, block, nblocks, seed):
    ct = special_blocks(nblocks, block, seed=seed, dtype=dtype)
    ef = rnd((nblocks * block,), seed=seed + 1, scale=1e-3)
    got = ops.encode_ef(ct, ef, block)
    want = jax.jit(ref.encode_ef_ref, static_argnums=2)(ct, ef, block)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    # the zero block's compensated signal is just ef: residual must be
    # ef - decode(encode(ef)), finite either way
    assert np.isfinite(np.asarray(got[2])).all()


@settings(max_examples=20, deadline=None)
@given(st.sampled_from([jnp.float32, jnp.bfloat16]),
       st.sampled_from([128, 1024]), st.integers(1, 8),
       st.integers(0, 10_000))
def test_dequantize_into_property(out_dtype, block, nblocks, seed):
    x = special_blocks(nblocks, block, seed=seed)
    codes, scales = ops.quantize(x, block)
    got = ops.dequantize_into(codes, scales, block, out_dtype=out_dtype)
    want = jax.jit(ref.dequantize_into_ref,
                   static_argnums=(2, 3))(codes, scales, block, out_dtype)
    assert got.dtype == jnp.dtype(out_dtype)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# 8-device: deferred-EF microbatch + serve quant matmul on real shards
# ---------------------------------------------------------------------------

_DRIVER_8DEV = textwrap.dedent("""
    import os, json, dataclasses
    # FMA-free XLA:CPU, as in conftest.py: the comparison is bitwise
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                               "--xla_cpu_max_isa=AVX")
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_config, build_model
    from repro.configs.base import ParallelConfig
    from repro.core.fsdp import FSDPRuntime
    from repro.core.schedule import APPROX_VARIANTS, CommSchedule
    from repro.optim import make_optimizer
    from repro.launch.mesh import make_local_mesh

    MESH8 = make_local_mesh(8, 1)
    out = {}

    # deferred-EF microbatch accumulation vs single-batch on 8-way shards
    def train(micro, steps=2):
        cfg = get_config("qwen2.5-14b").reduced()
        cfg = dataclasses.replace(cfg, n_layers=3, parallel=ParallelConfig(
            ("data",), ("data",), microbatches=micro))
        rt = FSDPRuntime(build_model(cfg), MESH8,
                         schedule=CommSchedule(reduce_wire="q8_block"),
                         donate=False)
        params = rt.init_params(0)
        opt = make_optimizer(cfg)
        state = opt.init(rt)
        fn = rt.make_train_step(opt)
        st = jnp.int32(0)
        rng = np.random.default_rng(0)
        losses = []
        for _ in range(steps):
            batch = {"tokens": jnp.asarray(
                rng.integers(0, cfg.vocab, (8, 16)), jnp.int32)}
            params, state, st, m = fn(params, state, st, batch)
            losses.append(float(m["loss"]))
        return losses

    ref_l, acc_l = train(1), train(2)
    out["defer_finite"] = bool(np.isfinite(acc_l).all())
    out["defer_rel"] = max(abs(a - b) / max(1.0, abs(a))
                           for a, b in zip(ref_l, acc_l))

    # serve quant matmul vs dense-dequant q8 serve on 8-way shards
    cfg = get_config("gemma2-2b").reduced()
    model = build_model(cfg)

    def prefill(sched):
        rt = FSDPRuntime(model, MESH8, schedule=sched)
        params = rt.init_params(0)
        cache = model.init_cache(8, 32)
        rng = np.random.default_rng(0)
        batch = {"tokens": jnp.asarray(
            rng.integers(0, cfg.vocab, (8, 8)), jnp.int32)}
        logits, _ = rt.make_prefill_step()(params, batch, cache)
        return np.asarray(logits, np.float32)

    ld = prefill(CommSchedule(param_store="q8_block"))
    lq = prefill(APPROX_VARIANTS["q8_serve_matmul"])
    out["serve_rel"] = float(np.linalg.norm(lq - ld) / np.linalg.norm(ld))
    print(json.dumps(out))
""")


@pytest.mark.slow
def test_fused_8dev_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _DRIVER_8DEV],
                         capture_output=True, text=True, env=env,
                         timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    data = json.loads(out.stdout.strip().splitlines()[-1])
    assert data["defer_finite"], data
    assert data["defer_rel"] < 0.02, data
    assert data["serve_rel"] < 0.15, data
