"""Pallas TPU kernels: fused optimizer step + ParamStore rebuild.

The optimizers used to run the update as an unfused jnp chain -- gather
the fp32 master view, do the Adam math, then hand the result to
``store.rebuild`` (a second full pass for bf16 rounding, the fp8 cast, or
the q8 blockwise requantize).  These kernels fuse the whole group update
into one VMEM residency per tile: grad-apply + moment update + weight
write + the store re-encode, so the updated fp32 weights never round-trip
HBM between the math and the encode (the 8-to-12-stream win
``bench_kernels.py`` prices).

Four store epilogues, one math core:

  * fp32      -- write w' as-is (bitwise the pre-fusion path).
  * bf16      -- round w' to bf16 in-register (the storage buffer).
  * fp8_*     -- emit fp8 codes + the fp32 master in one pass (dtypes via
                 ``compat.float8_dtypes``: no versioned jnp symbols here).
  * q8_block  -- blockwise absmax requantize in-register (the same
                 ``_requant`` the fused 8-bit Adam kernel uses, bitwise
                 identical to ``ops.quantize``).

Tiling: flat epilogues tile the buffer in place where its layout allows
and otherwise as (rows, 128) lane tiles (``_flat_tiling``); block
epilogues run (TILE_BLOCKS, block) tiles with (TILE_BLOCKS, 1) scale
columns (blockwise_quant's layout rule) and require the planner's align
guarantee (shard last dim % block == 0).  Interpret mode (non-TPU) runs ONE
full-width tile per the kernels doctrine (blockwise_quant._resolve_tile).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..compat import float8_dtypes
from .adam8bit_update import _dequant_log, _requant, _requant_log
from .blockwise_quant import _resolve_tile, blocks_spec, scales_spec

LANES = 128
TILE_ROWS = 64  # flat-epilogue grid rows (matches adam_update.py)


def _tile_rows(rows: int, interpret: bool) -> int:
    return max(1, rows) if interpret else max(1, min(TILE_ROWS, rows))


def _flat_tiling(shape, interpret: bool):
    """Tiling of a buffer for the elementwise (flat-epilogue) kernels:
    ``(view, spec, grid, to_view, from_view)``.

    A buffer of rank >= 2 whose last dim is lane-aligned (the layered
    ``(n_layers, shard)`` groups) is tiled in place as ``(lead, last)``
    with ``(lead, cols)`` blocks over its columns: reshaping it to
    ``(rows, 128)`` would make XLA:TPU relayout every input and output (a
    full HBM copy each, and a large temporary).  Anything else is viewed
    as ``(rows, 128)`` lane tiles, zero-padding the tail lane (elementwise
    math on zero inputs stays zero, so the pad is inert and sliced back
    off).  Either way a compiled tile holds TILE_ROWS x 128 elements;
    interpret mode runs one full-width tile."""
    n = 1
    for d in shape:
        n *= d
    if len(shape) >= 2 and shape[-1] % LANES == 0:
        lead, last = n // shape[-1], shape[-1]
        cols = last if interpret else min(
            last, max(LANES, TILE_ROWS * LANES // lead // LANES * LANES))
        return ((lead, last),
                lambda: pl.BlockSpec((lead, cols), lambda i: (0, i)),
                (pl.cdiv(last, cols),),
                lambda x: x.reshape(lead, last),
                lambda o: o.reshape(shape))
    pn = -(-n // LANES) * LANES
    rows = pn // LANES
    tr = _tile_rows(rows, interpret)

    def to_view(x):
        flat = x.reshape(-1)
        if pn != n:
            flat = jnp.pad(flat, (0, pn - n))
        return flat.reshape(rows, LANES)

    def from_view(o):
        return o.reshape(-1)[:n].reshape(shape) if pn != n \
            else o.reshape(shape)

    return ((rows, LANES),
            lambda: pl.BlockSpec((tr, LANES), lambda i: (i, 0)),
            (pl.cdiv(rows, tr),), to_view, from_view)


def _scalar_stack(lr, b1, b2, eps, wd, c1, c2):
    return jnp.stack([jnp.asarray(x, jnp.float32)
                      for x in (lr, b1, b2, eps, wd, c1, c2, 0.0)])


# --------------------------------------------------------------------------- #
# shared in-kernel math (op-for-op kernels/ref.py's adamw_update_ref)
# --------------------------------------------------------------------------- #
def _adam_math(s_ref, w_ref, g_ref, m_ref, v_ref, mask_ref):
    lr, b1, b2, eps, wd, c1, c2, _ = [s_ref[i] for i in range(8)]
    g = g_ref[...].astype(jnp.float32)
    m = b1 * m_ref[...] + (1.0 - b1) * g
    v = b2 * v_ref[...] + (1.0 - b2) * g * g
    upd = m / (c1 * (jnp.sqrt(v / c2) + eps))
    w = w_ref[...].astype(jnp.float32)
    w2 = w - lr * (upd + wd * mask_ref[...] * w)
    return w2, m, v


def _adam8_math(s_ref, w_ref, g_ref, m8_ref, v8_ref, ms_ref, vs_ref,
                mask_ref):
    lr, b1, b2, eps, wd, c1, c2, _ = [s_ref[i] for i in range(8)]
    g = g_ref[...].astype(jnp.float32)
    m = m8_ref[...].astype(jnp.float32) * ms_ref[...]
    v = _dequant_log(v8_ref[...], vs_ref[...])
    m = b1 * m + (1.0 - b1) * g
    v = b2 * v + (1.0 - b2) * g * g
    upd = m / (c1 * (jnp.sqrt(v / c2) + eps))
    w = w_ref[...].astype(jnp.float32)
    w2 = w - lr * (upd + wd * mask_ref[...] * w)
    return w2, m, v


# --------------------------------------------------------------------------- #
# AdamW epilogues
# --------------------------------------------------------------------------- #
def _adamw_flat_kernel(out_dt, s_ref, w_ref, g_ref, m_ref, v_ref, mask_ref,
                       w_out, m_out, v_out):
    w2, m, v = _adam_math(s_ref, w_ref, g_ref, m_ref, v_ref, mask_ref)
    w_out[...] = w2.astype(out_dt)
    m_out[...] = m
    v_out[...] = v


def _adamw_fp8_kernel(code_dt, s_ref, w_ref, g_ref, m_ref, v_ref, mask_ref,
                      codes_out, w_out, m_out, v_out):
    w2, m, v = _adam_math(s_ref, w_ref, g_ref, m_ref, v_ref, mask_ref)
    codes_out[...] = w2.astype(code_dt)
    w_out[...] = w2
    m_out[...] = m
    v_out[...] = v


def _adamw_q8_kernel(s_ref, w_ref, g_ref, m_ref, v_ref, mask_ref,
                     codes_out, w_out, scales_out, m_out, v_out):
    w2, m, v = _adam_math(s_ref, w_ref, g_ref, m_ref, v_ref, mask_ref)
    codes, scales = _requant(w2)
    codes_out[...] = codes
    scales_out[...] = scales
    w_out[...] = w2
    m_out[...] = m
    v_out[...] = v


# --------------------------------------------------------------------------- #
# 8-bit Adam epilogues (moments always blockwise-quantized)
# --------------------------------------------------------------------------- #
def _adam8_flat_kernel(out_dt, s_ref, w_ref, g_ref, m8_ref, v8_ref, ms_ref,
                       vs_ref, mask_ref, w_out, m8_out, v8_out, ms_out,
                       vs_out):
    w2, m, v = _adam8_math(s_ref, w_ref, g_ref, m8_ref, v8_ref, ms_ref,
                           vs_ref, mask_ref)
    w_out[...] = w2.astype(out_dt)
    m8, ms = _requant(m)
    v8, vs = _requant_log(v)
    m8_out[...] = m8
    v8_out[...] = v8
    ms_out[...] = ms
    vs_out[...] = vs


def _adam8_fp8_kernel(code_dt, s_ref, w_ref, g_ref, m8_ref, v8_ref, ms_ref,
                      vs_ref, mask_ref, codes_out, w_out, m8_out, v8_out,
                      ms_out, vs_out):
    w2, m, v = _adam8_math(s_ref, w_ref, g_ref, m8_ref, v8_ref, ms_ref,
                           vs_ref, mask_ref)
    codes_out[...] = w2.astype(code_dt)
    w_out[...] = w2
    m8, ms = _requant(m)
    v8, vs = _requant_log(v)
    m8_out[...] = m8
    v8_out[...] = v8
    ms_out[...] = ms
    vs_out[...] = vs


def _adam8_q8_kernel(s_ref, w_ref, g_ref, m8_ref, v8_ref, ms_ref, vs_ref,
                     mask_ref, codes_out, w_out, scales_out, m8_out, v8_out,
                     ms_out, vs_out):
    w2, m, v = _adam8_math(s_ref, w_ref, g_ref, m8_ref, v8_ref, ms_ref,
                           vs_ref, mask_ref)
    codes, scales = _requant(w2)
    codes_out[...] = codes
    scales_out[...] = scales
    w_out[...] = w2
    m8, ms = _requant(m)
    v8, vs = _requant_log(v)
    m8_out[...] = m8
    v8_out[...] = v8
    ms_out[...] = ms
    vs_out[...] = vs


# --------------------------------------------------------------------------- #
# wrappers
# --------------------------------------------------------------------------- #
def _check_fmt(fmt: str) -> None:
    if fmt not in ("fp32", "bf16", "q8_block") and not (
            fmt.startswith("fp8_") and fmt in float8_dtypes()):
        raise ValueError(f"unknown store fmt {fmt!r} for the fused update")


def _check_block(shape, block: int, who: str) -> None:
    if shape[-1] % block:
        raise ValueError(
            f"{who} needs last dim % block == 0, got {shape[-1]} % "
            f"{block} -- planner align missing?")


@functools.partial(jax.jit, static_argnames=("fmt", "block", "interpret"))
def adamw_store_update(w, g, m, v, mask, lr, b1, b2, eps, wd, c1, c2, *,
                       fmt: str = "fp32", block: int = 1024,
                       interpret: bool = False):
    """One fused pass: AdamW step + store re-encode.  ``w`` is the
    storage buffer (fp32, or bf16 for the bf16 store; fp8/q8 pass the
    fp32 master).  Returns ``(core, m2, v2)`` where ``core`` mirrors
    ``ParamStore.rebuild``: a bare array for flat formats, the
    codes(+scales)+master dict for fp8/q8."""
    _check_fmt(fmt)
    scalars = _scalar_stack(lr, b1, b2, eps, wd, c1, c2)
    n = w.size

    if fmt == "q8_block":
        _check_block(w.shape, block, "q8_block store update")
        nb = n // block
        tb = _resolve_tile(nb, interpret, None)
        blk = lambda: blocks_spec(tb, block)
        vec = lambda: scales_spec(tb)
        r = lambda x: x.reshape(nb, block)
        codes, w2, scales, m2, v2 = pl.pallas_call(
            _adamw_q8_kernel,
            grid=(pl.cdiv(nb, tb),),
            in_specs=[pl.BlockSpec((8,), lambda i: (0,)),
                      blk(), blk(), blk(), blk(), blk()],
            out_specs=[blk(), blk(), vec(), blk(), blk()],
            out_shape=[
                jax.ShapeDtypeStruct((nb, block), jnp.int8),
                jax.ShapeDtypeStruct((nb, block), jnp.float32),
                jax.ShapeDtypeStruct((nb, 1), jnp.float32),
                jax.ShapeDtypeStruct((nb, block), jnp.float32),
                jax.ShapeDtypeStruct((nb, block), jnp.float32),
            ],
            name="adamw_store_update",
            interpret=interpret,
        )(scalars, r(w), r(g), r(m), r(v), r(mask))
        core = {"codes": codes.reshape(w.shape),
                "master": w2.reshape(w.shape),
                "scales": scales.reshape(
                    w.shape[:-1] + (w.shape[-1] // block,))}
        return core, m2.reshape(w.shape), v2.reshape(w.shape)

    # flat epilogues: elementwise, so any tiling of the buffer will do
    view, tile, grid, r, unview = _flat_tiling(w.shape, interpret)
    f32_out = jax.ShapeDtypeStruct(view, jnp.float32)
    args = (scalars, r(w), r(g), r(m), r(v), r(mask))
    in_specs = [pl.BlockSpec((8,), lambda i: (0,)),
                tile(), tile(), tile(), tile(), tile()]

    if fmt.startswith("fp8_"):
        code_dt = jnp.dtype(float8_dtypes()[fmt])
        codes, w2, m2, v2 = pl.pallas_call(
            functools.partial(_adamw_fp8_kernel, code_dt),
            grid=grid,
            in_specs=in_specs,
            out_specs=[tile(), tile(), tile(), tile()],
            out_shape=[jax.ShapeDtypeStruct(view, code_dt),
                       f32_out, f32_out, f32_out],
            name="adamw_store_update",
            interpret=interpret,
        )(*args)
        return ({"codes": unview(codes), "master": unview(w2)},
                unview(m2), unview(v2))

    out_dt = jnp.dtype(jnp.bfloat16 if fmt == "bf16" else jnp.float32)
    w2, m2, v2 = pl.pallas_call(
        functools.partial(_adamw_flat_kernel, out_dt),
        grid=grid,
        in_specs=in_specs,
        out_specs=[tile(), tile(), tile()],
        out_shape=[jax.ShapeDtypeStruct(view, out_dt), f32_out, f32_out],
        name="adamw_store_update",
        interpret=interpret,
    )(*args)
    return unview(w2), unview(m2), unview(v2)


@functools.partial(jax.jit, static_argnames=("fmt", "block", "interpret"))
def adam8bit_store_update(w, g, m8, v8, ms, vs, mask, lr, b1, b2, eps, wd,
                          c1, c2, *, fmt: str = "fp32", block: int = 1024,
                          interpret: bool = False):
    """One fused pass: 8-bit Adam step (blockwise moment dequant/requant)
    + store re-encode.  All formats run the (TILE_BLOCKS, block) grid --
    the quantized moments pin the block layout, so the planner align
    guarantee (last dim % block == 0) is already required.  Returns
    ``(core, m8', v8', ms', vs')``."""
    _check_fmt(fmt)
    _check_block(w.shape, block, "adam8bit store update")
    scalars = _scalar_stack(lr, b1, b2, eps, wd, c1, c2)
    n = w.size
    nb = n // block
    tb = _resolve_tile(nb, interpret, None)
    blk = lambda: blocks_spec(tb, block)
    vec = lambda: scales_spec(tb)
    r = lambda x: x.reshape(nb, block)
    in_specs = [pl.BlockSpec((8,), lambda i: (0,)),
                blk(), blk(), blk(), blk(), vec(), vec(), blk()]
    args = (scalars, r(w), r(g), r(m8), r(v8), ms.reshape(nb, 1),
            vs.reshape(nb, 1), r(mask))
    moment_outs = [
        jax.ShapeDtypeStruct((nb, block), jnp.int8),
        jax.ShapeDtypeStruct((nb, block), jnp.int8),
        jax.ShapeDtypeStruct((nb, 1), jnp.float32),
        jax.ShapeDtypeStruct((nb, 1), jnp.float32),
    ]

    def pack_moments(m8o, v8o, mso, vso):
        return (m8o.reshape(w.shape), v8o.reshape(w.shape),
                mso.reshape(ms.shape), vso.reshape(vs.shape))

    if fmt == "q8_block":
        codes, w2, scales, m8o, v8o, mso, vso = pl.pallas_call(
            _adam8_q8_kernel,
            grid=(pl.cdiv(nb, tb),),
            in_specs=in_specs,
            out_specs=[blk(), blk(), vec(), blk(), blk(), vec(), vec()],
            out_shape=[
                jax.ShapeDtypeStruct((nb, block), jnp.int8),
                jax.ShapeDtypeStruct((nb, block), jnp.float32),
                jax.ShapeDtypeStruct((nb, 1), jnp.float32),
            ] + moment_outs,
            name="adam8bit_store_update",
            interpret=interpret,
        )(*args)
        core = {"codes": codes.reshape(w.shape),
                "master": w2.reshape(w.shape),
                "scales": scales.reshape(
                    w.shape[:-1] + (w.shape[-1] // block,))}
        return (core,) + pack_moments(m8o, v8o, mso, vso)

    if fmt.startswith("fp8_"):
        code_dt = jnp.dtype(float8_dtypes()[fmt])
        codes, w2, m8o, v8o, mso, vso = pl.pallas_call(
            functools.partial(_adam8_fp8_kernel, code_dt),
            grid=(pl.cdiv(nb, tb),),
            in_specs=in_specs,
            out_specs=[blk(), blk(), blk(), blk(), vec(), vec()],
            out_shape=[
                jax.ShapeDtypeStruct((nb, block), code_dt),
                jax.ShapeDtypeStruct((nb, block), jnp.float32),
            ] + moment_outs,
            name="adam8bit_store_update",
            interpret=interpret,
        )(*args)
        core = {"codes": codes.reshape(w.shape),
                "master": w2.reshape(w.shape)}
        return (core,) + pack_moments(m8o, v8o, mso, vso)

    out_dt = jnp.dtype(jnp.bfloat16 if fmt == "bf16" else jnp.float32)
    w2, m8o, v8o, mso, vso = pl.pallas_call(
        functools.partial(_adam8_flat_kernel, out_dt),
        grid=(pl.cdiv(nb, tb),),
        in_specs=in_specs,
        out_specs=[blk(), blk(), blk(), vec(), vec()],
        out_shape=[jax.ShapeDtypeStruct((nb, block), out_dt)]
        + moment_outs,
        name="adam8bit_store_update",
        interpret=interpret,
    )(*args)
    return (w2.reshape(w.shape),) + pack_moments(m8o, v8o, mso, vso)
