"""Pallas TPU kernel: block-wise INT8 quantize / dequantize.

The paper's 8-bit Adam path quantizes each device's *local shard* in fixed
blocks (32x32 == 1024 flat elements), which RaggedShard's planner guarantees
never straddle tensors or device boundaries.  This is bandwidth-bound
elementwise work -- exactly what wants a fused VMEM pass.

Layout: x is viewed as (n_blocks, block); one grid row handles ``tile``
quant blocks.  block is a multiple of 128 (lane width).  The per-block
scales ride as an (n_blocks, 1) column with (tile, 1) blocks: Mosaic
admits a 1-D block only when it is the whole array or a multiple of 128,
so a (TILE_BLOCKS,) vector of scales does not lower.  TILE_BLOCKS = 32 is
int8's native sublane tiling on TPU (32 x 128 per packed tile), so the
int8 codes tiles never split a packed tile; a 32 x 1024 x 4 B f32 tile is
128 KiB per ref, far inside the scoped VMEM limit.

Tiling rule (``_resolve_tile``): compiled (TPU) runs the TILE_BLOCKS grid;
interpret mode (the CPU container, where the grid is unrolled by the
interpreter) defaults to ONE full-width tile -- the kernel body applied to
the whole (n_blocks, block) view, which is bitwise identical and keeps the
trace linear in ops, not in grid steps.  Tests pass ``tile_blocks=`` to
force the tiled grid in interpret mode and exercise the cdiv overhang
(partial last tile): per-block absmax has no cross-row dataflow and Pallas
pads reads / clips writes, so the overhang needs no masking -- pinned by
the partial-tile parity suite in tests/test_kernels.py.

Contract: ``n % block != 0``, ``block < 1``, and a scales/blocks mismatch
raise the same ValueError as the jnp reference (the checks are shared with
``quant.blockwise``), instead of failing later with a cryptic reshape
error.

``dequantize_into`` is the gather-path fused kernel: codes + scales ->
*compute dtype* in one pass, so no full-size fp32 buffer exists between
the dequant multiply and the cast (the jaxpr regression in
tests/test_kernels_fused.py pins this).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..quant.blockwise import _check_blocking, _check_scales

TILE_BLOCKS = 32


def _resolve_tile(total: int, interpret: bool,
                  tile_blocks: int | None) -> int:
    """Blocks per grid row: explicit override > full-width (interpret) >
    TILE_BLOCKS (compiled)."""
    if tile_blocks is not None:
        return max(1, min(tile_blocks, total))
    if interpret:
        return max(1, total)
    return max(1, min(TILE_BLOCKS, total))


def _quant_kernel(x_ref, codes_ref, scales_ref):
    x = x_ref[...].astype(jnp.float32)                     # (TB, block)
    absmax = jnp.max(jnp.abs(x), axis=1, keepdims=True)    # (TB, 1)
    scale = absmax / 127.0
    inv = jnp.where(scale > 0, 1.0 / jnp.maximum(scale, 1e-30), 0.0)
    codes = jnp.clip(jnp.round(x * inv), -127, 127)
    codes_ref[...] = codes.astype(jnp.int8)
    scales_ref[...] = scale


def _dequant_kernel(out_dtype, codes_ref, scales_ref, out_ref):
    # one fused pass: int8 -> f32 multiply -> target dtype, never writing
    # the f32 product to memory (out_ref IS the compute-dtype buffer)
    out_ref[...] = (
        codes_ref[...].astype(jnp.float32) * scales_ref[...]
    ).astype(out_dtype)


def blocks_spec(tb: int, block: int) -> pl.BlockSpec:
    """(tb, block) tiles over an (n_blocks, block) view."""
    return pl.BlockSpec((tb, block), lambda i: (i, 0))


def scales_spec(tb: int) -> pl.BlockSpec:
    """(tb, 1) tiles over the (n_blocks, 1) scales column."""
    return pl.BlockSpec((tb, 1), lambda i: (i, 0))


@functools.partial(jax.jit,
                   static_argnames=("block", "interpret", "tile_blocks"))
def quantize(x, *, block: int = 1024, interpret: bool = False,
             tile_blocks: int | None = None):
    """x: (..., n) with n % block == 0 -> (codes int8 like x, scales f32
    (..., n//block))."""
    shape = x.shape
    n = shape[-1]
    _check_blocking(n, block, "quantize")
    nb = n // block
    lead = 1
    for s in shape[:-1]:
        lead *= s
    xb = x.reshape(lead * nb, block)
    total = lead * nb
    tb = _resolve_tile(total, interpret, tile_blocks)
    grid = (pl.cdiv(total, tb),)
    codes, scales = pl.pallas_call(
        _quant_kernel,
        grid=grid,
        in_specs=[blocks_spec(tb, block)],
        out_specs=[blocks_spec(tb, block), scales_spec(tb)],
        out_shape=[
            jax.ShapeDtypeStruct((total, block), jnp.int8),
            jax.ShapeDtypeStruct((total, 1), jnp.float32),
        ],
        name="quantize",
        interpret=interpret,
    )(xb)
    return codes.reshape(shape), scales.reshape(shape[:-1] + (nb,))


@functools.partial(jax.jit,
                   static_argnames=("block", "out_dtype", "interpret",
                                    "tile_blocks"))
def dequantize_into(codes, scales, *, block: int = 1024,
                    out_dtype=jnp.float32, interpret: bool = False,
                    tile_blocks: int | None = None):
    """Fused dequant-into-compute-dtype: codes + scales -> ``out_dtype``
    in one VMEM pass (the all-gather decode hot path).  With
    out_dtype=float32 this is the plain dequantize."""
    shape = codes.shape
    n = shape[-1]
    _check_blocking(n, block, "dequantize")
    nb = n // block
    _check_scales(n, block, scales.shape[-1], "dequantize")
    lead = 1
    for s in shape[:-1]:
        lead *= s
    cb = codes.reshape(lead * nb, block)
    sb = scales.reshape(lead * nb, 1)
    total = lead * nb
    tb = _resolve_tile(total, interpret, tile_blocks)
    out_dtype = jnp.dtype(out_dtype)
    out = pl.pallas_call(
        functools.partial(_dequant_kernel, out_dtype),
        grid=(pl.cdiv(total, tb),),
        in_specs=[blocks_spec(tb, block), scales_spec(tb)],
        out_specs=blocks_spec(tb, block),
        out_shape=jax.ShapeDtypeStruct((total, block), out_dtype),
        name="dequantize_into",
        interpret=interpret,
    )(cb, sb)
    return out.reshape(shape)


def dequantize(codes, scales, *, block: int = 1024, interpret: bool = False,
               tile_blocks: int | None = None):
    """f32 dequantize (the pre-fusion signature, kept for the optimizer
    paths that want the fp32 buffer anyway)."""
    return dequantize_into(codes, scales, block=block,
                           out_dtype=jnp.float32, interpret=interpret,
                           tile_blocks=tile_blocks)
