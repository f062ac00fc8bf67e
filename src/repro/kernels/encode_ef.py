"""Pallas TPU kernel: fused gradient-wire encode + error feedback.

The q8_block reduce wire (QSDP, Markov et al.) runs, per backward pass and
per device: ``comp = ct.astype(f32) + ef`` (apply the residual), blockwise
INT8 encode of ``comp``, and ``new_ef = comp - decode(encode(comp))`` (the
fresh quantization error).  Unfused that is three full-size passes over the
cotangent with an fp32 intermediate per step; this kernel does EF-add,
absmax/scale, round/clip, and residual update in ONE VMEM pass.

Bitwise contract: the kernel body performs the exact op sequence of the
unfused path (cast, add, absmax, divide, round/clip, multiply, subtract),
so codes, scales, and the residual are bitwise identical to
``core.wire.codec_reduce_scatter``'s unfused composition -- pinned by
tests/test_kernels_fused.py.  Tiling/contract rules are shared with
``blockwise_quant`` (full-width single tile in interpret mode, TILE_BLOCKS
grid compiled, identical ValueErrors to the jnp reference).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..quant.blockwise import _check_blocking
from .blockwise_quant import _resolve_tile, blocks_spec, scales_spec


def _encode_ef_kernel(ct_ref, ef_ref, codes_ref, scales_ref, newef_ref):
    comp = ct_ref[...].astype(jnp.float32) + ef_ref[...]   # (TB, block)
    absmax = jnp.max(jnp.abs(comp), axis=1, keepdims=True)  # (TB, 1)
    scale = absmax / 127.0
    inv = jnp.where(scale > 0, 1.0 / jnp.maximum(scale, 1e-30), 0.0)
    codes = jnp.clip(jnp.round(comp * inv), -127, 127)
    codes_ref[...] = codes.astype(jnp.int8)
    scales_ref[...] = scale
    # codes holds integral f32 values in [-127, 127]: multiplying here is
    # bit-identical to dequantizing the int8 output
    newef_ref[...] = comp - codes * scale


@functools.partial(jax.jit,
                   static_argnames=("block", "interpret", "tile_blocks"))
def encode_ef(ct, ef, *, block: int = 1024, interpret: bool = False,
              tile_blocks: int | None = None):
    """(ct (..., n) any float, ef (..., n) f32) ->
    (codes int8 (..., n), scales f32 (..., n//block), new_ef f32 (..., n)).

    Semantics: ``comp = ct.f32 + ef; codes, scales = quantize(comp);
    new_ef = comp - dequantize(codes, scales)`` -- fused."""
    shape = ct.shape
    n = shape[-1]
    _check_blocking(n, block, "encode_ef")
    if ef.shape != ct.shape:
        raise ValueError(
            f"encode_ef: ef shape {ef.shape} != ct shape {ct.shape}")
    nb = n // block
    lead = 1
    for s in shape[:-1]:
        lead *= s
    total = lead * nb
    ctb = ct.reshape(total, block)
    efb = ef.astype(jnp.float32).reshape(total, block)
    tb = _resolve_tile(total, interpret, tile_blocks)
    codes, scales, new_ef = pl.pallas_call(
        _encode_ef_kernel,
        grid=(pl.cdiv(total, tb),),
        in_specs=[blocks_spec(tb, block), blocks_spec(tb, block)],
        out_specs=[blocks_spec(tb, block), scales_spec(tb),
                   blocks_spec(tb, block)],
        out_shape=[
            jax.ShapeDtypeStruct((total, block), jnp.int8),
            jax.ShapeDtypeStruct((total, 1), jnp.float32),
            jax.ShapeDtypeStruct((total, block), jnp.float32),
        ],
        name="encode_ef",
        interpret=interpret,
    )(ctb, efb)
    return (codes.reshape(shape), scales.reshape(shape[:-1] + (nb,)),
            new_ef.reshape(shape))
