"""Pallas TPU kernel: fused 8-bit Adam step (paper §6.3).

Per tile of quant blocks: dequantize(m8, v8) -> Adam math -> weight update
-> requantize, all in one VMEM residency.  The unfused path round-trips the
dequantized fp32 moments through HBM twice; fusing keeps the moments at
int8 in HBM (the whole point of 8-bit Adam) *and* avoids the fp32 spill.

Grid row = TILE_BLOCKS quant blocks of ``block`` elements; scales are one
f32 per block, carried as an (n_blocks, 1) column (blockwise_quant's
layout rule).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .blockwise_quant import TILE_BLOCKS, blocks_spec, scales_spec

_RANGE_NATS = 24.0  # keep in sync with repro.quant.blockwise.RANGE_NATS


def _requant(x):
    """(TB, block) -> (codes int8 (TB, block), scales f32 (TB, 1))."""
    absmax = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    scale = absmax / 127.0
    inv = jnp.where(scale > 0, 1.0 / jnp.maximum(scale, 1e-30), 0.0)
    codes = jnp.clip(jnp.round(x * inv), -127, 127).astype(jnp.int8)
    return codes, scale


def _requant_log(x):
    """Non-negative log-space requant (second moment: linear int8 underflows
    and explodes the update; see repro.quant.blockwise)."""
    absmax = jnp.max(x, axis=1, keepdims=True)
    safe = x / jnp.maximum(absmax, 1e-38)
    logq = jnp.log(jnp.maximum(safe, 1e-38)) / _RANGE_NATS
    codes = jnp.round(127.0 * (1.0 + logq))
    codes = jnp.where(x > 0, jnp.clip(codes, 1, 127), 0).astype(jnp.int8)
    return codes, absmax


def _dequant_log(codes, scales):
    c = codes.astype(jnp.float32)
    val = jnp.exp((c - 127.0) / 127.0 * _RANGE_NATS) * scales
    return jnp.where(c > 0, val, 0.0)


def _adam8_kernel(s_ref, w_ref, g_ref, m8_ref, v8_ref, ms_ref, vs_ref,
                  mask_ref, w_out, m8_out, v8_out, ms_out, vs_out):
    lr, b1, b2, eps, wd, c1, c2, _ = [s_ref[i] for i in range(8)]
    g = g_ref[...].astype(jnp.float32)
    m = m8_ref[...].astype(jnp.float32) * ms_ref[...]
    v = _dequant_log(v8_ref[...], vs_ref[...])
    m = b1 * m + (1.0 - b1) * g
    v = b2 * v + (1.0 - b2) * g * g
    upd = m / (c1 * (jnp.sqrt(v / c2) + eps))  # = ref.adamw_update_ref
    w = w_ref[...]
    w_out[...] = w - lr * (upd + wd * mask_ref[...] * w)
    m8, ms = _requant(m)
    v8, vs = _requant_log(v)
    m8_out[...] = m8
    v8_out[...] = v8
    ms_out[...] = ms
    vs_out[...] = vs


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def adam8bit_update(w, g, m8, v8, ms, vs, mask, lr, b1, b2, eps, wd, c1, c2,
                    *, block: int = 1024, interpret: bool = False):
    """Flat (n,) arrays, n % block == 0; ms/vs are (n//block,)."""
    n = w.size
    nb = n // block
    tb = min(TILE_BLOCKS, nb)
    scalars = jnp.stack([
        jnp.asarray(x, jnp.float32)
        for x in (lr, b1, b2, eps, wd, c1, c2, 0.0)
    ])

    def r(x, dt):
        return x.reshape(nb, block).astype(dt)

    blk = lambda: blocks_spec(tb, block)
    vec = lambda: scales_spec(tb)
    outs = pl.pallas_call(
        _adam8_kernel,
        grid=(pl.cdiv(nb, tb),),
        in_specs=[pl.BlockSpec((8,), lambda i: (0,)),
                  blk(), blk(), blk(), blk(), vec(), vec(), blk()],
        out_specs=[blk(), blk(), blk(), vec(), vec()],
        out_shape=[
            jax.ShapeDtypeStruct((nb, block), jnp.float32),
            jax.ShapeDtypeStruct((nb, block), jnp.int8),
            jax.ShapeDtypeStruct((nb, block), jnp.int8),
            jax.ShapeDtypeStruct((nb, 1), jnp.float32),
            jax.ShapeDtypeStruct((nb, 1), jnp.float32),
        ],
        name="adam8bit_update",
        interpret=interpret,
    )(scalars, r(w, jnp.float32), r(g, jnp.float32), r(m8, jnp.int8),
      r(v8, jnp.int8), ms.reshape(nb, 1), vs.reshape(nb, 1),
      r(mask, jnp.float32))
    w2, m8o, v8o, mso, vso = outs
    return (w2.reshape(w.shape), m8o.reshape(w.shape), v8o.reshape(w.shape),
            mso.reshape(ms.shape), vso.reshape(vs.shape))
