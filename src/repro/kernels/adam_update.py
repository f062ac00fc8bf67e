"""Pallas TPU kernel: fused AdamW step over the flat DBuffer shard.

One VMEM pass reads (w, g, m, v, wd_mask) and writes (w', m', v') -- 5 HBM
streams in, 3 out, versus ~12 round trips for the unfused jnp chain.  This
is the DBuffer group-fused optimizer claim made concrete for TPU.

Scalars (lr, beta-corrections) arrive as a (8,) f32 array broadcast to every
tile (simple + interpret-friendly; SMEM prefetch would shave a copy on real
hardware).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
TILE_ROWS = 64  # 64 x 128 x 4B x 8 bufs = 256 KiB VMEM working set


def _adamw_kernel(s_ref, w_ref, g_ref, m_ref, v_ref, mask_ref,
                  w_out, m_out, v_out):
    lr, b1, b2, eps, wd, c1, c2, _ = [s_ref[i] for i in range(8)]
    g = g_ref[...].astype(jnp.float32)
    m = b1 * m_ref[...] + (1.0 - b1) * g
    v = b2 * v_ref[...] + (1.0 - b2) * g * g
    upd = m / (c1 * (jnp.sqrt(v / c2) + eps))  # = ref.adamw_update_ref
    w = w_ref[...]
    w_out[...] = w - lr * (upd + wd * mask_ref[...] * w)
    m_out[...] = m
    v_out[...] = v


@functools.partial(jax.jit, static_argnames=("interpret",))
def adamw_update(w, g, m, v, mask, lr, b1, b2, eps, wd, c1, c2,
                 *, interpret: bool = False):
    """All arrays flat (n,) with n % 128 == 0 (DBuffer lane alignment)."""
    n = w.size
    rows = n // LANES
    tr = min(TILE_ROWS, rows)
    scalars = jnp.stack([
        jnp.asarray(x, jnp.float32)
        for x in (lr, b1, b2, eps, wd, c1, c2, 0.0)
    ])

    def r(x, dt=jnp.float32):
        return x.reshape(rows, LANES).astype(dt)

    outs = pl.pallas_call(
        _adamw_kernel,
        grid=(pl.cdiv(rows, tr),),
        in_specs=[
            pl.BlockSpec((8,), lambda i: (0,)),
            pl.BlockSpec((tr, LANES), lambda i: (i, 0)),
            pl.BlockSpec((tr, LANES), lambda i: (i, 0)),
            pl.BlockSpec((tr, LANES), lambda i: (i, 0)),
            pl.BlockSpec((tr, LANES), lambda i: (i, 0)),
            pl.BlockSpec((tr, LANES), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((tr, LANES), lambda i: (i, 0)),
            pl.BlockSpec((tr, LANES), lambda i: (i, 0)),
            pl.BlockSpec((tr, LANES), lambda i: (i, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((rows, LANES), jnp.float32)] * 3,
        name="adamw_update",
        interpret=interpret,
    )(scalars, r(w), r(g), r(m), r(v), r(mask))
    return tuple(o.reshape(w.shape) for o in outs)
