"""Pallas TPU kernel: int8 x int8 matmul on gathered q8_block codes.

The serve/decode hot path with ``param_store="q8_block"`` previously
dequantized every gathered layer to the compute dtype before its matmuls.
This kernel keeps the weight in int8 end to end (the rtp-llm dequant-GEMM
pattern): the per-block weight scale is folded into the *activation*, the
scaled activation is quantized per row, and the MXU contracts int8 x int8
into int32.

Scale algebra.  A (K, N) weight is stored row-major in the flat buffer, so
quant block ``b`` covers flat elements [b*block, (b+1)*block) and the
dequant scale of element (k, n) varies along the contraction index k --
a post-hoc rescale of an int8 GEMM is impossible.  Two layouts make the
scale separable per output-column group j (both produced by the planner's
block-aligned tensor starts):

  * case A -- ``N % block == 0``: each row k holds nj = N/block blocks;
    block j of row k covers columns [j*block, (j+1)*block), scale
    s(k, j) = scales[k*nj + j].
  * case B -- ``block % N == 0``: one block spans r = block/N whole rows,
    s(k) = scales[k // r] independent of n (nj = 1).  K need NOT be a
    multiple of r: a trailing partial block (ceil(K/r) scales) folds to
    per-row scales truncated at K -- the codes and scales are the
    buffer's own, so the dequant semantics match the fallback path
    bitwise whatever shares the overhang block.

Both cases reduce to one contract: scales arranged (nj, K); for group j,
``y[:, cols_j] = rowquant(x * s[j]) @ codes[:, cols_j]`` rescaled by the
activation row scale.  Shapes outside these two cases are ineligible
(``quant_eligible``) and fall back to the fused dequantize.

``q8_slice_cols`` slices columns out of a QuantTensor when the scale
layout permits (case B -> per-row scales, any slice; case A -> block-
aligned slices), so the serve path's KV head slicing stays on the int8
GEMM instead of densifying the whole projection.

Tiling: the grid runs (row tiles of TILE_M activations) x (nj column
groups).  Each step holds a (TILE_M, K) activation tile, its group's (1, K)
scale row and the (K, N/nj) codes -- row quantization is per activation
row, so tiling M changes no value.  Interpret mode (non-TPU) runs all rows
as one tile (blockwise_quant._resolve_tile's doctrine).

Parity class: ALLCLOSE vs the dense reference (x @ dequantize(w)) -- the
activation row-quantization is new error by design, bounded by ~1/254
relative per element.  The kernel-vs-jnp-equivalent comparison is bitwise
(same op sequence); both are pinned in tests/test_kernels_fused.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..quant.blockwise import _check_blocking, _check_scales

TILE_M = 256  # activation rows per grid step on TPU (a multiple of 8)


def quant_eligible(shape: tuple[int, ...], block: int) -> bool:
    """Can a tensor of ``shape`` run the int8-GEMM path with this quant
    block?  2-D with a separable scale layout: N % block == 0 (case A)
    or block % N == 0 (case B; K need not be a multiple of block//N --
    the trailing partial block folds to truncated per-row scales)."""
    if len(shape) != 2:
        return False
    k, n = shape
    return n % block == 0 or block % n == 0


def fold_scales(scales_flat, k: int, n: int, block: int) -> jax.Array:
    """Rearrange flat row-major block scales into the kernel's (nj, K)
    contract (see module docstring)."""
    if n % block == 0:
        nj = n // block
        return scales_flat.reshape(k, nj).T           # s[j, k]
    if block % n == 0:
        r = block // n
        # ceil(k/r) scales cover k rows; truncate the overhang block's
        # repeat at k (partial last block, see module docstring)
        return jnp.repeat(scales_flat, r)[:k].reshape(1, k)
    raise ValueError(
        f"q8_matmul: weight ({k}, {n}) has no separable scale layout for "
        f"block {block} (need N % block == 0 or block % N == 0)")


def _q8mm_kernel(out_dtype, x_ref, s_ref, w_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)                # (TM, K)
    a = x * s_ref[...]                                # fold w-scales, (TM, K)
    rmax = jnp.max(jnp.abs(a), axis=1, keepdims=True)  # per-row absmax
    rs = rmax / 127.0
    inv = jnp.where(rs > 0, 1.0 / jnp.maximum(rs, 1e-30), 0.0)
    a8 = jnp.clip(jnp.round(a * inv), -127, 127).astype(jnp.int8)
    acc = jax.lax.dot_general(
        a8, w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)             # int8 x int8 -> int32
    o_ref[...] = (acc.astype(jnp.float32) * rs).astype(out_dtype)


@functools.partial(jax.jit,
                   static_argnames=("block", "out_dtype", "interpret"))
def q8_matmul(x, codes, scales, *, block: int = 1024, out_dtype=None,
              interpret: bool = False):
    """x: (..., K) float; codes: (K, N) int8; scales: flat f32
    ((K*N)//block,) row-major block scales.  Returns (..., N) in
    ``out_dtype`` (default: x.dtype) without ever materializing the
    dequantized weight."""
    k, n = codes.shape
    if n % block == 0:
        _check_blocking(k * n, block, "q8_matmul")
        _check_scales(k * n, block, scales.shape[-1], "q8_matmul")
    elif block % n == 0:
        # case B tolerates a trailing partial block: ceil-count scales
        nb = -(-(k * n) // block)
        if scales.shape[-1] != nb:
            raise ValueError(
                f"q8_matmul: expected {nb} block scales for ({k}, {n}) "
                f"with block {block}, got {scales.shape[-1]}")
    else:
        raise ValueError(
            f"q8_matmul: weight ({k}, {n}) has no separable scale layout "
            f"for block {block} (need N % block == 0 or block % N == 0)")
    out_dtype = jnp.dtype(out_dtype if out_dtype is not None else x.dtype)
    lead = x.shape[:-1]
    m = 1
    for s in lead:
        m *= s
    xm = x.reshape(m, k)
    s2 = fold_scales(scales, k, n, block)             # (nj, K)
    nj = s2.shape[0]
    ncols = n // nj
    tm = max(1, m) if interpret else min(TILE_M, m)
    out = pl.pallas_call(
        functools.partial(_q8mm_kernel, out_dtype),
        grid=(pl.cdiv(m, tm), nj),
        in_specs=[
            pl.BlockSpec((tm, k), lambda i, j: (i, 0)),
            # one (1, K) scale row per column group, leading dim squeezed
            pl.BlockSpec((pl.squeezed, 1, k), lambda i, j: (j, 0, 0)),
            pl.BlockSpec((k, ncols), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((tm, ncols), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        name="q8_matmul",
        interpret=interpret,
    )(xm, s2.reshape(nj, 1, k), codes)
    return out.reshape(lead + (n,))


# --------------------------------------------------------------------------- #
# QuantTensor: a gathered-but-still-quantized weight view
# --------------------------------------------------------------------------- #
class QuantTensor:
    """A 2-D weight as int8 codes + flat block scales, as unpacked from a
    gathered q8_block buffer (core.dbuffer.unpack_quant).  Model code
    multiplies through ``layers.dense`` -> ``ops.q8_matmul`` so the dense
    weight never materializes.  Registered as a pytree (codes/scales are
    leaves, block is static) so it traces through scan/jit."""

    __slots__ = ("codes", "scales", "block")

    def __init__(self, codes, scales, block: int):
        self.codes = codes
        self.scales = scales
        self.block = int(block)

    @property
    def shape(self):
        return self.codes.shape

    @property
    def ndim(self):
        return self.codes.ndim

    def __repr__(self):
        return (f"QuantTensor(shape={tuple(self.codes.shape)}, "
                f"block={self.block})")


jax.tree_util.register_pytree_node(
    QuantTensor,
    lambda qt: ((qt.codes, qt.scales), qt.block),
    lambda block, leaves: QuantTensor(leaves[0], leaves[1], block),
)


def q8_slice_cols(qt: QuantTensor, start, width: int):
    """Slice columns [start, start + width) out of a (K, N) QuantTensor
    without densifying, when the scale layout permits:

      * case B (``block % N == 0``): the block scale never varies along
        n, so ANY column slice keeps the layout.  Re-expressed with
        per-row scales (new block = width, nj = 1), truncating the
        overhang block's repeat at K -- dequant values are exactly those
        of the sliced dense weight.  ``start`` may be traced (the serve
        path slices by a ``lax.axis_index``-derived KV head).
      * case A (``N % block == 0``): only whole-block slices are
        representable -- requires ``width % block == 0`` and ``start``
        a block multiple.  A traced ``start`` is accepted under the
        caller contract ``start % width == 0`` (head slicing), which
        implies block alignment when ``width % block == 0``.

    Returns the sliced QuantTensor, or None when the slice is not
    scale-representable (caller falls back to ``to_dense``).
    """
    k, n = qt.codes.shape
    block = qt.block
    width = int(width)
    if not 0 < width <= n:
        raise ValueError(
            f"q8_slice_cols: width {width} out of range for N={n}")
    if block % n == 0:
        r = block // n
        row_scales = jnp.repeat(qt.scales, r)[:k]
        codes = jax.lax.dynamic_slice(qt.codes, (0, start), (k, width))
        return QuantTensor(codes, row_scales, width)
    if n % block == 0 and width % block == 0:
        if isinstance(start, int) and start % block:
            return None
        nj = n // block
        codes = jax.lax.dynamic_slice(qt.codes, (0, start), (k, width))
        s2 = jax.lax.dynamic_slice(qt.scales.reshape(k, nj),
                                   (0, start // block),
                                   (k, width // block))
        return QuantTensor(codes, s2.reshape(-1), block)
    return None
