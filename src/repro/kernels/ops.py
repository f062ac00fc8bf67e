"""THE dispatch layer for every quant hot path (repro.kernels), and for
the fused causal attention of ``layers.attention``.

Every hot-path call site (core.wire encode/decode, core.store
create/rebuild, the q8 reduce-scatter internals, optim.adam8bit, the serve
int8-GEMM) goes through these wrappers -- never through ``quant.blockwise``
directly (CI greps for that).  Dispatch rule:

  * TPU backend: the Pallas kernels compile to Mosaic with the TILE_BLOCKS
    grid.
  * everywhere else (this CPU container): the same kernel body runs in
    ``interpret=True`` mode as ONE full-width tile -- traced jnp, bitwise
    identical to the jitted jnp reference and O(ops), not O(grid steps)
    (see blockwise_quant._resolve_tile).

``quant.blockwise`` stays the reference implementation and the parity
oracle (re-exported through ref.py); the log-space variants used by 8-bit
Adam's second moment have no standalone fused kernel (the fused
adam8bit_update kernel inlines them), so their dispatch is the reference
on every backend -- documented here so the import-check story stays
one sentence: hot paths import repro.kernels.ops, full stop.
"""
from __future__ import annotations

import jax

from ..quant.blockwise import (dequantize_blockwise_log,
                               quantize_blockwise_log)
from .adam8bit_update import adam8bit_update as _adam8
from .adam_update import adamw_update as _adamw
from .blockwise_quant import (dequantize as _deq,
                              dequantize_into as _deq_into, quantize as _q)
from .encode_ef import encode_ef as _encode_ef
from .flash_attention import LANES as _LANES
from .flash_attention import causal_attention as _causal_attention
from .fused_update import (adam8bit_store_update as _adam8_store,
                           adamw_store_update as _adamw_store)
from .q8_matmul import (QuantTensor, fold_scales, q8_matmul as _q8mm,
                        q8_slice_cols as _q8_slice, quant_eligible)

__all__ = [
    "quantize", "dequantize", "dequantize_into", "encode_ef", "q8_matmul",
    "quantize_log", "dequantize_log", "adamw_update", "adam8bit_update",
    "adamw_store_update", "adam8bit_store_update", "q8_slice_cols",
    "QuantTensor", "quant_eligible", "fold_scales", "causal_attention",
]


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def quantize(x, block: int = 1024):
    """Blockwise absmax int8 encode (store create/rebuild, wire encode).

    PARITY: BITWISE -- vs the jitted quant.blockwise reference.
    """
    return _q(x, block=block, interpret=_interpret())


def dequantize(codes, scales, block: int = 1024):
    """Blockwise decode to fp32 (cold paths, 8-bit Adam moments).

    PARITY: BITWISE -- vs the jitted quant.blockwise reference.
    """
    return _deq(codes, scales, block=block, interpret=_interpret())


def dequantize_into(codes, scales, block: int = 1024, *, out_dtype):
    """Gather-path fused decode: codes + scales -> out_dtype, no
    intermediate full-size fp32 buffer.

    PARITY: BITWISE -- vs the jitted decode+cast composition.
    """
    return _deq_into(codes, scales, block=block, out_dtype=out_dtype,
                     interpret=_interpret())


def encode_ef(ct, ef, block: int = 1024):
    """Reduce-path fused encode + error feedback:
    (codes, scales, new_ef) of ``comp = ct.f32 + ef``.

    PARITY: BITWISE -- vs the jitted unfused compensate+encode.
    """
    return _encode_ef(ct, ef, block=block, interpret=_interpret())


def q8_matmul(x, codes, scales, block: int = 1024, *, out_dtype=None):
    """Serve-path int8 x int8 GEMM on gathered codes: the weight scale
    folds into the activation, which is row-quantized to int8.

    PARITY: ALLCLOSE -- bounded new error vs the dense oracle (bitwise
    only against its own jnp op-sequence twin).
    """
    return _q8mm(x, codes, scales, block=block, out_dtype=out_dtype,
                 interpret=_interpret())


def quantize_log(x, block: int = 1024):
    """Log-space blockwise quantize (8-bit Adam's v): reference on every
    backend -- no standalone fused kernel (adam8bit_update fuses it).

    PARITY: BITWISE -- reference passthrough.
    """
    return quantize_blockwise_log(x, block)


def dequantize_log(codes, scales, block: int = 1024):
    """Log-space blockwise decode; reference passthrough like
    ``quantize_log``.

    PARITY: BITWISE -- reference passthrough.
    """
    return dequantize_blockwise_log(codes, scales, block)


def adamw_update(w, g, m, v, mask, *, lr, b1, b2, eps, wd, c1, c2):
    """Fused AdamW moment + weight update.

    PARITY: BITWISE -- vs the jitted kernels/ref.py composition.
    """
    return _adamw(w, g, m, v, mask, lr, b1, b2, eps, wd, c1, c2,
                  interpret=_interpret())


def adam8bit_update(w, g, m8, v8, ms, vs, mask, *, lr, b1, b2, eps, wd,
                    c1, c2, block: int = 1024):
    """Fused 8-bit Adam update (blockwise-quantized moments; the moment
    (de)quant inside is the BITWISE-class blockwise codec).

    PARITY: ALLCLOSE -- few-ulp vs the jitted kernels/ref.py
    composition: the log-space second-moment decode's ``exp`` compiles
    differently inside the pallas interpreter than in the fused XLA
    reference graph (last-ulp transcendental drift, amplified to at
    most a few representation steps through the update chain).
    """
    return _adam8(w, g, m8, v8, ms, vs, mask, lr, b1, b2, eps, wd, c1, c2,
                  block=block, interpret=_interpret())


def adamw_store_update(w, g, m, v, mask, *, lr, b1, b2, eps, wd, c1, c2,
                       fmt: str = "fp32", block: int = 1024):
    """Fused AdamW step + ParamStore rebuild: moment update, weight
    write, and the storage re-encode (bf16 round / fp8 cast / q8
    blockwise requantize) in one pass -- the optimizer hot path for every
    store format.  Returns ``(core, m2, v2)``; ``core`` mirrors
    ``ParamStore.rebuild``.

    PARITY: BITWISE -- vs the jitted kernels/ref.py composition on a
    compiler that contracts no multiply-add (XLA:CPU pinned FMA-free, as
    the tests run it), and on TPU v5e, Mosaic kernel vs XLA:TPU reference
    (chip_smoke.py checks it on a real group shard; DESIGN.md §Kernels).
    """
    return _adamw_store(w, g, m, v, mask, lr, b1, b2, eps, wd, c1, c2,
                        fmt=fmt, block=block, interpret=_interpret())


def adam8bit_store_update(w, g, m8, v8, ms, vs, mask, *, lr, b1, b2, eps,
                          wd, c1, c2, fmt: str = "fp32",
                          block: int = 1024):
    """Fused 8-bit Adam step + ParamStore rebuild: blockwise moment
    dequant/requant AND the storage re-encode in one pass.  Returns
    ``(core, m8', v8', ms', vs')``.

    PARITY: ALLCLOSE -- few-ulp vs the jitted kernels/ref.py
    composition, inherited from ``adam8bit_update``'s log-space
    second-moment ``exp`` (compiles differently in the pallas
    interpreter vs the fused reference graph); the tests pin
    integer-view distance <= 4 on every leaf.
    """
    return _adam8_store(w, g, m8, v8, ms, vs, mask, lr, b1, b2, eps, wd,
                        c1, c2, fmt=fmt, block=block,
                        interpret=_interpret())


def q8_slice_cols(qt, start, width: int):
    """Column slice of a gathered q8 ``QuantTensor`` when the scale
    layout permits (serve-path KV head slicing; ``start`` may be traced).
    Returns the sliced QuantTensor, or None when the slice is not
    scale-representable (caller falls back to ``to_dense``).

    PARITY: BITWISE -- pure index/layout transformation; the sliced
    tensor dequantizes to exactly the sliced dequantized original.
    """
    return _q8_slice(qt, start, width)


def causal_attention(q, k, v, *, scale: float):
    """Fused causal self-attention over positions 0..T-1 (GQA): scores stay
    in VMEM, KV blocks above the diagonal are skipped, and the backward
    keeps only the output and the per-row logsumexp.  Returns None where
    the kernel does not compile here -- off a TPU, or for a sequence its
    128-multiple blocks do not tile (the caller falls back to
    ``layers.chunked_attention``).

    PARITY: ALLCLOSE -- vs ``layers.chunked_attention`` (bf16 MXU
    operands, float32 softmax statistics and accumulators).
    """
    if _interpret() or q.shape[2] % _LANES:
        return None
    return _causal_attention(q, k, v, scale=scale)
