"""Pure-jnp oracles for every Pallas kernel (the correctness contract)."""
from __future__ import annotations

import jax.numpy as jnp

from ..quant.blockwise import (
    dequantize_blockwise, dequantize_blockwise_log, quantize_blockwise,
    quantize_blockwise_log,
)


def quantize_ref(x, block: int):
    return quantize_blockwise(x, block)


def dequantize_ref(codes, scales, block: int):
    return dequantize_blockwise(codes, scales, block)


def dequantize_into_ref(codes, scales, block: int, out_dtype):
    """Unfused gather-path decode: f32 dequant buffer, THEN the cast --
    exactly what the fused kernel eliminates (same values, one more
    full-size fp32 materialization)."""
    return dequantize_blockwise(codes, scales, block).astype(out_dtype)


def encode_ef_ref(ct, ef, block: int):
    """Unfused reduce-path encode + error feedback (the op sequence
    core.wire ran before fusion): returns (codes, scales, new_ef)."""
    comp = ct.astype(jnp.float32) + ef
    codes, scales = quantize_blockwise(comp, block)
    new_ef = comp - dequantize_blockwise(codes, scales, block)
    return codes, scales, new_ef


def q8_matmul_ref(x, codes, scales, block: int, out_dtype=None):
    """Dense semantic oracle for the int8-GEMM path: dequantize the whole
    weight, matmul in f32.  The kernel is ALLCLOSE to this (activation
    row-quantization error), never bitwise."""
    k, n = codes.shape
    w = dequantize_blockwise(codes.reshape(-1), scales, block).reshape(k, n)
    y = x.astype(jnp.float32) @ w
    return y.astype(out_dtype if out_dtype is not None else x.dtype)


def adamw_update_ref(w, g, m, v, mask, lr, b1, b2, eps, wd, c1, c2):
    g = g.astype(jnp.float32)
    m2 = b1 * m + (1 - b1) * g
    v2 = b2 * v + (1 - b2) * g * g
    # one divide by the whole denominator: XLA's simplifier rewrites
    # (m / c1) / d into m / (c1 * d) anyway, and the Pallas kernels spell
    # it this way so Mosaic runs the op sequence XLA:TPU runs
    upd = m2 / (c1 * (jnp.sqrt(v2 / c2) + eps))
    w2 = w - lr * (upd + wd * mask * w)
    return w2, m2, v2


def adam8bit_update_ref(w, g, m8, v8, ms, vs, mask, lr, b1, b2, eps, wd,
                        c1, c2, block: int):
    m = dequantize_blockwise(m8, ms, block)
    v = dequantize_blockwise_log(v8, vs, block)
    w2, m2, v2 = adamw_update_ref(w, g, m, v, mask, lr, b1, b2, eps, wd,
                                  c1, c2)
    m8o, mso = quantize_blockwise(m2, block)
    v8o, vso = quantize_blockwise_log(v2, block)
    return w2, m8o, v8o, mso, vso


def store_pack_ref(w2_f32, fmt: str, block: int):
    """Unfused ``ParamStore.rebuild`` semantics on an updated fp32 buffer:
    the storage re-encode the fused update kernels fold into their
    epilogue (bare array for flat formats, codes(+scales)+master dict for
    fp8/q8)."""
    if fmt == "fp32":
        return w2_f32
    if fmt == "bf16":
        return w2_f32.astype(jnp.bfloat16)
    if fmt.startswith("fp8_"):
        from ..compat import float8_dtypes

        return {"codes": w2_f32.astype(float8_dtypes()[fmt]),
                "master": w2_f32}
    if fmt == "q8_block":
        codes, scales = quantize_blockwise(w2_f32, block)
        return {"codes": codes, "master": w2_f32, "scales": scales}
    raise ValueError(f"unknown store fmt {fmt!r}")


def adamw_store_update_ref(w, g, m, v, mask, lr, b1, b2, eps, wd, c1, c2,
                           fmt: str, block: int):
    """Unfused oracle for the fused AdamW + store-rebuild kernel: the
    update math on the fp32 view of the storage buffer, THEN the store
    re-encode as a second full pass."""
    w2, m2, v2 = adamw_update_ref(w.astype(jnp.float32), g, m, v, mask,
                                  lr, b1, b2, eps, wd, c1, c2)
    return store_pack_ref(w2, fmt, block), m2, v2


def adam8bit_store_update_ref(w, g, m8, v8, ms, vs, mask, lr, b1, b2, eps,
                              wd, c1, c2, fmt: str, block: int):
    """Unfused oracle for the fused 8-bit Adam + store-rebuild kernel."""
    w2, m8o, v8o, mso, vso = adam8bit_update_ref(
        w.astype(jnp.float32), g, m8, v8, ms, vs, mask, lr, b1, b2, eps,
        wd, c1, c2, block)
    return store_pack_ref(w2, fmt, block), m8o, v8o, mso, vso
