"""Pallas TPU kernels: causal flash attention, forward and backward.

Causal self-attention over a whole sequence (training, and prefill without
a cache) whose positions are the sequence's own index.  Three kernels, one
``custom_vjp``:

  * ``flash_attention_fwd``  -- online softmax over KV blocks; writes the
    output and the per-row logsumexp, nothing of size T x T.
  * ``flash_attention_dq``   -- dQ, one Q block against its KV blocks.
  * ``flash_attention_dkv``  -- dK and dV, one KV block against its Q
    blocks, scores held transposed (keys in sublanes, queries in lanes) so
    that P^T dO and dS^T Q are plain matmuls.

The backward keeps only (q, k, v, o, logsumexp): the probabilities are
recomputed per block in VMEM.  Every KV block wholly above the diagonal is
skipped -- its compute by ``pl.when``, its DMA by an index map that repeats
the last block needed, which the pipeline does not fetch again.  Only the
blocks that cross the diagonal build a mask.

GQA without copies: a grid step holds one KV head and the ``group`` query
heads that share it (q viewed as (B, Hkv, group, T, hd)), so K and V are
read once per group and dK, dV are summed over the group in VMEM.

Precision: q, k, v and dO enter the MXU as bf16 with float32 accumulation
(one bf16 pass, as XLA:TPU runs the float32 einsums of
``layers.chunked_attention`` at default precision); the score multiplier is
applied to the float32 scores; softmax statistics, the output accumulator
and the dQ, dK, dV accumulators stay float32.

Interpret mode (non-TPU) runs the same grid; tests use T = 256 with
128-blocks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
NEG_INF = -1e30
NT = (((1,), (1,)), ((), ()))   # contract the last dims: a @ b.T
VMEM_LIMIT = 64 * 1024 * 1024


def block_sizes(T: int) -> tuple[int, int]:
    """(block_q, block_kv) for a sequence of T tokens: 512 where T allows,
    else the largest multiple of 128 that divides T.  512 x 512 was the
    fastest of 256 to 1024 at head sizes 64 and 128 alike on a v5e."""
    for b in (512, 384, 256, 128):
        if T % b == 0:
            return b, b
    raise ValueError(f"T={T} is not a multiple of {LANES}")


def _lanes(x, n):
    """A (rows, 128) lane-broadcast statistic widened or cut to n lanes."""
    if n > LANES:
        x = jnp.tile(x, (1, pl.cdiv(n, LANES)))
    return x[:, :n]


def _params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=VMEM_LIMIT)


def _causal_mask(s, row0, col0, *, rows_are_q):
    r = lax.broadcasted_iota(jnp.int32, s.shape, 0) + row0
    c = lax.broadcasted_iota(jnp.int32, s.shape, 1) + col0
    ok = (c <= r) if rows_are_q else (r <= c)
    return jnp.where(ok, s, NEG_INF)


def _q_major_maps(bq, bk):
    """Index maps of a grid (b, h, Q block i, KV block j): the Q-side
    blocks follow i; above the diagonal the KV map repeats the last block
    needed, which the pipeline does not fetch again."""
    def q_map(b, h, i, j):
        return (b, h, 0, i, 0)

    def kv_map(b, h, i, j):
        return (b, h, jnp.minimum(j, (i * bq + bq - 1) // bk), 0)

    return q_map, kv_map


def _when_needed(needed, below, step):
    """Run ``step(masked)``: unmasked on blocks wholly at or below the
    diagonal, masked on blocks that cross it, not at all above it."""
    pl.when(below)(lambda: step(False))
    pl.when(needed & jnp.logical_not(below))(lambda: step(True))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_sc, l_sc, acc_sc, *,
                scale, bq, bk, groups, nk):
    i, j = pl.program_id(2), pl.program_id(3)
    hd = acc_sc.shape[-1]

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full(m_sc.shape, NEG_INF, jnp.float32)
        l_sc[...] = jnp.zeros(l_sc.shape, jnp.float32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)

    def step(masked):
        k, v = k_ref[...], v_ref[...]
        for g in range(groups):
            s = lax.dot_general(q_ref[g], k, NT,
                                preferred_element_type=jnp.float32) * scale
            if masked:
                s = _causal_mask(s, i * bq, j * bk, rows_are_q=True)
            m_prev = m_sc[g]
            m_next = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            p = jnp.exp(s - _lanes(m_next, bk))
            alpha = jnp.exp(m_prev - m_next)
            l_sc[g] = alpha * l_sc[g] + p.sum(axis=1, keepdims=True)
            m_sc[g] = m_next
            acc_sc[g] = acc_sc[g] * _lanes(alpha, hd) + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)

    _when_needed((j * bk) <= (i * bq + bq - 1),
                 (j * bk + bk - 1) <= (i * bq), step)

    @pl.when(j == nk - 1)
    def _store():
        for g in range(groups):
            l = l_sc[g]
            o_ref[g] = (acc_sc[g] / _lanes(l, hd)).astype(o_ref.dtype)
            lse_ref[g] = m_sc[g] + jnp.log(l)


def _fwd(q, k, v, scale, blocks, interpret):
    """q (B, Hkv, G, T, hd), k and v (B, Hkv, T, hd) ->
    (o like q, logsumexp (B, Hkv, G, T, 128) float32, lane-broadcast)."""
    B, Hkv, G, T, hd = q.shape
    bq, bk = blocks
    nq, nk = T // bq, T // bk

    q_map, kv_map = _q_major_maps(bq, bk)
    kernel = functools.partial(_fwd_kernel, scale=scale, bq=bq, bk=bk,
                               groups=G, nk=nk)
    q_spec = pl.BlockSpec((None, None, G, bq, hd), q_map)
    kv_spec = pl.BlockSpec((None, None, bk, hd), kv_map)
    return pl.pallas_call(
        kernel,
        grid=(B, Hkv, nq, nk),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[q_spec,
                   pl.BlockSpec((None, None, G, bq, LANES), q_map)],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((B, Hkv, G, T, LANES), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((G, bq, LANES), jnp.float32),
                        pltpu.VMEM((G, bq, LANES), jnp.float32),
                        pltpu.VMEM((G, bq, hd), jnp.float32)],
        compiler_params=_params("parallel", "parallel", "parallel",
                                "arbitrary"),
        interpret=interpret,
        name="flash_attention_fwd",
    )(q, k, v)


# ---------------------------------------------------------------------------
# backward: dQ
# ---------------------------------------------------------------------------

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref, dq_sc, *,
               scale, bq, bk, groups, nk):
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        dq_sc[...] = jnp.zeros(dq_sc.shape, jnp.float32)

    def step(masked):
        k, v = k_ref[...], v_ref[...]
        for g in range(groups):
            s = lax.dot_general(q_ref[g], k, NT,
                                preferred_element_type=jnp.float32) * scale
            if masked:
                s = _causal_mask(s, i * bq, j * bk, rows_are_q=True)
            p = jnp.exp(s - _lanes(lse_ref[g], bk))
            dp = lax.dot_general(do_ref[g], v, NT,
                                 preferred_element_type=jnp.float32)
            ds = p * (dp - _lanes(di_ref[g], bk)) * scale
            dq_sc[g] += jnp.dot(ds.astype(k.dtype), k,
                                preferred_element_type=jnp.float32)

    _when_needed((j * bk) <= (i * bq + bq - 1),
                 (j * bk + bk - 1) <= (i * bq), step)

    @pl.when(j == nk - 1)
    def _store():
        dq_ref[...] = dq_sc[...].astype(dq_ref.dtype)


def _dq(q, k, v, do, lse, di, scale, blocks, interpret):
    B, Hkv, G, T, hd = q.shape
    bq, bk = blocks
    nq, nk = T // bq, T // bk

    q_map, kv_map = _q_major_maps(bq, bk)
    kernel = functools.partial(_dq_kernel, scale=scale, bq=bq, bk=bk,
                               groups=G, nk=nk)
    q_spec = pl.BlockSpec((None, None, G, bq, hd), q_map)
    row_spec = pl.BlockSpec((None, None, G, bq, LANES), q_map)
    kv_spec = pl.BlockSpec((None, None, bk, hd), kv_map)
    return pl.pallas_call(
        kernel,
        grid=(B, Hkv, nq, nk),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((G, bq, hd), jnp.float32)],
        compiler_params=_params("parallel", "parallel", "parallel",
                                "arbitrary"),
        interpret=interpret,
        name="flash_attention_dq",
    )(q, k, v, do, lse, di)


# ---------------------------------------------------------------------------
# backward: dK, dV
# ---------------------------------------------------------------------------

def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dk_ref, dv_ref,
                dk_sc, dv_sc, *, scale, bq, bk, groups, nq):
    j, i = pl.program_id(2), pl.program_id(3)

    @pl.when(i == 0)
    def _init():
        dk_sc[...] = jnp.zeros(dk_sc.shape, jnp.float32)
        dv_sc[...] = jnp.zeros(dv_sc.shape, jnp.float32)

    def step(masked):
        k, v = k_ref[...], v_ref[...]
        for g in range(groups):
            q, do = q_ref[g], do_ref[g]
            # transposed scores: keys in sublanes, queries in lanes
            st = lax.dot_general(k, q, NT,
                                 preferred_element_type=jnp.float32) * scale
            if masked:
                st = _causal_mask(st, j * bk, i * bq, rows_are_q=False)
            pt = jnp.exp(st - lse_ref[pl.ds(g, 1), :])
            dv_sc[...] += jnp.dot(pt.astype(do.dtype), do,
                                  preferred_element_type=jnp.float32)
            dpt = lax.dot_general(v, do, NT,
                                  preferred_element_type=jnp.float32)
            dst = pt * (dpt - di_ref[pl.ds(g, 1), :]) * scale
            dk_sc[...] += jnp.dot(dst.astype(q.dtype), q,
                                  preferred_element_type=jnp.float32)

    _when_needed((i * bq + bq - 1) >= (j * bk),
                 (i * bq) >= (j * bk + bk - 1), step)

    @pl.when(i == nq - 1)
    def _store():
        dk_ref[...] = dk_sc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_sc[...].astype(dv_ref.dtype)


def _dkv(q, k, v, do, lse_row, di_row, scale, blocks, interpret):
    B, Hkv, G, T, hd = q.shape
    bq, bk = blocks
    nq, nk = T // bq, T // bk

    def q_index(i, j):
        # below the diagonal: repeat the first Q block needed (no new DMA)
        return jnp.maximum(i, (j * bk) // bq)

    def q_map(b, h, j, i):
        return (b, h, 0, q_index(i, j), 0)

    def row_map(b, h, j, i):
        return (b, h, 0, q_index(i, j))

    def kv_map(b, h, j, i):
        return (b, h, j, 0)

    kernel = functools.partial(_dkv_kernel, scale=scale, bq=bq, bk=bk,
                               groups=G, nq=nq)
    q_spec = pl.BlockSpec((None, None, G, bq, hd), q_map)
    row_spec = pl.BlockSpec((None, None, G, bq), row_map)
    kv_spec = pl.BlockSpec((None, None, bk, hd), kv_map)
    return pl.pallas_call(
        kernel,
        grid=(B, Hkv, nk, nq),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=[kv_spec, kv_spec],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, hd), jnp.float32),
                        pltpu.VMEM((bk, hd), jnp.float32)],
        compiler_params=_params("parallel", "parallel", "parallel",
                                "arbitrary"),
        interpret=interpret,
        name="flash_attention_dkv",
    )(q, k, v, do, lse_row, di_row)


# ---------------------------------------------------------------------------
# custom_vjp
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _attention(q, k, v, scale, blocks, interpret):
    return _fwd(q, k, v, scale, blocks, interpret)[0]


def _attention_fwd(q, k, v, scale, blocks, interpret):
    o, lse = _fwd(q, k, v, scale, blocks, interpret)
    return o, (q, k, v, o, lse)


def _attention_bwd(scale, blocks, interpret, res, do):
    q, k, v, o, lse = res
    di = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)
    dq = _dq(q, k, v, do, lse,
             jnp.broadcast_to(di[..., None], di.shape + (LANES,)),
             scale, blocks, interpret)
    dk, dv = _dkv(q, k, v, do, lse[..., 0], di, scale, blocks, interpret)
    return dq, dk, dv


_attention.defvjp(_attention_fwd, _attention_bwd)


@functools.partial(jax.jit, static_argnames=("scale", "blocks", "interpret"))
def causal_attention(q, k, v, *, scale: float, blocks=None,
                     interpret: bool = False):
    """Causal softmax(q k^T * scale) v with positions 0..T-1.

    q (B, Hq, T, hd), k and v (B, Hkv, T, hd) with Hq a multiple of Hkv;
    returns (B, Hq, T, hd) in q's dtype.  ``blocks`` is (block_q,
    block_kv), by default ``block_sizes(T)``."""
    B, Hq, T, hd = q.shape
    Hkv = k.shape[1]
    blocks = tuple(blocks) if blocks is not None else block_sizes(T)
    qg = q.reshape(B, Hkv, Hq // Hkv, T, hd)
    o = _attention(qg, k, v, float(scale), blocks, interpret)
    return o.reshape(B, Hq, T, hd)
