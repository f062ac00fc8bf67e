"""Token traffic: a seeded pool of distinct training batches.

The distribution is the one ``repro.data.pipeline.SyntheticStream`` draws
(Zipf unigram plus a Markov successor band), computed for a whole pool at
once instead of one Python step per position: position ``t`` either
resets to a fresh unigram draw or follows ``f(x) = (a*x + b) % vocab``
from the previous token, so its token is ``f^n(x_r)``, where ``r`` is
the last reset and ``n = t - r``.  ``f^n(x) = A_n*x + B_n (mod vocab)``
with ``A_n = a^n`` and ``B_n = b*(1 + a + ... + a^(n-1))``.
"""
from __future__ import annotations

import json
import pathlib

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent


def load(name: str) -> dict:
    return json.loads((HERE / "traffic" / f"{name}.json").read_text())


def _affine_powers(a: int, b: int, vocab: int, n: int):
    """(A, B) with ``f^k(x) = (A[k]*x + B[k]) % vocab`` for k < n."""
    A = np.empty(n, np.int64)
    B = np.empty(n, np.int64)
    A[0], B[0] = 1, 0
    for k in range(1, n):
        A[k] = (A[k - 1] * a) % vocab
        B[k] = (B[k - 1] * a + b) % vocab
    return A, B


def token_pool(vocab: int, seq: int, rows: int, seed: int,
               order_mix: float = 0.7) -> np.ndarray:
    """``(rows, seq)`` int32 tokens drawn from ``seed``."""
    rng = np.random.default_rng(seed)
    a = int(rng.integers(3, 97)) * 2 + 1
    b = int(rng.integers(0, vocab))
    ranks = np.arange(1, vocab + 1, dtype=np.float64)
    cdf = np.cumsum(1.0 / ranks)
    cdf /= cdf[-1]
    iid = np.minimum(np.searchsorted(cdf, rng.random((rows, seq))),
                     vocab - 1).astype(np.int64)
    follow = rng.random((rows, seq)) < order_mix
    follow[:, 0] = False
    pos = np.arange(seq)
    last_reset = np.maximum.accumulate(np.where(follow, 0, pos[None]), axis=1)
    A, B = _affine_powers(a, b, vocab, seq)
    n = pos[None] - last_reset
    start = np.take_along_axis(iid, last_reset, axis=1)
    return ((A[n] * start + B[n]) % vocab).astype(np.int32)


def batch_pool(traffic: dict, vocab: int, chips: int, seed: int) -> np.ndarray:
    """``(pool, global_batch, seq)`` int32: ``traffic["pool"]`` distinct
    global batches of ``batch_per_chip * chips`` rows."""
    rows = traffic["batch_per_chip"] * chips
    toks = token_pool(vocab, traffic["seq"], traffic["pool"] * rows, seed,
                      traffic.get("order_mix", 0.7))
    return toks.reshape(traffic["pool"], rows, traffic["seq"])
