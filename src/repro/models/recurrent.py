"""Recurrent-family models: xLSTM (sLSTM + mLSTM stacks) and Hymba
(parallel attention + Mamba heads per layer).

Both have O(1)-state decode, which is what makes the ``long_500k`` shape
runnable (see DESIGN.md §Arch-applicability).  The paper's FSDP technique is
fully applicable: their parameter trees are ragged-packed like any other.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..core.ragged import TensorSpec
from . import layers as L
from .ssm import (
    mamba_mix, mamba_param_shapes, mlstm_mix, mlstm_param_shapes,
    slstm_mix, slstm_param_shapes,
)
from .transformer import GroupDef, spec


class XLSTMModel:
    """xLSTM-125m [arXiv:2405.04517]: super-blocks of (slstm_every-1) mLSTM
    blocks followed by one sLSTM block, scanned over.  Stabilized sigmoid
    gating replaces the paper's exponential gating (DESIGN.md)."""

    def __init__(self, cfg):
        self.cfg = cfg
        k = cfg.slstm_every or cfg.n_layers
        if cfg.n_layers % k:
            raise ValueError(
                f"n_layers={cfg.n_layers} not divisible by "
                f"slstm_every={k}")
        self.per_block = k
        self.n_blocks = cfg.n_layers // k
        self.tp = 1

    def groups(self) -> dict[str, GroupDef]:
        cfg = self.cfg
        D = cfg.d_model
        specs = []
        for i in range(self.per_block - 1):
            specs.append(spec(cfg, f"m{i}_ln", (D,)))
            for n, s in mlstm_param_shapes(cfg, D, prefix=f"m{i}_").items():
                specs.append(spec(cfg, n, s))
        specs.append(spec(cfg, "s_ln", (D,)))
        for n, s in slstm_param_shapes(cfg, D, prefix="s_").items():
            specs.append(spec(cfg, n, s))
        g = {
            "layers": GroupDef(tuple(specs), n_layers=self.n_blocks),
            "globals": GroupDef((
                spec(cfg, "emb", (cfg.vocab, D)),
                spec(cfg, "final_ln", (D,)),
                spec(cfg, "head", (D, cfg.vocab)),
            )),
        }
        return g

    # ------------------------------------------------------------------ #
    def _block(self, p, x, states):
        cfg = self.cfg
        new_states = {"m": [], "s": None}
        for i in range(self.per_block - 1):
            st = None if states is None else jax.tree.map(
                lambda t, i=i: t[i], states["m"])
            h = L.rms_norm(x, p[f"m{i}_ln"], cfg.norm_eps)
            out, ns = mlstm_mix(cfg, p, h, state=st, prefix=f"m{i}_")
            x = x + out
            new_states["m"].append(ns)
        st = None if states is None else states["s"]
        h = L.rms_norm(x, p["s_ln"], cfg.norm_eps)
        out, ns = slstm_mix(cfg, p, h, state=st, prefix="s_")
        x = x + out
        new_states["s"] = ns
        new_states["m"] = jax.tree.map(lambda *ts: jnp.stack(ts),
                                       *new_states["m"])
        return x, new_states

    def _backbone(self, pg, x, states=None):
        def body(p, carry, xs):
            x = carry
            x, ns = self._block(p, x, xs)
            return x, ns

        x, new_states = pg.scan(["layers"], body, x, states)
        return x, new_states

    def loss(self, pg, batch):
        cfg = self.cfg
        tokens = batch["tokens"]
        B, T = tokens.shape
        g = pg.globals("globals")
        x = L.embed(tokens, g["emb"].astype(pg.compute_dtype))
        x, _ = self._backbone(pg, x)
        x = L.rms_norm(x, g["final_ln"], cfg.norm_eps)
        logits = L.lm_logits(x, g["head"])
        nll, w = L.vocab_parallel_ce(
            logits[:, :-1], tokens[:, 1:], jnp.ones((B, T - 1), jnp.float32))
        return nll, w, {}

    def cache_shapes(self, batch: int, seq_len: int) -> dict[str, Any]:
        cfg = self.cfg
        H = cfg.n_heads
        hd = cfg.d_model // H
        nm = self.per_block - 1
        return {
            "m": {
                "C": ((self.n_blocks, nm, batch, H, hd, hd), jnp.float32),
                "n": ((self.n_blocks, nm, batch, H, hd), jnp.float32),
            },
            "s": {
                "c": ((self.n_blocks, batch, H, hd), jnp.float32),
                "n": ((self.n_blocks, batch, H, hd), jnp.float32),
                "m": ((self.n_blocks, batch, H, hd), jnp.float32),
            },
        }

    def cache_batch_dims(self):
        return {"m": {"C": 2, "n": 2},
                "s": {"c": 1, "n": 1, "m": 1}}

    def init_cache(self, batch: int, seq_len: int):
        def mk(path_key, s, d):
            init = -1e30 if path_key == ("s", "m") else 0.0
            return jnp.full(s, init, d)

        shapes = self.cache_shapes(batch, seq_len)
        return {
            grp: {k: mk((grp, k), s, d) for k, (s, d) in sub.items()}
            for grp, sub in shapes.items()
        }

    def prefill(self, pg, batch, cache):
        cfg = self.cfg
        tokens = batch["tokens"]
        g = pg.globals("globals")
        x = L.embed(tokens, g["emb"].astype(pg.compute_dtype))
        x, new_states = self._backbone(pg, x, cache)
        x = L.rms_norm(x[:, -1:], g["final_ln"], cfg.norm_eps)
        return L.lm_logits(x, g["head"]), new_states

    def decode(self, pg, batch, cache, index):
        return self.prefill(pg, batch, cache)


class HymbaModel:
    """Hymba-1.5B [arXiv:2411.13676]: each layer runs attention and a Mamba
    head in parallel on the same input; outputs are normed and averaged.
    Sliding-window attention everywhere except 3 global layers (first,
    middle, last).  Meta-tokens are omitted (DESIGN.md)."""

    def __init__(self, cfg):
        self.cfg = cfg
        self.n_blocks = cfg.n_layers
        self.tp = 1
        self.d_inner = cfg.n_heads * cfg.hd

    def groups(self) -> dict[str, GroupDef]:
        cfg = self.cfg
        D, hd = cfg.d_model, cfg.hd
        Hq, Hkv = cfg.n_heads, cfg.n_kv_heads
        specs = [
            spec(cfg, "ln1", (D,)),
            spec(cfg, "wq", (D, Hq * hd)),
            spec(cfg, "wk", (D, Hkv * hd)),
            spec(cfg, "wv", (D, Hkv * hd)),
            spec(cfg, "wo", (Hq * hd, D)),
            spec(cfg, "attn_n", (Hq * hd,)),
            spec(cfg, "ssm_n", (self.d_inner,)),
            spec(cfg, "ln2", (D,)),
            spec(cfg, "w1", (D, cfg.d_ff)),
            spec(cfg, "w3", (D, cfg.d_ff)),
            spec(cfg, "w2", (cfg.d_ff, D)),
        ]
        for n, s in mamba_param_shapes(cfg, D, d_inner=self.d_inner).items():
            specs.append(spec(cfg, n, s))
        return {
            "layers": GroupDef(tuple(specs), n_layers=self.n_blocks),
            "globals": GroupDef((
                spec(cfg, "emb", (cfg.vocab, D)),
                spec(cfg, "final_ln", (D,)),
                spec(cfg, "head", (D, cfg.vocab)),
            )),
        }

    def _layer_windows(self):
        cfg = self.cfg
        big = np.int32(2**30)
        w = np.full(cfg.n_layers, cfg.sliding_window or big, np.int32)
        for i in (0, cfg.n_layers // 2, cfg.n_layers - 1):
            w[i] = big
        return jnp.asarray(w)

    def _block(self, p, x, q_pos, window, cache, cache_index, pg):
        cfg = self.cfg
        h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
        attn_cache = None if cache is None else cache["attn"]
        ssm_state = None if cache is None else cache["ssm"]
        # attention branch (wo applied after fusing with ssm branch)
        attn_out, new_attn = self._attn_branch(
            p, h, q_pos, window, attn_cache, cache_index)
        ssm_out, new_ssm = mamba_mix(cfg, p, h, state=ssm_state,
                                     d_inner=self.d_inner)
        fused = 0.5 * (
            L.rms_norm(attn_out, p["attn_n"], cfg.norm_eps)
            + L.rms_norm(ssm_out, p["ssm_n"], cfg.norm_eps)
        )
        x = x + fused @ p["wo"].astype(x.dtype)
        h = L.rms_norm(x, p["ln2"], cfg.norm_eps)
        up = jax.nn.silu(h @ p["w1"].astype(x.dtype)) * (h @ p["w3"].astype(x.dtype))
        x = x + up @ p["w2"].astype(x.dtype)
        new_cache = (
            None if cache is None else {"attn": new_attn, "ssm": new_ssm}
        )
        return x, new_cache

    def _attn_branch(self, p, h, q_pos, window, cache, cache_index):
        """Attention without the output projection (fused later); the Mamba
        out_proj is likewise an identity-sized map into the fused space."""
        cfg = self.cfg
        B, T, D = h.shape
        hd = cfg.hd
        Hq, Hkv = cfg.n_heads, cfg.n_kv_heads

        def proj(name, hh):
            return (h @ p[name].astype(h.dtype)).reshape(
                B, T, hh, hd).transpose(0, 2, 1, 3)

        q = L.rope(proj("wq", Hq), q_pos, cfg.rope_theta)
        k = L.rope(proj("wk", Hkv), q_pos, cfg.rope_theta)
        v = proj("wv", Hkv)
        if cache is None:
            out = L.chunked_attention(q, k, v, q_pos=q_pos, kv_pos=q_pos,
                                      window=window)
            new_cache = None
        else:
            W = cache["k"].shape[2]
            idx = jnp.asarray(cache_index, jnp.int32)
            slot = idx % W
            if idx.ndim == 1:  # per-row positions (continuous batching)
                ck = jax.vmap(lambda c, kn, s: jax.lax.dynamic_update_slice(
                    c, kn, (0, s, 0)))(cache["k"], k.astype(cache["k"].dtype),
                                       slot)
                cv = jax.vmap(lambda c, vn, s: jax.lax.dynamic_update_slice(
                    c, vn, (0, s, 0)))(cache["v"], v.astype(cache["v"].dtype),
                                       slot)
                cpos = jax.vmap(lambda c, p, s: jax.lax.dynamic_update_slice(
                    c, p, (s,)))(cache["pos"], q_pos[:, :T].astype(jnp.int32),
                                 slot)
            else:
                ck = jax.lax.dynamic_update_slice(
                    cache["k"], k.astype(cache["k"].dtype), (0, 0, slot, 0))
                cv = jax.lax.dynamic_update_slice(
                    cache["v"], v.astype(cache["v"].dtype), (0, 0, slot, 0))
                cpos = jax.lax.dynamic_update_slice(
                    cache["pos"], q_pos[:, :T].astype(jnp.int32), (0, slot))
            out = L.chunked_attention(q, ck, cv, q_pos=q_pos, kv_pos=cpos,
                                      kv_valid=cpos >= 0, window=window)
            new_cache = {"k": ck, "v": cv, "pos": cpos}
        return out.transpose(0, 2, 1, 3).reshape(B, T, Hq * hd), new_cache

    def _backbone(self, pg, x, q_pos, caches=None, cache_index=0):
        windows = self._layer_windows()

        def body(p, carry, xs):
            x = carry
            win, cache = xs
            x, nc = self._block(p, x, q_pos, win, cache, cache_index, pg)
            return x, nc

        return pg.scan(["layers"], body, x, (windows, caches))

    def loss(self, pg, batch):
        cfg = self.cfg
        tokens = batch["tokens"]
        B, T = tokens.shape
        q_pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))
        g = pg.globals("globals")
        x = L.embed(tokens, g["emb"].astype(pg.compute_dtype))
        x, _ = self._backbone(pg, x, q_pos)
        x = L.rms_norm(x, g["final_ln"], cfg.norm_eps)
        logits = L.lm_logits(x, g["head"])
        nll, w = L.vocab_parallel_ce(
            logits[:, :-1], tokens[:, 1:], jnp.ones((B, T - 1), jnp.float32))
        return nll, w, {}

    def cache_window(self, seq_len: int) -> int:
        if self.cfg.sliding_window and seq_len > 65536:
            return self.cfg.sliding_window
        return seq_len

    def cache_shapes(self, batch: int, seq_len: int) -> dict[str, Any]:
        cfg = self.cfg
        W = self.cache_window(seq_len)
        N = cfg.ssm_state
        K = cfg.conv_kernel
        Lb = self.n_blocks
        return {
            "attn": {
                "k": ((Lb, batch, cfg.n_kv_heads, W, cfg.hd), jnp.bfloat16),
                "v": ((Lb, batch, cfg.n_kv_heads, W, cfg.hd), jnp.bfloat16),
                "pos": ((Lb, batch, W), jnp.int32),
            },
            "ssm": {
                "conv": ((Lb, batch, K - 1, self.d_inner), jnp.bfloat16),
                "ssm": ((Lb, batch, self.d_inner, N), jnp.float32),
            },
        }

    def cache_batch_dims(self):
        return {"attn": {"k": 1, "v": 1, "pos": 1},
                "ssm": {"conv": 1, "ssm": 1}}

    def init_cache(self, batch: int, seq_len: int):
        out = {}
        for grp, sub in self.cache_shapes(batch, seq_len).items():
            out[grp] = {
                k: (jnp.full(s, -1, d) if k == "pos" else jnp.zeros(s, d))
                for k, (s, d) in sub.items()
            }
        return out

    def prefill(self, pg, batch, cache):
        cfg = self.cfg
        tokens = batch["tokens"]
        B, T = tokens.shape
        q_pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))
        g = pg.globals("globals")
        x = L.embed(tokens, g["emb"].astype(pg.compute_dtype))
        x, nc = self._backbone(pg, x, q_pos, caches=cache, cache_index=0)
        x = L.rms_norm(x[:, -1:], g["final_ln"], cfg.norm_eps)
        return L.lm_logits(x, g["head"]), nc

    def decode(self, pg, batch, cache, index):
        cfg = self.cfg
        tokens = batch["tokens"]
        B = tokens.shape[0]
        idx = jnp.asarray(index, jnp.int32)
        q_pos = (idx[:, None] if idx.ndim == 1
                 else jnp.broadcast_to(idx[None, None], (B, 1)))
        g = pg.globals("globals")
        x = L.embed(tokens, g["emb"].astype(pg.compute_dtype))
        x, nc = self._backbone(pg, x, q_pos, caches=cache, cache_index=idx)
        x = L.rms_norm(x, g["final_ln"], cfg.norm_eps)
        return L.lm_logits(x, g["head"]), nc
