"""Encoder-decoder backbone for SeamlessM4T-medium [arXiv:2308.11596].

Per the assignment carve-out, the audio frontend (mel-spectrogram + conv
feature extractor) is a stub: ``input_specs`` supplies precomputed frame
embeddings (B, n_frames, d_model).  This module implements the transformer
backbone: a bidirectional encoder over frames and a causal decoder with
cross-attention over encoder output.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from . import layers as L
from .transformer import GroupDef, spec


class EncDecModel:
    def __init__(self, cfg):
        self.cfg = cfg
        self.tp = 1
        self.n_enc = cfg.encoder_layers
        self.n_dec = cfg.n_layers

    # ------------------------------------------------------------------ #
    def _enc_layer_specs(self):
        cfg = self.cfg
        D, hd = cfg.d_model, cfg.hd
        Hq, Hkv = cfg.n_heads, cfg.n_kv_heads
        return tuple(
            spec(cfg, n, s) for n, s in [
                ("ln1", (D,)),
                ("wq", (D, Hq * hd)), ("wk", (D, Hkv * hd)),
                ("wv", (D, Hkv * hd)), ("wo", (Hq * hd, D)),
                ("ln2", (D,)),
                ("w1", (D, cfg.d_ff)), ("w3", (D, cfg.d_ff)),
                ("w2", (cfg.d_ff, D)),
            ]
        )

    def _dec_layer_specs(self):
        cfg = self.cfg
        D, hd = cfg.d_model, cfg.hd
        Hq, Hkv = cfg.n_heads, cfg.n_kv_heads
        names = [
            ("ln1", (D,)),
            ("wq", (D, Hq * hd)), ("wk", (D, Hkv * hd)),
            ("wv", (D, Hkv * hd)), ("wo", (Hq * hd, D)),
            ("x_lnq", (D,)),
            ("x_wq", (D, Hq * hd)), ("x_wk", (D, Hkv * hd)),
            ("x_wv", (D, Hkv * hd)), ("x_wo", (Hq * hd, D)),
            ("ln2", (D,)),
            ("w1", (D, cfg.d_ff)), ("w3", (D, cfg.d_ff)),
            ("w2", (cfg.d_ff, D)),
        ]
        return tuple(spec(cfg, n, s) for n, s in names)

    def groups(self) -> dict[str, GroupDef]:
        cfg = self.cfg
        D = cfg.d_model
        return {
            "enc_layers": GroupDef(self._enc_layer_specs(), n_layers=self.n_enc),
            "dec_layers": GroupDef(self._dec_layer_specs(), n_layers=self.n_dec),
            "globals": GroupDef((
                spec(cfg, "frame_proj", (D, D)),
                spec(cfg, "enc_final_ln", (D,)),
                spec(cfg, "emb", (cfg.vocab, D)),
                spec(cfg, "final_ln", (D,)),
                spec(cfg, "head", (D, cfg.vocab)),
            )),
        }

    # ------------------------------------------------------------------ #
    def _encode(self, pg, frames, g):
        cfg = self.cfg
        x = frames.astype(pg.compute_dtype) @ g["frame_proj"].astype(
            pg.compute_dtype)
        B, F, D = x.shape
        pos = jnp.broadcast_to(jnp.arange(F, dtype=jnp.int32)[None], (B, F))

        def body(p, carry, _):
            x = carry
            h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
            out, _ = L.attention(cfg, p, h, q_pos=pos, causal=False)
            x = x + out
            h = L.rms_norm(x, p["ln2"], cfg.norm_eps)
            x = x + L.mlp(cfg, p, h)
            return x, None

        x, _ = pg.scan(["enc_layers"], body, x, None)
        return L.rms_norm(x, g["enc_final_ln"], cfg.norm_eps)

    def _dec_block(self, p, x, memory, q_pos, cache, cache_index):
        cfg = self.cfg
        h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
        out, new_cache = L.attention(
            cfg, p, h, q_pos=q_pos, cache=cache, cache_index=cache_index)
        x = x + out
        x = x + L.cross_attention(cfg, p, x, memory)
        h = L.rms_norm(x, p["ln2"], cfg.norm_eps)
        x = x + L.mlp(cfg, p, h)
        return x, new_cache

    def _decode_stack(self, pg, x, memory, q_pos, caches=None,
                      cache_index=0):
        def body(p, carry, xs):
            x = carry
            x, nc = self._dec_block(p, x, memory, q_pos, xs, cache_index)
            return x, nc

        return pg.scan(["dec_layers"], body, x, caches)

    # ------------------------------------------------------------------ #
    def loss(self, pg, batch):
        cfg = self.cfg
        tokens = batch["tokens"]
        frames = batch["frames"]
        B, T = tokens.shape
        g = pg.globals("globals")
        memory = self._encode(pg, frames, g)
        q_pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))
        x = L.embed(tokens, g["emb"].astype(pg.compute_dtype))
        x, _ = self._decode_stack(pg, x, memory, q_pos)
        x = L.rms_norm(x, g["final_ln"], cfg.norm_eps)
        logits = L.lm_logits(x, g["head"])
        nll, w = L.vocab_parallel_ce(
            logits[:, :-1], tokens[:, 1:], jnp.ones((B, T - 1), jnp.float32))
        return nll, w, {}

    def cache_shapes(self, batch: int, seq_len: int) -> dict[str, Any]:
        cfg = self.cfg
        return {
            "k": ((self.n_dec, batch, cfg.n_kv_heads, seq_len, cfg.hd),
                  jnp.bfloat16),
            "v": ((self.n_dec, batch, cfg.n_kv_heads, seq_len, cfg.hd),
                  jnp.bfloat16),
            "pos": ((self.n_dec, batch, seq_len), jnp.int32),
            "memory": ((batch, cfg.n_frames, cfg.d_model), jnp.bfloat16),
        }

    def cache_batch_dims(self):
        return {"k": 1, "v": 1, "pos": 1, "memory": 0}

    def init_cache(self, batch: int, seq_len: int):
        out = {}
        for k, (s, d) in self.cache_shapes(batch, seq_len).items():
            out[k] = jnp.full(s, -1, d) if k == "pos" else jnp.zeros(s, d)
        return out

    def prefill(self, pg, batch, cache):
        """Encode frames into the cache memory + prefill decoder tokens."""
        cfg = self.cfg
        tokens = batch["tokens"]
        B, T = tokens.shape
        g = pg.globals("globals")
        memory = self._encode(pg, batch["frames"], g)
        q_pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))
        x = L.embed(tokens, g["emb"].astype(pg.compute_dtype))
        kv = {k: cache[k] for k in ("k", "v", "pos")}
        x, nc = self._decode_stack(pg, x, memory, q_pos, caches=kv,
                                   cache_index=0)
        x = L.rms_norm(x[:, -1:], g["final_ln"], cfg.norm_eps)
        nc["memory"] = memory.astype(jnp.bfloat16)
        return L.lm_logits(x, g["head"]), nc

    def decode(self, pg, batch, cache, index):
        cfg = self.cfg
        tokens = batch["tokens"]
        B = tokens.shape[0]
        g = pg.globals("globals")
        memory = cache["memory"].astype(pg.compute_dtype)
        idx = jnp.asarray(index, jnp.int32)
        q_pos = (idx[:, None] if idx.ndim == 1
                 else jnp.broadcast_to(idx[None, None], (B, 1)))
        index = idx
        x = L.embed(tokens, g["emb"].astype(pg.compute_dtype))
        kv = {k: cache[k] for k in ("k", "v", "pos")}
        x, nc = self._decode_stack(pg, x, memory, q_pos, caches=kv,
                                   cache_index=index)
        x = L.rms_norm(x, g["final_ln"], cfg.norm_eps)
        nc["memory"] = cache["memory"]
        return L.lm_logits(x, g["head"]), nc
