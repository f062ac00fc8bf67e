"""Decoder-only transformer LM family: dense (qwen/gemma/nemotron/llama),
MoE (granite/qwen3), and VLM (llama-3.2-vision cross-attn variant).

The model is written against the ParamGetter protocol (repro.core.fsdp):
``pg.globals(group)`` returns gathered+unpacked tensors of an unstacked
group; ``pg.scan(groups, body, carry, xs)`` runs the FSDP layer scan
(per-layer all-gather -> zero-copy unpack -> body, with remat), which is the
ZeRO-3 schedule.  The same code runs on one CPU device (mesh of size 1) and
on the 512-chip multi-pod mesh.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .. import spans
from ..core.ragged import ShardDim, TensorSpec
from . import layers as L
from .moe import ROUTING, moe_ffn


# ---------------------------------------------------------------------------
# Group definitions (consumed by repro.core.fsdp)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class GroupDef:
    """One communication group: a list of FULL logical tensor specs, stacked
    ``n_layers`` times if part of a layer scan, with optional *outer*
    (TP/EP) sharding applied before RaggedShard (paper Fig. 5)."""

    specs: tuple[TensorSpec, ...]
    n_layers: int | None = None
    outer: dict[str, ShardDim] = dataclasses.field(default_factory=dict)
    # grads of a model-axis-replicated group need a psum over "model"
    replicated_over_model: bool = False


def _gran(cfg, shape) -> int:
    """Granularity policy: block-quantized optimizers get quant_block-sized
    blocks on big tensors (the paper's 32x32 case); else element-wise."""
    size = int(np.prod(shape))
    if (
        cfg.optimizer == "adam8bit"
        and len(shape) >= 2
        and size % cfg.quant_block == 0
    ):
        return cfg.quant_block
    return 1


def spec(cfg, name, shape) -> TensorSpec:
    return TensorSpec(name, tuple(shape), granularity=_gran(cfg, shape))


# ---------------------------------------------------------------------------
# Decoder LM
# ---------------------------------------------------------------------------

class DecoderLM:
    def __init__(self, cfg):
        self.cfg = cfg
        self.tp = cfg.parallel.tp
        self.ep = cfg.parallel.ep
        self.is_vlm = cfg.cross_attn_interval > 0
        if self.is_vlm:
            if cfg.n_layers % cfg.cross_attn_interval:
                raise ValueError(
                    f"n_layers={cfg.n_layers} not divisible by "
                    f"cross_attn_interval={cfg.cross_attn_interval}")
            self.n_blocks = cfg.n_layers // cfg.cross_attn_interval
            self.selfs_per_block = cfg.cross_attn_interval - 1
        else:
            self.n_blocks = cfg.n_layers
            self.selfs_per_block = 1
        #: layers whose MLP routes tokens to experts (a VLM's prefixed
        #: self-layers run the dense MLP)
        self.moe_layers = (self.n_blocks if cfg.n_experts and not self.is_vlm
                           else 0)
        #: the train step's metrics carry the routing (``loss``'s report)
        #: of a dropless model; the capacity path reports none yet (the
        #: tiny routed cell of ``tests/chipbench`` pins it so, and reads its
        #: choices from outside through ``routing_report.py``)
        self.reports_routing = (bool(self.moe_layers)
                                and cfg.capacity_factor is None)
        #: the layer scan's remat policy: a routed model keeps its routing
        #: (``moe.ROUTING``) so the recomputed forward routes as the
        #: forward did; None recomputes everything
        self.remat_policy = (
            jax.checkpoint_policies.save_only_these_names(ROUTING)
            if self.moe_layers else None)

    # ---------------- specs ------------------------------------------------
    def _self_layer_specs(self, prefix=""):
        cfg = self.cfg
        D, hd = cfg.d_model, cfg.hd
        Hq, Hkv = cfg.n_heads, cfg.n_kv_heads
        tp = self.tp
        kv_tp = min(tp, Hkv)
        sharded, replicated = [], []
        out_sh: dict[str, ShardDim] = {}

        def add(name, shape, dim=None):
            s = spec(cfg, prefix + name, shape)
            if dim is not None and self.tp > 1:
                sharded.append(s)
                out_sh[s.name] = ShardDim(dim, "model")
            elif self.tp > 1:
                replicated.append(s)
            else:
                sharded.append(s)

        add("ln1", (D,))
        add("wq", (D, Hq * hd), dim=1)
        add("wk", (D, Hkv * hd), dim=1 if kv_tp == tp else None)
        add("wv", (D, Hkv * hd), dim=1 if kv_tp == tp else None)
        if cfg.qkv_bias:
            add("wq_b", (Hq * hd,), dim=0)
            add("wk_b", (Hkv * hd,), dim=0 if kv_tp == tp else None)
            add("wv_b", (Hkv * hd,), dim=0 if kv_tp == tp else None)
        add("wo", (Hq * hd, D), dim=0)
        if cfg.post_norms:
            add("post_ln1", (D,))
        add("ln2", (D,))
        if cfg.n_experts and not prefix:
            # router lives with the (data x model)-FSDP'd group; experts
            # are a separate EP group (see groups())
            add("moe_router", (D, cfg.n_experts))
        else:
            add("w1", (D, cfg.d_ff), dim=1)
            if cfg.mlp in ("swiglu", "geglu"):
                add("w3", (D, cfg.d_ff), dim=1)
            add("w2", (cfg.d_ff, D), dim=0)
        if cfg.post_norms:
            add("post_ln2", (D,))
        return sharded, replicated, out_sh

    def _cross_layer_specs(self):
        cfg = self.cfg
        D, hd = cfg.d_model, cfg.hd
        Hq, Hkv = cfg.n_heads, cfg.n_kv_heads
        tp = self.tp
        kv_tp = min(tp, Hkv)
        sharded, replicated, out_sh = [], [], {}

        def add(name, shape, dim=None):
            s = spec(cfg, name, shape)
            if dim is not None and tp > 1:
                sharded.append(s)
                out_sh[s.name] = ShardDim(dim, "model")
            elif tp > 1:
                replicated.append(s)
            else:
                sharded.append(s)

        add("x_lnq", (D,))
        add("x_wq", (D, Hq * hd), dim=1)
        add("x_wk", (D, Hkv * hd), dim=1 if kv_tp == tp else None)
        add("x_wv", (D, Hkv * hd), dim=1 if kv_tp == tp else None)
        add("x_wo", (Hq * hd, D), dim=0)
        add("x_gate", (1,))
        add("c_ln2", (D,))
        add("c_w1", (D, cfg.d_ff), dim=1)
        if cfg.mlp in ("swiglu", "geglu"):
            add("c_w3", (D, cfg.d_ff), dim=1)
        add("c_w2", (cfg.d_ff, D), dim=0)
        add("c_gate", (1,))
        return sharded, replicated, out_sh

    def groups(self) -> dict[str, GroupDef]:
        cfg = self.cfg
        D, V = cfg.d_model, cfg.vocab
        g: dict[str, GroupDef] = {}

        # --- layer stack ---------------------------------------------------
        sharded, replicated, out_sh = [], [], {}
        for i in range(self.selfs_per_block):
            pre = f"s{i}_" if self.is_vlm else ""
            s, r, o = self._self_layer_specs(pre)
            sharded += s
            replicated += r
            out_sh.update(o)
        if self.is_vlm:
            s, r, o = self._cross_layer_specs()
            sharded += s
            replicated += r
            out_sh.update(o)
        g["layers"] = GroupDef(tuple(sharded), n_layers=self.n_blocks,
                               outer=out_sh)
        if replicated:
            g["layers_rep"] = GroupDef(tuple(replicated),
                                       n_layers=self.n_blocks,
                                       replicated_over_model=True)

        # --- MoE experts (EP outer sharding over "model") --------------------
        if cfg.n_experts:
            E, F = cfg.n_experts, cfg.d_ff
            especs = [
                spec(cfg, "moe_w1", (E, D, F)),
                spec(cfg, "moe_w3", (E, D, F)),
                spec(cfg, "moe_w2", (E, F, D)),
            ]
            eout = (
                {s.name: ShardDim(0, "model") for s in especs}
                if self.ep > 1
                else {}
            )
            g["layers_experts"] = GroupDef(
                tuple(especs), n_layers=self.n_blocks, outer=eout
            )

        # --- globals ---------------------------------------------------------
        gl = [spec(cfg, "emb", (V, D)), spec(cfg, "final_ln", (D,))]
        gout = {}
        if self.tp > 1:
            gout["emb"] = ShardDim(0, "model")
        if not cfg.tie_embeddings:
            gl.append(spec(cfg, "head", (D, V)))
            if self.tp > 1:
                gout["head"] = ShardDim(1, "model")
        g["globals"] = GroupDef(tuple(gl), outer=gout)
        return g

    # ---------------- forward ------------------------------------------------
    def _layer_windows(self):
        """Per-layer attention window (int32 array, big = global), or None
        where no layer has one.  gemma2 alternates local(sliding)/global
        [arXiv:2408.00118]."""
        cfg = self.cfg
        if not cfg.sliding_window:
            return None
        big = np.int32(2**30)
        if cfg.local_global_alternate:
            w = [
                cfg.sliding_window if i % 2 == 0 else big
                for i in range(cfg.n_layers)
            ]
        else:
            w = [cfg.sliding_window] * cfg.n_layers
        return jnp.asarray(w, jnp.int32)

    def _residual(self, x, out):
        """``x + out``, the branch times ``residual_multiplier`` where
        given."""
        if self.cfg.residual_multiplier is not None:
            out = out * self.cfg.residual_multiplier
        return x + out

    def _self_block(self, p, x, q_pos, window, cache=None, cache_index=0,
                    pg=None, prefix="", sp=False):
        """(x, new cache, load-balance term, routing or None)."""
        cfg = self.cfg
        tp_axis = pg.tp_axis if self.tp > 1 else None
        with jax.named_scope(spans.MODEL_ATTN):
            h = L.rms_norm(x, p[prefix + "ln1"], cfg.norm_eps)
            h = L.gather_seq(h, tp_axis, sp)  # SP: gather seq for attention
            attn_cfg = _AttnView(cfg, prefix)
            out, new_cache = L.attention(
                attn_cfg, p, h, q_pos=q_pos, cache=cache,
                cache_index=cache_index, window=window, tp_axis=tp_axis,
                tp=self.tp, prefix=prefix, sp=sp,
            )
            if cfg.post_norms:
                out = L.rms_norm(out, p[prefix + "post_ln1"], cfg.norm_eps)
            x = self._residual(x, out)
        with jax.named_scope(spans.MODEL_MLP):
            h = L.rms_norm(x, p[prefix + "ln2"], cfg.norm_eps)
            if cfg.n_experts and not prefix:
                moe_out, aux, routing = moe_ffn(
                    cfg, p, h,
                    ep_axis=pg.ep_axis if self.ep > 1 else None, ep=self.ep,
                )
                if cfg.post_norms:
                    moe_out = L.rms_norm(moe_out, p[prefix + "post_ln2"],
                                         cfg.norm_eps)
                return self._residual(x, moe_out), new_cache, aux, routing
            h = L.gather_seq(h, tp_axis, sp)
            out = L.mlp(cfg, p, h, tp_axis=tp_axis, prefix=prefix, sp=sp)
            if cfg.post_norms:
                out = L.rms_norm(out, p[prefix + "post_ln2"], cfg.norm_eps)
            return self._residual(x, out), new_cache, 0.0, None

    def _cross_block(self, p, x, memory, pg):
        cfg = self.cfg
        tp_axis = pg.tp_axis if self.tp > 1 else None
        with jax.named_scope(spans.MODEL_ATTN):
            out = L.cross_attention(cfg, p, x, memory, tp_axis=tp_axis,
                                    tp=self.tp)
            x = x + jnp.tanh(p["x_gate"].astype(x.dtype)) * out
        with jax.named_scope(spans.MODEL_MLP):
            h = L.rms_norm(x, p["c_ln2"], cfg.norm_eps)
            out = L.mlp(cfg, p, h, tp_axis=tp_axis, prefix="c_")
            return x + jnp.tanh(p["c_gate"].astype(x.dtype)) * out

    def _scan_groups(self):
        names = ["layers"]
        if self.tp > 1:
            names.append("layers_rep")
        if self.cfg.n_experts:
            names.append("layers_experts")
        return names

    def _backbone(self, pg, x, q_pos, memory=None, caches=None,
                  cache_index=0, sp=False):
        """Run the layer stack.  caches: pytree with leading dim n_blocks.
        Returns (x, load-balance term summed over layers, new caches,
        each layer's routing where the model ``reports_routing``, else
        None)."""
        cfg = self.cfg
        windows = self._layer_windows()
        if windows is not None:
            windows = windows.reshape(
                self.n_blocks, self.selfs_per_block
                if not self.is_vlm else cfg.cross_attn_interval
            )[:, : self.selfs_per_block]

        def body(p, carry, xs):
            x, aux = carry
            win, cache = xs
            new_caches, routing = [], None
            for i in range(self.selfs_per_block):
                pre = f"s{i}_" if self.is_vlm else ""
                c_i = None if cache is None else jax.tree.map(
                    lambda t, i=i: t[i], cache)
                x, nc, a, r = self._self_block(
                    p, x, q_pos, None if win is None else win[i],
                    cache=c_i, cache_index=cache_index,
                    pg=pg, prefix=pre, sp=sp,
                )
                aux = aux + a
                if nc is not None:
                    new_caches.append(nc)
                if r is not None:
                    routing = r
            if self.is_vlm and memory is not None:
                x = self._cross_block(p, x, memory, pg)
            y = (
                jax.tree.map(lambda *ts: jnp.stack(ts), *new_caches)
                if new_caches
                else None
            )
            if self.reports_routing:
                y = (y, routing)
            return (x, aux), y

        xs = (windows, caches)
        (x, aux), ys = pg.scan(self._scan_groups(), body,
                               (x, jnp.float32(0)), xs)
        new_caches, routing = ys if self.reports_routing else (ys, None)
        return x, aux, new_caches, routing

    def _sp_active(self, T: int) -> bool:
        """Sequence parallelism: residual stream seq-sharded over the TP
        axis (Megatron-SP); active for multi-token steps that divide."""
        sp = self.cfg.parallel.sequence_parallel and self.tp > 1
        if sp and self.cfg.n_experts:
            raise ValueError("sequence_parallel with MoE is not supported")
        return sp and T > 1 and T % self.tp == 0

    def _embed_in(self, pg, tokens, sp=False):
        cfg = self.cfg
        g = pg.globals("globals")
        vstart = 0
        tp_axis = pg.tp_axis if self.tp > 1 else None
        with jax.named_scope(spans.MODEL_EMBED):
            if self.tp > 1:
                vstart = L.axis_index(pg.tp_axis) * g["emb"].shape[0]
            x = L.embed(tokens, g["emb"].astype(pg.compute_dtype),
                        tp_axis=None, vocab_start=vstart)
            if cfg.embedding_multiplier is not None:
                x = x * cfg.embedding_multiplier
            if self.tp > 1:
                x = L.reduce_out(x, tp_axis, sp)  # SP: reduce-scatter(seq)
        return x, g, vstart

    def _logits(self, pg, g, x, sp=False):
        cfg = self.cfg
        x = L.gather_seq(x, pg.tp_axis if self.tp > 1 else None, sp)
        x = L.rms_norm(x, g["final_ln"], cfg.norm_eps)
        head = g["emb"].T if cfg.tie_embeddings else g["head"]
        return L.lm_logits(x, head, softcap=cfg.final_softcap,
                           scaling=cfg.logits_scaling)

    # ---------------- public API ----------------------------------------------
    def loss(self, pg, batch):
        """(summed token NLL plus the load-balance term, token weight,
        report): a model that ``reports_routing`` reports ``routing``,
        ``experts`` and ``kept`` each (moe_layers, B * T, k) in the batch's
        token order."""
        cfg = self.cfg
        tokens = batch["tokens"]
        B, T = tokens.shape
        sp = self._sp_active(T)
        x, g, vstart = self._embed_in(pg, tokens, sp=sp)
        memory = batch.get("patches") if self.is_vlm else None
        if memory is not None:
            memory = memory.astype(pg.compute_dtype)
        # q_pos None: the sequence's own index (layers.attention)
        x, aux, _, routing = self._backbone(pg, x, None, memory=memory,
                                            sp=sp)
        report = {} if routing is None else {"routing": routing}
        tp_axis = pg.tp_axis if self.tp > 1 else None
        with jax.named_scope(spans.MODEL_HEAD_LOSS):
            if cfg.ce_chunk:
                # §Perf beyond-paper: vocab-chunked online-logsumexp CE --
                # never materializes the (B, T, V) fp32 logits buffer
                x = L.gather_seq(x, tp_axis, sp)
                x = L.rms_norm(x, g["final_ln"], cfg.norm_eps)
                head = g["emb"].T if cfg.tie_embeddings else g["head"]
                nll, w = L.chunked_ce(
                    x[:, :-1], head.astype(pg.compute_dtype), tokens[:, 1:],
                    jnp.ones((B, T - 1), jnp.float32),
                    vocab_chunk=cfg.ce_chunk, softcap=cfg.final_softcap,
                    tp_axis=tp_axis, vocab_start=vstart,
                    scaling=cfg.logits_scaling,
                )
            else:
                logits = self._logits(pg, g, x, sp=sp)
                nll, w = L.vocab_parallel_ce(
                    logits[:, :-1], tokens[:, 1:],
                    jnp.ones((B, T - 1), jnp.float32),
                    tp_axis=tp_axis, vocab_start=vstart,
                )
            return nll + aux * w / max(cfg.n_layers, 1), w, report

    def cache_window(self, seq_len: int) -> int:
        """Ring-buffer size.  Long-context decode on a sliding-window arch
        caps the cache at the window (the gemma2 long_500k variant: all
        layers windowed -- see DESIGN.md)."""
        cfg = self.cfg
        if cfg.sliding_window and seq_len > 65536:
            return cfg.sliding_window
        return seq_len

    def cache_shapes(self, batch: int, seq_len: int) -> dict[str, Any]:
        """Full (global) KV cache shapes, leading dim = scan blocks.

        With TP > n_kv (replicated-KV GQA), each model rank caches its one
        sliced head: the global head dim is ``tp`` (sharded over "model",
        pairs duplicated -- noted in EXPERIMENTS)."""
        cfg = self.cfg
        W = self.cache_window(seq_len)
        if self.tp > 1 and self.tp <= cfg.n_kv_heads:
            raise ValueError(
                f"replicated-KV cache layout needs tp > n_kv_heads; got "
                f"tp={self.tp}, n_kv_heads={cfg.n_kv_heads}")
        hkv = self.tp if self.tp > 1 else cfg.n_kv_heads
        shape = (self.n_blocks, self.selfs_per_block, batch, hkv, W, cfg.hd)
        return {
            "k": (shape, jnp.bfloat16),
            "v": (shape, jnp.bfloat16),
            "pos": ((self.n_blocks, self.selfs_per_block, batch, W),
                    jnp.int32),
        }

    def cache_batch_dims(self):
        """Batch-dim index per cache leaf (for runtime cache sharding)."""
        return {"k": 2, "v": 2, "pos": 2}

    def init_cache(self, batch: int, seq_len: int):
        out = {}
        for k, (s, d) in self.cache_shapes(batch, seq_len).items():
            out[k] = (jnp.zeros(s, d) if k != "pos"
                      else jnp.full(s, -1, d))
        return out

    def prefill(self, pg, batch, cache):
        cfg = self.cfg
        tokens = batch["tokens"]
        sp = self._sp_active(tokens.shape[1])
        x, g, _ = self._embed_in(pg, tokens, sp=sp)
        memory = batch.get("patches") if self.is_vlm else None
        if memory is not None:
            memory = memory.astype(pg.compute_dtype)
        x, _, new_cache, _ = self._backbone(
            pg, x, None, memory=memory, caches=cache, cache_index=0, sp=sp)
        x = L.gather_seq(x, pg.tp_axis if self.tp > 1 else None, sp)
        logits = self._logits(pg, g, x[:, -1:])
        return logits, new_cache

    def decode(self, pg, batch, cache, index):
        """One token against a filled cache.  index: int32 scalar position,
        or a (B,) vector of per-row positions (continuous batching)."""
        cfg = self.cfg
        tokens = batch["tokens"]  # (B, 1)
        B = tokens.shape[0]
        idx = jnp.asarray(index, jnp.int32)
        q_pos = (idx[:, None] if idx.ndim == 1
                 else jnp.broadcast_to(idx[None, None], (B, 1)))
        index = idx
        x, g, _ = self._embed_in(pg, tokens)
        memory = batch.get("patches") if self.is_vlm else None
        if memory is not None:
            memory = memory.astype(pg.compute_dtype)
        x, _, new_cache, _ = self._backbone(
            pg, x, q_pos, memory=memory, caches=cache, cache_index=index)
        logits = self._logits(pg, g, x)
        return logits, new_cache


class _AttnView:
    """cfg proxy letting prefixed (VLM self-layer) params reuse L.attention."""

    def __init__(self, cfg, prefix):
        self._cfg = cfg
        self._prefix = prefix

    def __getattr__(self, k):
        return getattr(self._cfg, k)
