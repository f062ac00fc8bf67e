"""Config system: model architecture + parallelism + run shapes.

Every assigned architecture gets one ``<id>.py`` in this package exporting
``CONFIG`` (exact paper/model-card numbers, cited) built on these dataclasses.
``ModelConfig.reduced()`` derives the CPU smoke-test variant (2 layers,
d_model<=512, <=4 experts) required by the assignment.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Mapping, Optional


@dataclasses.dataclass(frozen=True)
class ParallelConfig:
    """How one architecture maps onto the production mesh.

    The paper's primary axis is FSDP (ZeRO-3).  Small/medium models use
    *pure FSDP* over every non-pod mesh axis (the paper's main mode); very
    large dense models add TP over ``model``; MoE models add EP over
    ``model``.  ``pod`` defaults to HSDP replication (paper §6.1 sweeps
    2x/4x replication); set ``pod_fsdp=True`` to extend ZeRO-3 across pods.

    The flat schedule knobs below are the *legacy* surface: at runtime init
    they are lowered (bitwise-neutrally) onto a typed
    ``core.policy.PolicySet`` -- a default ``ShardingPolicy`` plus one
    exact-name rule per ``group_schedules`` entry -- and resolved into the
    ``ShardingPlan`` the runtime consumes.  New code should prefer
    ``core.policy.plan(model, mesh, policies)`` with explicit policies
    (or ``policies="auto"`` for the cost-model planner); see DESIGN.md
    §Policy API for the lowering table.
    """

    fsdp_axes: tuple[str, ...] = ("data", "model")  # param-shard axes
    batch_axes: tuple[str, ...] = ("data", "model")  # batch-shard axes
    tp: int = 1           # tensor parallel degree over "model"
    ep: int = 1           # expert parallel degree over "model"
    pod_fsdp: bool = False   # multi-pod: extend FSDP over "pod" (else HSDP)
    sequence_parallel: bool = False  # shard activations over "model" (w/ tp)
    microbatches: int = 1    # gradient accumulation chunks

    # --- communication schedule (core.schedule.CommSchedule) ----------------
    # two-slot double-buffered layer all-gathers: the layer scan runs over
    # pairs, slot i%2 gathers layer i, both slots issue before either
    # layer's compute; gathered buffers never ride the scan carry
    prefetch: bool = False
    reshard_after_forward: bool = True  # drop gathered params after fwd (remat)
    keep_last_gathered: bool = False  # last layer's gathered params stay live
    gather_dtype: Optional[str] = None  # all-gather wire dtype (None=compute)
    # grad reduce-scatter dtype (None=wire).  When set, it also pins the
    # accumulate dtype of the replica gradient psums -- notably the HSDP
    # cross-pod psum in FSDPRuntime._reduce_grads ("fp32" buys exact
    # cross-pod accumulation for 2x reduce bandwidth).  Legacy spelling:
    # lowers bitwise-neutrally onto reduce_wire's cast codecs
    reduce_dtype: Optional[str] = None
    # wire FORMAT of the gradient reduce-scatter (core.wire.WireCodec):
    # None derives a cast codec from reduce_dtype / the gather wire dtype
    # (the legacy path, bit for bit); "fp32"/"bf16" name the cast codec;
    # "q8_block" is the QSDP-style quantized gradient wire -- int8 codes +
    # per-block scales (~4x fewer bytes than fp32) with per-shard
    # error-feedback residuals in the param state tree.  Mutually
    # exclusive with reduce_dtype
    reduce_wire: Optional[str] = None
    # "xla" = lax.all_gather/psum_scatter, overlap left to XLA's
    # latency-hiding scheduler; "ring" = explicit lax.ppermute chunk ring
    # (bitwise identical to xla; issue order visible in the HLO)
    gather_mode: str = "xla"
    # gradient reduce-scatter algorithm: "match" mirrors the gather mode
    # (bitwise identical to XLA's linear-order reduction); "ring_acc" is the
    # accumulate-in-flight ring -- n-1 chunk-hops instead of the order-exact
    # ring's n(n-1)/2, trading bitwise-vs-XLA reproducibility for bandwidth
    reduce_mode: str = "match"
    # storage format of the sharded parameter buffers (core.store.ParamStore):
    # "fp32" (master weights, the default), "bf16" (half-size storage),
    # "q8_block" (block-wise int8 codes + scales alongside an fp32 master
    # shard; the all-gather moves the quantized payload, ~4x fewer wire
    # bytes than fp32).  Per-group overrides go through group_schedules,
    # e.g. {"layers": {"param_store": "q8_block"}}
    param_store: str = "fp32"
    # per-group schedule overrides, group name -> dict over
    # schedule.GROUP_OVERRIDE_KEYS (gather_mode/gather_dtype/reduce_dtype/
    # sharded), e.g. {"globals": {"sharded": False},
    #                 "layers": {"reduce_dtype": "fp32"}} keeps the small
    # globals group replicated (no per-step gather) and fp32-reduces only
    # the layer stack
    group_schedules: Optional[Mapping[str, Mapping[str, Any]]] = None

    def __post_init__(self):
        # TP shards activations over "model", so parameters can't also be
        # ZeRO-sharded over it.  EP is fine: the runtime strips "model" from
        # the expert groups' FSDP axes (experts are Shard(0) over "model").
        # ValueError (not assert): config validation must survive python -O.
        if self.tp > 1 and "model" in self.fsdp_axes:
            raise ValueError(
                f"tp={self.tp} shards activations over 'model'; fsdp_axes "
                f"{self.fsdp_axes} must not ZeRO-shard parameters over it "
                f"too")
        if self.microbatches < 1:
            raise ValueError(
                f"microbatches must be >= 1, got {self.microbatches}")


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    arch_type: str  # dense | moe | ssm | hybrid | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 0  # 0 -> d_model // n_heads

    # --- attention variants -------------------------------------------------
    qkv_bias: bool = False                 # qwen1.5/2.5
    attn_softcap: Optional[float] = None   # gemma2 attn logit softcap
    final_softcap: Optional[float] = None  # gemma2 final logit softcap
    sliding_window: Optional[int] = None
    local_global_alternate: bool = False   # gemma2: alternate local/global
    post_norms: bool = False               # gemma2 post-attn/post-mlp norms

    # --- mlp ----------------------------------------------------------------
    mlp: str = "swiglu"  # swiglu | geglu | squared_relu

    # --- moe ----------------------------------------------------------------
    n_experts: int = 0
    top_k: int = 0
    moe_aux_coef: float = 0.01
    # None: dropless -- every choice runs, experts take uneven groups
    capacity_factor: Optional[float] = 1.25
    # load-balance term E * sum_e P_e * f_e with f_e each expert's share of
    # the choices ("switch") or of the tokens, k times that ("mixtral": hf
    # load_balancing_loss_func)
    moe_aux_form: str = "switch"

    # --- scalar multipliers (granite); None leaves the path as it is -------
    embedding_multiplier: Optional[float] = None  # token embeddings times it
    attention_multiplier: Optional[float] = None  # replaces 1/sqrt(head_dim)
    residual_multiplier: Optional[float] = None   # each block branch times it
    logits_scaling: Optional[float] = None        # LM-head logits over it

    # --- vlm (stub vision frontend: input_specs provides patch embeddings) --
    cross_attn_interval: int = 0  # every k-th layer is a cross-attn layer
    n_patches: int = 1024

    # --- audio / enc-dec (stub audio frontend: frame embeddings) ------------
    encoder_layers: int = 0
    n_frames: int = 1024

    # --- ssm / hybrid --------------------------------------------------------
    ssm_state: int = 0
    conv_kernel: int = 4
    slstm_every: int = 0        # xlstm: every k-th block is sLSTM
    ssm_expand: int = 2

    # --- misc ----------------------------------------------------------------
    attn_chunk: int = 1024  # KV-chunk for online-softmax attention (§Perf)
    ce_chunk: int = 0       # vocab-chunked CE (0 = materialize logits)
    rope_theta: float = 1_000_000.0
    norm_eps: float = 1e-6
    tie_embeddings: bool = False
    source: str = ""  # citation from the assignment table

    # --- parallel + training defaults ---------------------------------------
    parallel: ParallelConfig = ParallelConfig()
    optimizer: str = "adamw"  # adamw | adam8bit | sgd | muon
    quant_block: int = 1024   # flat elements per quant block (32x32 paper blocks)
    learning_rate: float = 3e-4

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def is_encdec(self) -> bool:
        return self.encoder_layers > 0

    def reduced(self) -> "ModelConfig":
        """CPU smoke variant: same family, 2 layers, d_model<=512, <=4 experts."""
        d = min(self.d_model, 256)
        heads = max(2, min(self.n_heads, 4))
        kv = max(1, min(self.n_kv_heads, heads))
        hd = d // heads
        return dataclasses.replace(
            self,
            n_layers=2,
            d_model=d,
            n_heads=heads,
            n_kv_heads=kv,
            head_dim=hd,
            d_ff=min(self.d_ff, 512) if self.d_ff else 0,
            vocab=min(self.vocab, 512),
            n_experts=min(self.n_experts, 4) if self.n_experts else 0,
            top_k=min(self.top_k, 2) if self.top_k else 0,
            sliding_window=min(self.sliding_window, 16)
            if self.sliding_window
            else None,
            cross_attn_interval=2 if self.cross_attn_interval else 0,
            n_patches=8,
            encoder_layers=2 if self.encoder_layers else 0,
            n_frames=16,
            ssm_state=min(self.ssm_state, 8) if self.ssm_state else 0,
            slstm_every=2 if self.slstm_every else 0,
            parallel=ParallelConfig(
                fsdp_axes=("data",), batch_axes=("data",), microbatches=1
            ),
            quant_block=64,
        )


# ---------------------------------------------------------------------------
# Input shapes assigned to this paper
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode


SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524_288, 1, "decode"),
}
