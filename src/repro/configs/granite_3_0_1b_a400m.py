"""Granite-3.0-1B-A400M [hf:ibm-granite/granite-3.0-1b-a400m-base], as
published (``model_type: granitemoe``): 24L, d_model=1024, 16H (GQA kv=8)
of 64, 32 experts top-8 with SwiGLU width 512, dropless routing (hf's
``GraniteMoeParallelExperts`` has no capacity), embeddings times 12,
attention scores times 1/64 in place of 1/sqrt(64), each residual branch
times 0.22, logits over 6, head tied to the embedding, RMSNorm eps 1e-6,
RoPE theta 1e4, vocab=49155.  The load-balance term is hf's
``load_balancing_loss_func`` at ``router_aux_loss_coef`` 0.001 (the
GraniteMoe default, as recalled), taken per layer and averaged over the
layers where hf pools every layer's tokens before the product."""
from .base import ModelConfig, ParallelConfig

CONFIG = ModelConfig(
    name="granite-3.0-1b-a400m",
    arch_type="moe",
    n_layers=24, d_model=1024, n_heads=16, n_kv_heads=8,
    d_ff=512, vocab=49155, head_dim=64,
    n_experts=32, top_k=8, capacity_factor=None,
    moe_aux_coef=0.001, moe_aux_form="mixtral",
    embedding_multiplier=12.0, attention_multiplier=0.015625,
    residual_multiplier=0.22, logits_scaling=6.0,
    rope_theta=1e4, norm_eps=1e-6, tie_embeddings=True,
    source="[hf:ibm-granite/granite-3.0-1b-a400m-base]",
    parallel=ParallelConfig(fsdp_axes=("data", "model"),
                            batch_axes=("data", "model")),
    optimizer="adamw",
)
