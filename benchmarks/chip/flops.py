"""Model FLOPs of a training step: what the forward and backward passes
require, not what the program computes.

Per token, 6x the matmul parameters a token passes through: the
attention projections, the MLP (in a sparse-expert layer the router and
the ``k`` routed experts, no capacity padding) and the LM head; the
embedding lookup is no matmul.  Attention scores and values add
``3 * 2 * T * (H * hd)`` per layer per token at the causal half.
Recomputation (remat) is not counted.
"""
from __future__ import annotations


def matmul_params_per_token(conf: dict) -> int:
    D = conf["hidden_size"]
    H, Hkv = conf["num_attention_heads"], conf["num_key_value_heads"]
    hd = conf.get("head_dim") or D // H
    F = conf["intermediate_size"]
    attn = D * H * hd + 2 * D * Hkv * hd + H * hd * D
    E = conf.get("num_local_experts", 0)
    mlp = D * E + conf["num_experts_per_tok"] * 3 * D * F if E else 3 * D * F
    return conf["num_hidden_layers"] * (attn + mlp) + D * conf["vocab_size"]


def attention_flops_per_token(conf: dict, seq: int) -> int:
    D, H = conf["hidden_size"], conf["num_attention_heads"]
    hd = conf.get("head_dim") or D // H
    return conf["num_hidden_layers"] * 3 * 2 * seq * H * hd


def train_flops_per_token(conf: dict, seq: int) -> int:
    return 6 * matmul_params_per_token(conf) + attention_flops_per_token(
        conf, seq)
