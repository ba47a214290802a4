"""Analytic FLOPs of a Laguna-family configuration as cut: the operations
the forward pass requires (causal attention counted at the half it needs,
a sliding-window layer at the keys inside its window and no more, the
``num_experts_per_tok`` active experts of an expert layer and not all it
holds, the shared expert on every token)."""
from __future__ import annotations

from typing import Dict


def head_flops_per_token(cfg: Dict) -> float:
    """The vocabulary projection's part of ``forward_flops_per_token``: a
    prefill needs it at a prompt's last position only."""
    return 2.0 * cfg["hidden_size"] * cfg["vocab_size"]


def window_context(window: int, context: float) -> float:
    """Mean keys a row of a sliding-window layer sees in a sequence whose
    causal rows see ``context`` on average (a whole sequence of p positions
    has context (p + 1) / 2): row t sees min(t + 1, window)."""
    p = 2.0 * context - 1.0
    if p <= window:
        return context
    return (window * (window + 1) / 2.0 + (p - window) * window) / p


def layer_flops_per_token(cfg: Dict, i: int, context: float) -> float:
    """Matmul FLOPs of one token through layer ``i`` attending to
    ``context`` positions where the layer keeps them all: 2 per
    multiply-add."""
    d, Dh = cfg["hidden_size"], cfg["head_dim"]
    H, Hkv = cfg["num_attention_heads_per_layer"][i], cfg["num_key_value_heads"]
    if cfg["layer_types"][i] == "sliding_attention":
        context = window_context(cfg["sliding_window"], context)
    proj = d * H * Dh + 2 * d * Hkv * Dh + H * Dh * d \
        + (d * H if cfg["gating"] else 0)
    attn = 2.0 * proj + 2.0 * H * 2 * Dh * context
    if cfg["mlp_layer_types"][i] == "dense":
        ffn = 2.0 * 3 * d * cfg["intermediate_size"]
    else:
        ffn = (2.0 * d * cfg["num_experts"]
               + 2.0 * 3 * d * (cfg["moe_intermediate_size"]
                                * cfg["num_experts_per_tok"]
                                + cfg["shared_expert_intermediate_size"]))
    return attn + ffn


def forward_flops_per_token(cfg: Dict, context: float) -> float:
    return head_flops_per_token(cfg) + sum(
        layer_flops_per_token(cfg, i, context)
        for i in range(cfg["num_hidden_layers"]))
