"""Analytic FLOPs of an LFM2-MoE configuration as cut: the operations the
forward pass requires (causal attention counted at the half it needs, the
``num_experts_per_tok`` active experts of an expert layer and not all it
holds)."""
from __future__ import annotations

from typing import Dict


def head_flops_per_token(cfg: Dict) -> float:
    """The vocabulary projection's part of ``forward_flops_per_token``: a
    prefill needs it at a prompt's last position only."""
    return 2.0 * cfg["hidden_size"] * cfg["vocab_size"]


def layer_flops_per_token(cfg: Dict, i: int, context: float) -> float:
    """Matmul FLOPs of one token through layer ``i`` attending to
    ``context`` positions: 2 per multiply-add."""
    d = cfg["hidden_size"]
    Dh = cfg.get("head_dim") or d // cfg["num_attention_heads"]
    dkv = cfg["num_key_value_heads"] * Dh
    if cfg["layer_types"][i] == "conv":
        mixer = 2.0 * (d * 3 * d + d * d) + 2.0 * d * cfg["conv_L_cache"]
    else:
        mixer = 2.0 * (2 * d * d + 2 * d * dkv) + 4.0 * d * context
    if i < cfg["num_dense_layers"]:
        ffn = 2.0 * 3 * d * cfg["intermediate_size"]
    else:
        ffn = (2.0 * d * cfg["num_experts"] + cfg["num_experts_per_tok"]
               * 2.0 * 3 * d * cfg["moe_intermediate_size"])
    return mixer + ffn


def forward_flops_per_token(cfg: Dict, context: float) -> float:
    return head_flops_per_token(cfg) + sum(
        layer_flops_per_token(cfg, i, context)
        for i in range(len(cfg["layer_types"])))
