"""Analytic FLOPs of a DeepSeek-V3-family configuration as cut: the
operations the forward pass requires in its expanded form (causal
attention counted at the half it needs over scores of ``qk_head_dim`` and
values of ``v_head_dim``, the ``num_experts_per_tok`` active experts of an
expert layer and not all it holds, the shared expert on every token)."""
from __future__ import annotations

from typing import Dict


def head_flops_per_token(cfg: Dict) -> float:
    """The vocabulary projection's part of ``forward_flops_per_token``: a
    prefill needs it at a prompt's last position only."""
    return 2.0 * cfg["hidden_size"] * cfg["vocab_size"]


def layer_flops_per_token(cfg: Dict, i: int, context: float) -> float:
    """Matmul FLOPs of one token through layer ``i`` attending to
    ``context`` positions: 2 per multiply-add."""
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv, r = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"], cfg["kv_lora_rank"])
    proj = d * H * (dn + dr) + d * (r + dr) + r * H * (dn + dv) + H * dv * d
    attn = 2.0 * proj + 2.0 * H * (dn + dr + dv) * context
    if i < cfg["first_k_dense_replace"]:
        ffn = 2.0 * 3 * d * cfg["intermediate_size"]
    else:
        F = cfg["moe_intermediate_size"]
        ffn = (2.0 * d * cfg["n_routed_experts"] + 2.0 * 3 * d * F
               * (cfg["num_experts_per_tok"] + cfg["n_shared_experts"]))
    return attn + ffn


def forward_flops_per_token(cfg: Dict, context: float) -> float:
    return head_flops_per_token(cfg) + sum(
        layer_flops_per_token(cfg, i, context)
        for i in range(cfg["num_hidden_layers"]))
