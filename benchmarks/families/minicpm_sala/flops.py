"""Analytic FLOPs of a MiniCPM-SALA configuration as cut: the operations
the forward pass requires (a block-sparse layer at the keys its selection
names plus the compressed keys it is scored against, not at the whole
causal half; a lightning layer at its recurrence, two ``Dh x Dh`` products
a head and token, whatever chunking a kernel adds)."""
from __future__ import annotations

from typing import Dict


def head_flops_per_token(cfg: Dict) -> float:
    """The vocabulary projection's part of ``forward_flops_per_token``: a
    prefill needs it at a prompt's last position only."""
    return 2.0 * cfg["hidden_size"] * cfg["vocab_size"]


def selected_context(cfg: Dict, context: float) -> float:
    """Mean keys a row of a block-sparse layer attends to in a sequence
    whose causal rows see ``context`` on average (a whole sequence of p
    positions has context (p + 1) / 2): every key while t < dense_len,
    then ``topk`` blocks of which its own is half full on average."""
    sel = cfg["assumed"]["sparse_config"]
    p = 2.0 * context - 1.0
    dense = float(sel["dense_len"])
    if p <= dense:
        return context
    chosen = sel["topk"] * sel["block"] - sel["block"] / 2.0
    return (dense * (dense + 1) / 2.0 + (p - dense) * chosen) / p


def layer_flops_per_token(cfg: Dict, i: int, context: float) -> float:
    """Matmul FLOPs of one token through layer ``i`` in a sequence whose
    causal rows see ``context`` positions on average: 2 per multiply-add."""
    d = cfg["hidden_size"]
    ffn = 2.0 * 3 * d * cfg["intermediate_size"]
    if cfg["mixer_types"][i] == "minicpm4":
        H, Hkv, Dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                      cfg["head_dim"])
        proj = 3 * d * H * Dh + 2 * d * Hkv * Dh      # q, gate, out; k, v
        stride = cfg["assumed"]["sparse_config"]["stride"]
        mix = 2.0 * H * 2 * Dh * selected_context(cfg, context) \
            + 2.0 * H * Dh * context / stride
    else:
        H, Dh = cfg["lightning_nh"], cfg["lightning_head_dim"]
        proj = 5 * d * H * Dh                         # q, k, v, gate, out
        mix = 2.0 * H * 2 * Dh * Dh
    return 2.0 * proj + mix + ffn


def forward_flops_per_token(cfg: Dict, context: float) -> float:
    return head_flops_per_token(cfg) + sum(
        layer_flops_per_token(cfg, i, context)
        for i in range(cfg["num_hidden_layers"]))
