"""Analytic FLOPs of GPT-2, the operations the forward and backward passes
require (no recomputation; causal attention counted at the half it needs).
The arithmetic of ``bench.py`` ``_tlm_flops``, owned by the benchmark."""
from __future__ import annotations

from typing import Dict


def head_flops_per_token(cfg: Dict) -> float:
    """The vocabulary projection's part of ``forward_flops_per_token``: a
    prefill needs it at a prompt's last position only."""
    return 2.0 * cfg["n_embd"] * cfg["vocab_size"]


def forward_flops_per_token(cfg: Dict, context: float) -> float:
    """Matmul FLOPs of one token's forward pass attending to ``context``
    positions: 2 per multiply-add."""
    d, L = cfg["n_embd"], cfg["n_layer"]
    blocks = 2.0 * L * 12 * d * d
    attention = L * 4 * d * context          # QK^T and PV
    return blocks + head_flops_per_token(cfg) + attention


def train_flops_per_sample(cfg: Dict, seq_len: int) -> float:
    """Forward + backward (3x forward) of one sequence of ``seq_len``;
    a causal position i attends to i + 1 positions: mean (T + 1) / 2."""
    per_token = forward_flops_per_token(cfg, (seq_len + 1) / 2.0)
    return 3.0 * per_token * seq_len
