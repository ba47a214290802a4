"""Operations and bytes the experts' matmuls of an LFM2-MoE configuration
need, from what was routed: the two sides of their roofline. What is
counted is what the result requires, whatever implements it: every routed
pair through its expert's three matrices once, every touched expert's
weights read once, every pair's input row read and output row written
once."""
from __future__ import annotations

from typing import Dict, Tuple

EXPERT_KERNELS = ("moe_experts_gate_up", "moe_experts_down")


def expert_sets(cfg: Dict) -> int:
    """(layer, expert) weight sets the configuration holds: what a step
    could touch at most."""
    return cfg["num_experts"] * (len(cfg["layer_types"])
                                 - cfg["num_dense_layers"])


def expert_weight_bytes(cfg: Dict, itemsize: int = 2) -> float:
    return 3.0 * cfg["hidden_size"] * cfg["moe_intermediate_size"] * itemsize


def experts_cost(cfg: Dict, pairs: float, experts_touched: float,
                 itemsize: int = 2) -> Tuple[float, float]:
    """(FLOPs, bytes) of ``pairs`` routed token-expert pairs over
    ``experts_touched`` distinct (layer, expert) weight sets."""
    d, F = cfg["hidden_size"], cfg["moe_intermediate_size"]
    flops = pairs * 2.0 * 3 * d * F
    nbytes = (experts_touched * expert_weight_bytes(cfg, itemsize)
              + pairs * 2.0 * d * itemsize)
    return flops, nbytes
