"""Operations and bytes the attention and the experts' matmuls of a
DeepSeek-V3-family configuration need: the two sides of their rooflines.
What is counted is what the result requires, whatever implements it.

Experts (as ``families/lfm2_moe/kernel_costs.py``, under the names the
accepted readers import): every routed pair through its expert's three
matrices once, every touched expert's weights read once. The shared
expert is a plain matmul outside the experts' kernels and is not counted
here.

Latent attention. A decode step attends in the ABSORBED form over the
cached rows: every live row is read once a layer (``row_bytes`` as the
pool lays it out), and every query head takes a dot with the row's 576
values and adds its 512 latent values into its sum: ``heads x 2 x (576 +
512)`` FLOPs a key. A prefill attends in the EXPANDED form: the causal
half of ``heads x 2 x (192 + 128)`` FLOPs a key (the spans count the keys
each live row sees, so the half is already taken), and reads and writes
each live position's q, k, v, o once a layer."""
from __future__ import annotations

from typing import Dict, Tuple

from benchmarks.families.lfm2_moe.kernel_costs import (  # noqa: F401
    EXPERT_KERNELS, experts_cost)

DECODE_KERNELS = ("paged_attention_latent_decode",)
PREFILL_KERNELS = ("flash_attention_fwd",)


def expert_sets(cfg: Dict) -> int:
    """(layer, expert) weight sets the configuration holds: what a step
    could touch at most."""
    return cfg["n_routed_experts"] * (cfg["num_hidden_layers"]
                                      - cfg["first_k_dense_replace"])


def row_values(cfg: Dict) -> int:
    """Values a cache row carries: the latent and the shared key part."""
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def mla_decode_cost(cfg: Dict, live_rows: float, row_bytes: float
                    ) -> Tuple[float, float]:
    """(FLOPs, bytes) of one decode step's attention over ``live_rows``
    cached rows (summed over the slots), all layers."""
    L, H = cfg["num_hidden_layers"], cfg["num_attention_heads"]
    flops = L * live_rows * H * 2.0 * (row_values(cfg) + cfg["kv_lora_rank"])
    return flops, L * live_rows * float(row_bytes)


def mla_prefill_cost(cfg: Dict, key_rows: float, tokens: float,
                     itemsize: int = 2) -> Tuple[float, float]:
    """(FLOPs, bytes) of one prefill's attention: ``key_rows`` the keys its
    live rows see (sum over rows: the causal half), ``tokens`` its live
    positions; all layers."""
    L, H = cfg["num_hidden_layers"], cfg["num_attention_heads"]
    dq, dv = cfg["qk_head_dim"], cfg["v_head_dim"]
    flops = L * key_rows * H * 2.0 * (dq + dv)
    nbytes = L * tokens * H * (2.0 * dq + 2.0 * dv) * itemsize
    return flops, nbytes
