"""Operations and bytes the attention of the two kinds of Laguna layer and
the experts' matmuls need: the two sides of their rooflines. What is
counted is what the result requires, whatever implements it.

Experts (as ``families/lfm2_moe/kernel_costs.py``, under the names the
accepted readers import): every routed pair through its expert's three
matrices once, every touched expert's weights read once. The shared
expert is a plain matmul outside the experts' kernels and is not counted
here.

Sliding-window attention: the keys INSIDE the window and no others, so a
kernel that walks tiles or pages older than the window reads LOW, never
over 100. A prefill: ``heads x 2 x (head + head)`` FLOPs a (row, key) pair
the spans count (``attn_window_key_rows``: sum over live rows of min(t +
1, window)), and q, o of the query heads and k, v of the key-value heads of
every live position read or written once a layer. A decode step: every
row inside a live slot's window read once a layer (``window_tokens`` x
``cache_row_bytes``: K and V of the key-value heads), every query head
taking a dot with the key and adding the value."""
from __future__ import annotations

from typing import Dict, Tuple

from benchmarks.families.lfm2_moe.kernel_costs import (  # noqa: F401
    EXPERT_KERNELS, experts_cost)

WINDOW_PREFILL_KERNELS = ("flash_attention_window_fwd",)
WINDOW_DECODE_KERNELS = ("paged_attention_window_decode",)
FULL_KERNELS = ("flash_attention_fwd", "paged_attention_decode")


def expert_sets(cfg: Dict) -> int:
    """(layer, expert) weight sets the configuration holds: what a step
    could touch at most."""
    return cfg["num_experts"] * sum(
        kind != "dense" for kind in cfg["mlp_layer_types"])


def window_layers(cfg: Dict):
    """Indices of the sliding-window layers."""
    return [i for i, kind in enumerate(cfg["layer_types"])
            if kind == "sliding_attention"]


def _window_heads(cfg: Dict) -> float:
    """Query heads summed over the sliding-window layers."""
    return float(sum(cfg["num_attention_heads_per_layer"][i]
                     for i in window_layers(cfg)))


def window_prefill_cost(cfg: Dict, window_key_rows: float, tokens: float,
                        itemsize: int = 2) -> Tuple[float, float]:
    """(FLOPs, bytes) of one prefill's sliding-window attention, all its
    layers: ``window_key_rows`` the keys its live rows see inside their
    windows, ``tokens`` its live positions."""
    Dh = cfg["head_dim"]
    flops = _window_heads(cfg) * window_key_rows * 2.0 * (Dh + Dh)
    kv = 2.0 * len(window_layers(cfg)) * cfg["num_key_value_heads"]
    nbytes = tokens * (2.0 * _window_heads(cfg) + kv) * Dh * itemsize
    return flops, nbytes


def window_decode_cost(cfg: Dict, window_tokens: float, row_bytes: float
                       ) -> Tuple[float, float]:
    """(FLOPs, bytes) of one decode step's sliding-window attention, all
    its layers: ``window_tokens`` the rows inside the live slots' windows,
    ``row_bytes`` one layer's K and V of one token as the cache lays them
    out."""
    Dh = cfg["head_dim"]
    flops = _window_heads(cfg) * window_tokens * 2.0 * (Dh + Dh)
    return flops, len(window_layers(cfg)) * window_tokens * float(row_bytes)
