"""Operations and bytes the two mechanisms of a MiniCPM-SALA layer need:
the two sides of their kernels' rooflines. What is counted is the WORK the
result requires, from what the program's spans carry, whatever implements
it: a kernel that computes score tiles its lists do not name, or a chunked
form's extra products, reads LOW, never over 100.

Block-sparse attention (``minicpm4`` layers). A prefill: ``heads x 2 x
(head + head)`` FLOPs a (row, key) pair the lists name (the span's
``attn_selected_key_rows``: sum over live rows of the keys in their chosen
blocks up to themselves, one layer's), and q, o of the query heads and k, v
of the key-value heads of every live position read or written once a
layer. A decode step: every row of the chosen pages up to the slot's
position read once a layer (``selected_tokens`` x ``cache_row_bytes``: K
and V of the key-value heads), every query head taking a dot with the key
and adding the value. The scoring against compressed keys and the choice
run in XLA, which a trace does not name apart: they are in neither side of
a roofline. What the trace does show of them is ``SELECTION_OPS``: this
family's programs run no loop on the device but the selection's own (a
prefill's ``ops.sparse_select.chosen_mask`` takes 256 query positions a
pass of a ``lax.map``, and ``ops.kth_largest`` finds the cut in 32 passes
of a ``fori_loop``, in a prefill and in a decode step; the sampler's own
k-th largest sits in a branch no greedy row takes), and a loop's event in
the device's trace spans its whole body, so the ``while`` family's time is
the selection's: all of it in a prefill, the choice's part in a decode
step. ``attn.sparse_busy_pct.tput`` adds it to the kernels' time.

Lightning attention. A prefill: the recurrence's two ``Dh x Dh`` products
a head and token (the least any form needs; the chunked kernel spends
three times that) and q, k, v, o of every live position once a layer:
memory binds. A decode step: every live slot's state of every layer read
once and written once (the span's ``state_bytes`` is one pass over them),
and the same two products."""
from __future__ import annotations

from typing import Dict, Tuple

SPARSE_PREFILL_KERNELS = ("flash_attention_sparse_fwd",)
SPARSE_DECODE_KERNELS = ("paged_attention_sparse_decode",)
SELECTION_OPS = ("while",)
LIGHTNING_PREFILL_KERNELS = ("lightning_attention_fwd",)
LIGHTNING_DECODE_KERNELS = ("lightning_decode",)


def sparse_layers(cfg: Dict) -> int:
    return sum(kind == "minicpm4" for kind in cfg["mixer_types"])


def lightning_layers(cfg: Dict) -> int:
    return sum(kind == "lightning-attn" for kind in cfg["mixer_types"])


def sparse_prefill_cost(cfg: Dict, selected_key_rows: float, tokens: float,
                        itemsize: int = 2) -> Tuple[float, float]:
    """(FLOPs, bytes) of one prefill's block-sparse attention, all its
    layers: ``selected_key_rows`` one layer's (row, key) pairs, ``tokens``
    the live positions."""
    H, Hkv, Dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    n = sparse_layers(cfg)
    flops = n * H * selected_key_rows * 2.0 * (Dh + Dh)
    return flops, n * tokens * (2.0 * H + 2.0 * Hkv) * Dh * itemsize


def sparse_decode_cost(cfg: Dict, selected_tokens: float, row_bytes: float
                       ) -> Tuple[float, float]:
    """(FLOPs, bytes) of one decode step's block-sparse attention, all its
    layers: ``selected_tokens`` the rows the live slots' lists name,
    ``row_bytes`` one layer's K and V of one token as the pages lay them
    out."""
    H, Dh = cfg["num_attention_heads"], cfg["head_dim"]
    n = sparse_layers(cfg)
    return (n * H * selected_tokens * 2.0 * (Dh + Dh),
            n * selected_tokens * float(row_bytes))


def lightning_prefill_cost(cfg: Dict, rows: float, itemsize: int = 2
                           ) -> Tuple[float, float]:
    """(FLOPs, bytes) of one prefill's lightning attention, all its
    layers, over ``rows`` live positions."""
    H, Dh = cfg["lightning_nh"], cfg["lightning_head_dim"]
    n = lightning_layers(cfg)
    return (n * rows * H * 2.0 * 2 * Dh * Dh,
            n * rows * 4.0 * H * Dh * itemsize)


def lightning_decode_cost(cfg: Dict, slots: float, state_bytes: float
                          ) -> Tuple[float, float]:
    """(FLOPs, bytes) of one decode step's lightning attention:
    ``state_bytes`` the live slots' states, all layers, once (they are
    read and written)."""
    H, Dh = cfg["lightning_nh"], cfg["lightning_head_dim"]
    return (lightning_layers(cfg) * slots * H * 2.0 * 2 * Dh * Dh,
            2.0 * float(state_bytes))
