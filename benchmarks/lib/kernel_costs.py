"""Operations and bytes one call of a named kernel needs, from its shapes:
the two sides of its roofline. What is counted is what the result
requires, not what this implementation does: causal attention at the half
of the score matrix it needs (the rule of ``families/gpt2/flops.py``), no
recomputation, every operand read once and every result written once."""
from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

from benchmarks.lib import peaks

FLASH_FWD = ("flash_attention_fwd",)
FLASH_BWD = ("flash_attention_bwd_dq", "flash_attention_bwd_dkv")


def _attention_matmul_flops(batch: int, heads: int, seq: int, head_dim: int,
                            causal: bool) -> float:
    """One [T, T] x head_dim matmul per head (QK^T, or PV): 2 per
    multiply-add; a causal row i needs i + 1 columns, (T + 1) / 2 a row."""
    context = (seq + 1) / 2.0 if causal else float(seq)
    return 2.0 * batch * heads * seq * context * head_dim


def flash_fwd_cost(batch: int, heads: int, seq: int, head_dim: int,
                   causal: bool = True, itemsize: int = 2) -> Tuple[float, float]:
    """(FLOPs, bytes) of one forward call: QK^T and PV; q, k, v read and
    o written in the compute dtype, one float32 log-sum-exp a row."""
    flops = 2 * _attention_matmul_flops(batch, heads, seq, head_dim, causal)
    rows = batch * heads * seq
    return flops, 4.0 * rows * head_dim * itemsize + 4.0 * rows


def flash_bwd_cost(batch: int, heads: int, seq: int, head_dim: int,
                   causal: bool = True, itemsize: int = 2) -> Tuple[float, float]:
    """(FLOPs, bytes) of the backward pass, the dq and the dk/dv kernel
    together: the four matmuls it needs (dV, dP, dQ, dK: twice the
    forward; recomputing the scores is not counted); q, k, v, do read and
    dq, dk, dv written, the log-sum-exp and the row sums of do * o read."""
    flops = 4 * _attention_matmul_flops(batch, heads, seq, head_dim, causal)
    rows = batch * heads * seq
    return flops, 7.0 * rows * head_dim * itemsize + 2 * 4.0 * rows


def train_roofline_pct(obs: Dict, names: Sequence[str], cost) -> Optional[float]:
    """Share of its roofline that the kernel(s) ``names`` reached in the
    traced part of a training window: the least time the chip could take
    over the calls the trace holds (one a layer a step; the steps in the
    trace are the run's own steps/s times the traced seconds) over the
    device time the trace gives those names. None where the trace has no
    operation of one of the names (a program that does not name them)."""
    tr = obs.get("trace")
    if not tr or any(n not in tr.get("by_op_s", {}) for n in names):
        return None
    cfg, traffic = obs["config"], obs["traffic"]
    flops, nbytes = cost(traffic["batch"], cfg["n_head"], traffic["seq_len"],
                         cfg["n_embd"] // cfg["n_head"])
    calls = cfg["n_layer"] * (obs["rate"] / traffic["batch"]) * tr["window_s"]
    seconds = sum(tr["by_op_s"][n] for n in names)
    return peaks.roofline_pct(calls * flops, calls * nbytes, seconds,
                              obs["device"]["kind"])[0]
