"""Fused flash-attention Pallas kernels (TPU).

Net-new beyond the reference (its sequence story is LSTM-only — SURVEY.md
§5.7): the O(T) HBM-traffic attention primitive that makes long contexts
first-class. The XLA path (parallel/ring_attention.attention) materializes
the [B,H,T,T] score tensor in HBM; these kernels keep each [BQ,BK] score
block in VMEM with the online-softmax recurrence, so HBM traffic is
O(B*H*T*D) regardless of T.

Design (same helper-probe-with-fallback seam as ops/pallas_lstm.py):
  - forward: grid (B*H, T/BQ, T/BK), k-blocks innermost ("arbitrary"
    semantics) with the (acc, m, l) carry in VMEM scratch; saves the
    logsumexp rows for the backward.
  - backward (FlashAttention-2 style, custom_vjp): one kernel accumulates
    dq over k-blocks, a second accumulates (dk, dv) over q-blocks; softmax
    probabilities are recomputed from the saved logsumexp, never stored.
  - causal blocks strictly above the diagonal are skipped (@pl.when), so
    causal attention does ~half the work.
  - masking uses a large negative (-1e30) everywhere, matching the XLA
    fallback: a fully-masked query row degrades to uniform attention
    instead of NaN.
  - bf16 i/o supported; matmul ACCUMULATION and the online-softmax
    recurrence (s, m, l, lse) are f32; with bf16 inputs the dot operands
    (q/k/v/do and the p/ds tiles) run in bf16 for full MXU rate — the
    standard flash-kernel precision recipe.

lse/delta are carried as [BH, T, 128] lane-replicated f32 (the standard
layout trick: per-row scalars live on all 128 lanes so no sub-tile
transposes are needed).
"""
from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from .kernels import envutil as kenv

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

f32 = jnp.float32
NEG = -1e30


def _kernel_eligible(D: int, dtype) -> bool:
    """The eligibility policy SHARED by every fused-attention probe
    (single-device and ring): not env-disabled, dtype,
    head-dim, and backend rules. Per-probe sequence-length rules layer on
    top."""
    if not kenv.fused_enabled("attention", ("DL4J_TPU_FUSED_ATTENTION",)):
        return False
    dt = jnp.dtype(dtype)
    if dt not in (jnp.float32, jnp.dtype(jnp.bfloat16)):
        return False
    if D % 128 != 0 and D not in (64, 96):
        # D is the lane dimension: multiples of the 128-lane tile are
        # native; 64/96 (GPT-2-class head dims) ride Mosaic's minor-dim
        # padding — the MXU pads the QK^T contraction to 128 either way,
        # so the only cost is padded q/k/v/o tiles in VMEM
        return False
    return kenv.backend_admits("attention", jax.default_backend(),
                               ("DL4J_TPU_FUSED_ATTN_INTERPRET",))


def fused_attention_applicable(B: int, H: int, T: int, D: int, dtype) -> bool:
    """Probe: can the fused kernels handle this call? (helper seam —
    callers fall back to the XLA path when False)."""
    # tiny T isn't worth the pallas_call overhead vs one fused XLA softmax
    return _kernel_eligible(D, dtype) and T % 128 == 0 and T >= 256


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _blocks(T: int) -> tuple:
    """(BQ, BK) block sizes. Resolution order: explicit env override
    (DL4J_TPU_ATTN_BQ / DL4J_TPU_ATTN_BK, for re-tuning sweeps) → a cached
    autotune decision for this (T, backend) from ops/kernels/autotune.py →
    the v5e-sweep defaults (tools/autotune_attention.py; see BASELINE.md's
    attention roofline note — the same preference order won at every head
    dim tried)."""
    def pick(env, pref):
        v = os.environ.get(env)
        if v:
            b = int(v)
            if T % b:
                raise ValueError(f"{env}={b} does not divide T={T}")
            return b
        for b in pref:
            if T % b == 0:
                return b
        raise ValueError(f"T={T} not a multiple of 128")
    # v5e sweep @ T=2048 (B=4,H=8, causal fwd+bwd): BK=1024 beats the old
    # BQ=BK=512 default at every head dim tried (D=128: 2.17 vs 2.75
    # ms/step; D=64: consistently top-2 across repeated sweeps) — bigger
    # k-blocks amortize the online-softmax carry updates and feed the MXU
    # longer contractions. BK=2048 was no better and BQ=1024 failed to
    # compile with it, so 512/1024 is the stable optimum.
    pref_q = (512, 256, 128)
    pref_k = (1024, 512, 256, 128)
    if os.environ.get("DL4J_TPU_ATTN_BQ") is None and \
            os.environ.get("DL4J_TPU_ATTN_BK") is None:
        from .kernels import autotune   # lazy: avoids an import cycle
        cached = autotune.cached_decision("attention", f"T{T}")
        if cached is not None:
            bq, bk = int(cached[0]), int(cached[1])
            if T % bq == 0 and T % bk == 0:
                return bq, bk
    return pick("DL4J_TPU_ATTN_BQ", pref_q), pick("DL4J_TPU_ATTN_BK", pref_k)


def _causal_mask_block(i, j, BQ, BK, s):
    row = i * BQ + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    col = j * BK + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(col <= row, s, NEG)


# ------------------------------------------------------------------ forward
# The kernels' names in the compiled program and in a device trace: the
# HLO instruction is named after the innermost scope around the call, and
# ``pallas_call(name=)`` opens one. A transformation wraps the FIRST scope
# opened under it (``jvp(name)``), so every call also sits in the outer
# scope ``SCOPE``, which takes that wrapping and leaves the name bare.
SCOPE = "flash_attention"
FWD_NAME = "flash_attention_fwd"


def _fwd_body(causal, masked, scale, BQ, BK, *refs):
    if masked:
        q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref, acc, m, l = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m, l = refs
    i = pl.program_id(1)
    j = pl.program_id(2)
    nj = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m[:] = jnp.full_like(m, NEG)
        l[:] = jnp.zeros_like(l)

    compute = True if not causal else (j * BK < (i + 1) * BQ)

    @pl.when(compute)
    def _update():
        # dots take the refs' NATIVE dtype with f32 accumulation: bf16
        # inputs run the MXU at full rate (upcasting first would halve
        # it); the softmax recurrence stays f32 throughout
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=f32) * scale
        if causal:
            s = _causal_mask_block(i, j, BQ, BK, s)
        if masked:
            s = jnp.where(mask_ref[0][0:1, :] > 0, s, NEG)
        m_prev = m[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l[:] = jnp.broadcast_to(l[:, :1] * corr + p.sum(1, keepdims=True),
                                l.shape)
        acc[:] = acc[:] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=f32)
        m[:] = jnp.broadcast_to(m_new, m.shape)

    @pl.when(j == nj - 1)
    def _finalize():
        o_ref[0] = (acc[:] / l[:, :1]).astype(o_ref.dtype)
        lse_ref[0] = m[:] + jnp.log(l[:])


def _fwd(q3, k3, v3, mask2, causal, scale):
    """q3/k3/v3: [BH, T, D]; mask2: [B, T] or None. Returns (o, lse)."""
    BH, T, D = q3.shape
    BQ, BK = _blocks(T)
    grid = (BH, T // BQ, T // BK)
    in_specs = [
        pl.BlockSpec((1, BQ, D), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, BK, D), lambda b, i, j: (b, j, 0)),
        pl.BlockSpec((1, BK, D), lambda b, i, j: (b, j, 0)),
    ]
    args = [q3, k3, v3]
    masked = mask2 is not None
    if masked:
        H = BH // mask2.shape[0]
        in_specs.append(pl.BlockSpec(
            (1, 1, BK), lambda b, i, j: (b // H, 0, j)))
        args.append(mask2[:, None, :].astype(f32))
    out_shape = [jax.ShapeDtypeStruct((BH, T, D), q3.dtype),
                 jax.ShapeDtypeStruct((BH, T, 128), f32)]
    out_specs = [pl.BlockSpec((1, BQ, D), lambda b, i, j: (b, i, 0)),
                 pl.BlockSpec((1, BQ, 128), lambda b, i, j: (b, i, 0))]
    with jax.named_scope(SCOPE):
        o, lse = pl.pallas_call(
            functools.partial(_fwd_body, causal, masked, scale, BQ, BK),
            name=FWD_NAME,
            grid=grid,
            in_specs=in_specs,
            out_specs=out_specs,
            out_shape=out_shape,
            scratch_shapes=[pltpu.VMEM((BQ, D), f32),
                            pltpu.VMEM((BQ, 128), f32),
                            pltpu.VMEM((BQ, 128), f32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=_interpret(),
        )(*args)
    return o, lse


# ------------------------------------------------------------------ dq pass
DQ_NAME = "flash_attention_bwd_dq"


def _dq_body(causal, masked, scale, BQ, BK, *refs):
    if masked:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, mask_ref,
         dq_ref, dq_acc) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dq_ref, dq_acc) = refs
    i = pl.program_id(1)
    j = pl.program_id(2)
    nj = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    compute = True if not causal else (j * BK < (i + 1) * BQ)

    @pl.when(compute)
    def _update():
        # native-dtype dot inputs, f32 accumulation (see _fwd_body)
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=f32) * scale
        if causal:
            s = _causal_mask_block(i, j, BQ, BK, s)
        if masked:
            s = jnp.where(mask_ref[0][0:1, :] > 0, s, NEG)
        p = jnp.exp(s - lse_ref[0][:, :1])
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=f32)
        ds = p * (dp - delta_ref[0][:, :1]) * scale
        dq_acc[:] += jax.lax.dot_general(ds.astype(k.dtype), k,
                                         (((1,), (0,)), ((), ())),
                                         preferred_element_type=f32)

    @pl.when(j == nj - 1)
    def _finalize():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


# ---------------------------------------------------------------- dkv pass
DKV_NAME = "flash_attention_bwd_dkv"


def _dkv_body(causal, masked, scale, BQ, BK, *refs):
    if masked:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, mask_ref,
         dk_ref, dv_ref, dk_acc, dv_acc) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_acc, dv_acc) = refs
    jk = pl.program_id(1)          # k-block (outer)
    i = pl.program_id(2)           # q-block (inner, "arbitrary")
    ni = pl.num_programs(2)

    @pl.when(i == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    compute = True if not causal else ((i + 1) * BQ > jk * BK)

    @pl.when(compute)
    def _update():
        # native-dtype dot inputs, f32 accumulation (see _fwd_body)
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=f32) * scale
        if causal:
            s = _causal_mask_block(i, jk, BQ, BK, s)
        if masked:
            s = jnp.where(mask_ref[0][0:1, :] > 0, s, NEG)
        p = jnp.exp(s - lse_ref[0][:, :1])                    # [BQ, BK]
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=f32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=f32)
        ds = p * (dp - delta_ref[0][:, :1]) * scale
        dk_acc[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=f32)

    @pl.when(i == ni - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd(q3, k3, v3, mask2, causal, scale, o3, lse, do3):
    BH, T, D = q3.shape
    BQ, BK = _blocks(T)
    masked = mask2 is not None
    # delta = rowsum(dO * O), lane-replicated like lse
    delta = jnp.sum(do3.astype(f32) * o3.astype(f32), axis=-1)
    delta = jnp.broadcast_to(delta[..., None], (BH, T, 128))

    common_args = [q3, k3, v3, do3, lse, delta]
    qspec = pl.BlockSpec((1, BQ, D), lambda b, x, y: (b, x, 0))

    def q_side(which):
        # index maps for the dq grid (b, i, j): q-indexed rows use i
        return {
            "q": pl.BlockSpec((1, BQ, D), lambda b, i, j: (b, i, 0)),
            "k": pl.BlockSpec((1, BK, D), lambda b, i, j: (b, j, 0)),
            "v": pl.BlockSpec((1, BK, D), lambda b, i, j: (b, j, 0)),
            "do": pl.BlockSpec((1, BQ, D), lambda b, i, j: (b, i, 0)),
            "lse": pl.BlockSpec((1, BQ, 128), lambda b, i, j: (b, i, 0)),
            "delta": pl.BlockSpec((1, BQ, 128), lambda b, i, j: (b, i, 0)),
        }[which]

    in_specs = [q_side(n) for n in ("q", "k", "v", "do", "lse", "delta")]
    args = list(common_args)
    if masked:
        H = BH // mask2.shape[0]
        in_specs.append(pl.BlockSpec(
            (1, 1, BK), lambda b, i, j: (b // H, 0, j)))
        args.append(mask2[:, None, :].astype(f32))
    with jax.named_scope(SCOPE):
        dq = pl.pallas_call(
            functools.partial(_dq_body, causal, masked, scale, BQ, BK),
            name=DQ_NAME,
            grid=(BH, T // BQ, T // BK),
            in_specs=in_specs,
            out_specs=[qspec],
            out_shape=[jax.ShapeDtypeStruct((BH, T, D), q3.dtype)],
            scratch_shapes=[pltpu.VMEM((BQ, D), f32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=_interpret(),
        )(*args)[0]

    # dkv grid is (b, jk, i): q-indexed rows use the INNER index i
    def kv_side(which):
        return {
            "q": pl.BlockSpec((1, BQ, D), lambda b, jk, i: (b, i, 0)),
            "k": pl.BlockSpec((1, BK, D), lambda b, jk, i: (b, jk, 0)),
            "v": pl.BlockSpec((1, BK, D), lambda b, jk, i: (b, jk, 0)),
            "do": pl.BlockSpec((1, BQ, D), lambda b, jk, i: (b, i, 0)),
            "lse": pl.BlockSpec((1, BQ, 128), lambda b, jk, i: (b, i, 0)),
            "delta": pl.BlockSpec((1, BQ, 128), lambda b, jk, i: (b, i, 0)),
        }[which]

    in_specs = [kv_side(n) for n in ("q", "k", "v", "do", "lse", "delta")]
    args = list(common_args)
    if masked:
        H = BH // mask2.shape[0]
        in_specs.append(pl.BlockSpec(
            (1, 1, BK), lambda b, jk, i: (b // H, 0, jk)))
        args.append(mask2[:, None, :].astype(f32))
    kvspec = pl.BlockSpec((1, BK, D), lambda b, jk, i: (b, jk, 0))
    with jax.named_scope(SCOPE):
        dk, dv = pl.pallas_call(
            functools.partial(_dkv_body, causal, masked, scale, BQ, BK),
            name=DKV_NAME,
            grid=(BH, T // BK, T // BQ),
            in_specs=in_specs,
            out_specs=[kvspec, kvspec],
            out_shape=[jax.ShapeDtypeStruct((BH, T, D), k3.dtype),
                       jax.ShapeDtypeStruct((BH, T, D), v3.dtype)],
            scratch_shapes=[pltpu.VMEM((BK, D), f32),
                            pltpu.VMEM((BK, D), f32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=_interpret(),
        )(*args)
    return dq, dk, dv


# ------------------------------------------------- ring-hop carry kernel
FWD_CARRY_NAME = "flash_attention_fwd_carry"


def _fwd_carry_body(causal, scale, BQ, BK, *refs):
    """One ring hop's local block, CARRY-EMITTING: the online-softmax
    state (acc, m, l) enters as kernel inputs and leaves raw (no
    normalize) so the ring can keep folding hops in. Same recurrence as
    _fwd_body; m/l ride the lane-replicated [.,128] layout between hops."""
    (q_ref, k_ref, v_ref, acc_in, m_in, l_in,
     acc_out, m_out, l_out, accs, ms, ls) = refs
    i = pl.program_id(1)
    j = pl.program_id(2)
    nj = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        accs[:] = acc_in[0]
        ms[:] = m_in[0]
        ls[:] = l_in[0]

    compute = True if not causal else (j * BK < (i + 1) * BQ)

    @pl.when(compute)
    def _update():
        # native-dtype dot inputs, f32 accumulation (see _fwd_body)
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=f32) * scale
        if causal:
            s = _causal_mask_block(i, j, BQ, BK, s)
        m_prev = ms[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        ls[:] = jnp.broadcast_to(ls[:, :1] * corr + p.sum(1, keepdims=True),
                                 ls.shape)
        accs[:] = accs[:] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=f32)
        ms[:] = jnp.broadcast_to(m_new, ms.shape)

    @pl.when(j == nj - 1)
    def _finalize():
        acc_out[0] = accs[:]
        m_out[0] = ms[:]
        l_out[0] = ls[:]


def flash_block_update(acc, m, l, q3, k3, v3, *, causal: bool,
                       scale: float):
    """Fused one-hop update for ring attention: fold the local [BH,Tq,D] x
    [BH,Tk,D] block into the running online-softmax carry WITHOUT
    materializing the [Tq,Tk] scores in HBM (the XLA ring body's
    _block_update does — parallel/ring_attention.py). acc [BH,Tq,D] f32;
    m/l lane-replicated [BH,Tq,128] f32. Returns the updated carry, raw
    (caller normalizes after the last hop)."""
    BH, Tq, D = q3.shape
    Tk = k3.shape[1]
    BQ, _ = _blocks(Tq)
    _, BK = _blocks(Tk)
    grid = (BH, Tq // BQ, Tk // BK)
    qspec = pl.BlockSpec((1, BQ, D), lambda b, i, j: (b, i, 0))
    kspec = pl.BlockSpec((1, BK, D), lambda b, i, j: (b, j, 0))
    lspec = pl.BlockSpec((1, BQ, 128), lambda b, i, j: (b, i, 0))
    with jax.named_scope(SCOPE):
        return pl.pallas_call(
            functools.partial(_fwd_carry_body, causal, scale, BQ, BK),
            name=FWD_CARRY_NAME,
            grid=grid,
            in_specs=[qspec, kspec, kspec, qspec, lspec, lspec],
            out_specs=[qspec, lspec, lspec],
            out_shape=[jax.ShapeDtypeStruct((BH, Tq, D), f32),
                       jax.ShapeDtypeStruct((BH, Tq, 128), f32),
                       jax.ShapeDtypeStruct((BH, Tq, 128), f32)],
            scratch_shapes=[pltpu.VMEM((BQ, D), f32),
                            pltpu.VMEM((BQ, 128), f32),
                            pltpu.VMEM((BQ, 128), f32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=_interpret(),
        )(q3, k3, v3, acc, m, l)


def flash_block_bwd(q3, k3, v3, o3, lse, do3, *, causal: bool,
                    scale: float):
    """One ring hop's backward contribution (FlashAttention-2 math with
    the GLOBAL logsumexp, so per-hop contributions sum exactly): returns
    (dq_contrib, dk, dv) for this (q, k-block) pair via the existing
    fused _dq/_dkv kernels."""
    return _bwd(q3, k3, v3, None, causal, scale, o3, lse, do3)


def fused_ring_applicable(t_local: int, D: int, dtype) -> bool:
    """Probe for the fused ring-hop kernels (helper seam): the per-device
    sequence block must tile the TPU lane dim; head-dim/dtype/backend
    rules are the shared _kernel_eligible policy. t_local = T / ring_size."""
    return _kernel_eligible(D, dtype) and t_local % 128 == 0 and t_local > 0


# --------------------------------------------------------------- custom vjp
@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _flash(q3, k3, v3, mask2, causal, scale):
    o, _ = _fwd(q3, k3, v3, mask2, causal, scale)
    return o


def _flash_fwd(q3, k3, v3, mask2, causal, scale):
    o, lse = _fwd(q3, k3, v3, mask2, causal, scale)
    return o, (q3, k3, v3, mask2, o, lse)


def _flash_bwd(causal, scale, res, do3):
    q3, k3, v3, mask2, o3, lse = res
    dq, dk, dv = _bwd(q3, k3, v3, mask2, causal, scale, o3, lse, do3)
    # mask2 is a traced array operand when present: an explicit zero
    # cotangent is version-stable, None-for-array is not
    dmask = None if mask2 is None else jnp.zeros_like(mask2)
    return dq, dk, dv, dmask


_flash.defvjp(_flash_fwd, _flash_bwd)


def _device_split(B: int, H: int):
    """How a program traced for SEVERAL devices must run the kernels.

    A Mosaic custom call cannot be partitioned automatically: under a jit
    over more than one device (ParallelWrapper's GSPMD step, a
    (data, model) mesh, the head-sharded decode prefill) it refuses to
    lower ("Mosaic kernels cannot be automatically partitioned" — first
    seen on four real chips; the CPU interpreter path is plain ops and
    never showed it). Attention is independent per batch row and per
    head, so each device runs the kernels on its own block under a
    ``shard_map``: batch over the mesh's ``data`` axis, heads over its
    ``model`` axis (the repo's axis names — parallel/mesh.py,
    parallel/tensor_parallel.py), T and D whole.

    The mesh comes from the tracing context
    (``jax.sharding.use_abstract_mesh``, which ParallelWrapper.fit and
    GenerationProgramSet.warm enter). Returns ``(spec, axis_names)`` for
    the shard_map, or None when there is nothing to split: no mesh in
    context (a one-device program), or every axis already manual (inside
    ParallelWrapper's own shard_map)."""
    from ..parallel.tensor_parallel import MODEL_AXIS
    mesh = jax.sharding.get_abstract_mesh()
    auto = frozenset(mesh.axis_names) - frozenset(mesh.manual_axes)
    if mesh.empty or not auto:
        return None

    def axis(name, n):         # an axis that is still ours to split evenly
        return name if name in auto and n % mesh.shape[name] == 0 else None
    # every remaining axis turns manual (Mosaic accepts nothing less); one
    # the spec does not name just repeats the work on its devices
    return P(axis("data", B), axis(MODEL_AXIS, H), None, None), auto


def flash_attention(q, k, v, *, causal: bool = False,
                    scale: Optional[float] = None, key_mask=None):
    """Fused softmax attention, [B,H,T,D] in/out — drop-in for
    parallel/ring_attention.attention when fused_attention_applicable.
    ``key_mask`` [B,T] excludes padded timesteps as keys."""
    B, H, T, D = q.shape
    scale = float(scale) if scale is not None else 1.0 / float(np.sqrt(D))

    def kernels(q, k, v, *mask):          # one device's [b,h,T,D] block
        b, h = q.shape[:2]
        o = _flash(q.reshape(b * h, T, D), k.reshape(b * h, T, D),
                   v.reshape(b * h, T, D), mask[0] if mask else None,
                   causal, scale)
        return o.reshape(b, h, T, D)

    args = (q, k, v) + (() if key_mask is None else (jnp.asarray(key_mask),))
    split = _device_split(B, H)
    if split is None:
        return kernels(*args)
    spec, axis_names = split
    return jax.shard_map(
        kernels, in_specs=(spec,) * 3 + (P(spec[0], None),) * (len(args) - 3),
        out_specs=spec, axis_names=axis_names, check_vma=False)(*args)
