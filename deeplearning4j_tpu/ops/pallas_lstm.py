"""Fused LSTM time-loop kernels (Pallas / TPU) — plain and Graves
(peephole) variants.

Reference hot loop: nn/layers/recurrent/LSTMHelpers.java:184-207 (fwd gemm
per timestep, incl. the peephole terms) and :466 (bwd loop). The
``lax.scan`` path re-reads the [H, 4H] recurrent matrix R from HBM on every
timestep — T * 16*H^2 bytes of redundant traffic that leaves the cell
bandwidth-bound at ~2% MFU. These kernels pin R (forward) and R plus the dR
accumulator (backward) in VMEM across the whole time loop: the TPU grid is
sequential on a core, so VMEM scratch and constant-index output blocks
persist between grid steps, turning the recurrence into a VMEM-resident
matmul chain. This is the accelerated-helper seam of the reference
(ConvolutionLayer.java:72 cuDNN probe) re-expressed the TPU way: the fused
path is used when it applies, the scan fallback otherwise, and parity tests
pin one to the other (tests/test_pallas_lstm.py).

No benchmark cell runs this kernel yet, so it has no chip timing on record
(ROADMAP Queue 3 item 8).

Supported fast path: tanh/sigmoid activations, float32, H % 128 == 0,
B % 8 == 0, VMEM-resident R (H <= 512); with or without peephole
connections (GravesLSTM) and with or without a per-step mask (masked steps
carry state through unchanged, the scan-path semantics). Everything else
falls back to the scan in nn/layers/recurrent.py.

Gate order along the 4H axis matches the scan path: [i, f, o, g].
Peepholes follow LSTMHelpers.java: i/f gates peep at c_{t-1}, o at c_t.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax import lax

from .kernels import envutil as kenv

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# VMEM is ~16MB/core (pallas guide): backward needs R + dR resident
# (2 * 16*H^2 bytes) plus ~1.5MB of blocks — H=512 uses ~9.5MB.
_MAX_FUSED_H = 512


def fused_lstm_applicable(B: int, H: int, dtype, *, peepholes, mask,
                          reverse: bool, activation: str,
                          gate_activation: str) -> bool:
    """Can the fused kernel handle this call? (the helper-probe predicate).
    ``peepholes`` may be None (plain LSTM) or the (pi, pf, po) tuple
    (GravesLSTM); ``mask`` may be None or a per-step mask — all four
    combinations run fused."""
    if not kenv.fused_enabled("lstm", ("DL4J_TPU_FUSED_LSTM",)):
        return False
    if reverse:
        # the kernels are forward-only; a reverse caller must flip inputs/
        # outputs itself and probe with reverse=False, as _lstm_scan does
        return False
    if activation != "tanh" or gate_activation != "sigmoid":
        return False
    dt = jnp.dtype(dtype)
    if dt == jnp.float32:
        min_b = 8            # f32 sublane tile
    elif dt == jnp.bfloat16:
        min_b = 16           # bf16 sublane tile
    else:
        return False
    if H % 128 != 0 or B % min_b != 0 or H > _MAX_FUSED_H:
        return False
    return kenv.backend_admits("lstm", jax.default_backend(),
                               ("DL4J_TPU_FUSED_LSTM_INTERPRET",))


def _interpret() -> bool:
    # CPU (tests) runs the kernels in the pallas interpreter
    return jax.default_backend() != "tpu"


# ------------------------------------------------------------------ forward
# the kernel's name in the compiled program and in a device trace (an
# outer scope takes a transformation's wrapping: see ops/pallas_attention.py)
SCOPE = "fused_lstm"
FWD_NAME = "fused_lstm_fwd"


def _fwd_body(peephole, masked, x_ref, r_ref, h0_ref, c0_ref, *rest):
    if masked:
        m_ref, rest = rest[0], rest[1:]
    if peephole:
        pi_ref, pf_ref, po_ref = rest[:3]
        rest = rest[3:]
    (hs_ref, gates_ref, cs_ref, cprev_ref, hprev_ref,
     hT_ref, cT_ref, h_scr, c_scr) = rest
    t = pl.program_id(0)
    f32 = jnp.float32

    @pl.when(t == 0)
    def _():
        # scratch carries stay f32 regardless of the I/O dtype (bf16 runs
        # compute in f32 — the MXU accumulates bf16 matmuls in f32 anyway)
        h_scr[:] = h0_ref[:].astype(f32)
        c_scr[:] = c0_ref[:].astype(f32)

    h_prev = h_scr[:]
    c_prev = c_scr[:]
    H = h_prev.shape[-1]
    gates = x_ref[0].astype(f32) + jnp.dot(
        h_prev.astype(r_ref.dtype), r_ref[:], preferred_element_type=f32)
    zi, zf = gates[:, :H], gates[:, H:2 * H]
    zo, zg = gates[:, 2 * H:3 * H], gates[:, 3 * H:]
    if peephole:  # LSTMHelpers.java: i/f peep at c_{t-1}
        zi = zi + c_prev * pi_ref[0].astype(f32)
        zf = zf + c_prev * pf_ref[0].astype(f32)
    i = jax.nn.sigmoid(zi)
    f = jax.nn.sigmoid(zf)
    g = jnp.tanh(zg)
    c_new = f * c_prev + i * g
    if peephole:  # o peeps at c_t (the candidate)
        zo = zo + c_new * po_ref[0].astype(f32)
    o = jax.nn.sigmoid(zo)
    h_new = o * jnp.tanh(c_new)
    if masked:
        # masked steps carry state through unchanged (scan semantics)
        m = m_ref[0, 0].astype(f32)[:, None]   # [B, 1]
        h = m * h_new + (1.0 - m) * h_prev
        c = m * c_new + (1.0 - m) * c_prev
    else:
        h, c = h_new, c_new
    hs_ref[0] = h.astype(hs_ref.dtype)
    # post-activation gates + candidate c + prev-state views are the
    # backward residuals; writing them here avoids a t-1 indexing problem
    # in the reverse kernel
    gates_ref[0] = jnp.concatenate([i, f, o, g], axis=-1).astype(gates_ref.dtype)
    cs_ref[0] = c_new.astype(cs_ref.dtype)
    cprev_ref[0] = c_prev.astype(cprev_ref.dtype)
    hprev_ref[0] = h_prev.astype(hprev_ref.dtype)
    hT_ref[:] = h.astype(hT_ref.dtype)
    cT_ref[:] = c.astype(cT_ref.dtype)
    h_scr[:] = h
    c_scr[:] = c


def _fwd_call(x_proj, h0, c0, R, mask, peep=None):
    T, B, H4 = x_proj.shape
    H = H4 // 4
    f32 = jnp.float32
    io = x_proj.dtype                            # f32 or bf16
    out_shape = [
        jax.ShapeDtypeStruct((T, B, H), io),     # hs
        jax.ShapeDtypeStruct((T, B, H4), io),    # gates (post-activation)
        jax.ShapeDtypeStruct((T, B, H), io),     # cs
        jax.ShapeDtypeStruct((T, B, H), io),     # c_prev per step
        jax.ShapeDtypeStruct((T, B, H), io),     # h_prev per step
        jax.ShapeDtypeStruct((B, H), io),        # hT
        jax.ShapeDtypeStruct((B, H), io),        # cT
    ]
    step_block = lambda w: pl.BlockSpec((1, B, w), lambda t: (t, 0, 0),
                                        memory_space=pltpu.VMEM)
    full = lambda: pl.BlockSpec(memory_space=pltpu.VMEM)
    const = lambda: pl.BlockSpec((B, H), lambda t: (0, 0),
                                 memory_space=pltpu.VMEM)
    peep_spec = lambda: pl.BlockSpec((1, H), lambda t: (0, 0),
                                     memory_space=pltpu.VMEM)
    in_specs = [step_block(H4), full(), const(), const()]
    args = [x_proj, R, h0, c0]
    if mask is not None:
        # [T, 1, B] with a (1, 1, B) block: the last two block dims equal
        # the full array dims, which the TPU lowering requires for
        # sub-(8,128) tiles
        in_specs.append(pl.BlockSpec((1, 1, B), lambda t: (t, 0, 0),
                                     memory_space=pltpu.VMEM))
        args.append(mask.reshape(T, 1, B))
    if peep is not None:
        in_specs += [peep_spec()] * 3
        args += [p.reshape(1, H) for p in peep]
    with jax.named_scope(SCOPE):
        return pl.pallas_call(
            functools.partial(_fwd_body, peep is not None, mask is not None),
            name=FWD_NAME,
            grid=(T,),
            in_specs=in_specs,
            out_specs=[step_block(H), step_block(H4), step_block(H),
                       step_block(H), step_block(H), const(), const()],
            out_shape=out_shape,
            scratch_shapes=[pltpu.VMEM((B, H), f32), pltpu.VMEM((B, H), f32)],
            interpret=_interpret(),
        )(*args)


# ----------------------------------------------------------------- backward
BWD_NAME = "fused_lstm_bwd"


def _bwd_body(peephole, masked, gates_ref, cs_ref, cprev_ref, hprev_ref,
              dhs_ref, r_ref, dhT_ref, dcT_ref, *rest):
    if masked:
        m_ref, rest = rest[0], rest[1:]
    if peephole:
        pi_ref, pf_ref, po_ref = rest[:3]
        rest = rest[3:]
        (dxp_ref, dh0_ref, dc0_ref, dR_ref, dpi_ref, dpf_ref, dpo_ref,
         dh_scr, dc_scr, dR_scr, dpi_scr, dpf_scr, dpo_scr) = rest
    else:
        (dxp_ref, dh0_ref, dc0_ref, dR_ref,
         dh_scr, dc_scr, dR_scr) = rest
    r = pl.program_id(0)
    f32 = jnp.float32
    T = pl.num_programs(0)

    @pl.when(r == 0)
    def _():
        # all running accumulators live in f32 scratch (bf16 accumulation
        # over T steps would lose the gradient's low bits)
        dh_scr[:] = dhT_ref[:].astype(f32)
        dc_scr[:] = dcT_ref[:].astype(f32)
        dR_scr[:] = jnp.zeros_like(dR_scr)
        if peephole:
            dpi_scr[:] = jnp.zeros_like(dpi_scr)
            dpf_scr[:] = jnp.zeros_like(dpf_scr)
            dpo_scr[:] = jnp.zeros_like(dpo_scr)

    gates = gates_ref[0].astype(f32)
    H = cs_ref.shape[-1]
    i, f, o = gates[:, :H], gates[:, H:2 * H], gates[:, 2 * H:3 * H]
    g = gates[:, 3 * H:]
    c = cs_ref[0].astype(f32)           # candidate c (pre-mask)
    c_prev = cprev_ref[0].astype(f32)
    h_prev = hprev_ref[0]               # stays io dtype for the MXU dot
    tc = jnp.tanh(c)
    # fwd: h = m*h_new + (1-m)*h_prev ; c = m*c_new + (1-m)*c_prev
    dh_tot = dh_scr[:] + dhs_ref[0].astype(f32)
    dc_tot = dc_scr[:]
    if masked:
        m = m_ref[0, 0].astype(f32)[:, None]   # [B, 1]
        dh_new = m * dh_tot
        dc_in = m * dc_tot
    else:
        dh_new, dc_in = dh_tot, dc_tot
    do = dh_new * tc
    dzo = do * o * (1.0 - o)
    dc = dc_in + dh_new * o * (1.0 - tc * tc)
    if peephole:  # zo = ... + c_new * po, so dc picks up dzo * po
        dc = dc + dzo * po_ref[0].astype(f32)
    dzi = dc * g * i * (1.0 - i)
    dzf = dc * c_prev * f * (1.0 - f)
    dzg = dc * i * (1.0 - g * g)
    dz = jnp.concatenate([dzi, dzf, dzo, dzg], axis=-1)    # [B, 4H]
    dxp_ref[0] = dz.astype(dxp_ref.dtype)
    # dR += h_prev^T @ dz — f32 scratch accumulation across the sequential
    # grid; written out (cast to the param dtype) on the final step
    dR_scr[:] += lax.dot_general(h_prev.astype(r_ref.dtype),
                                 dz.astype(r_ref.dtype),
                                 (((0,), (0,)), ((), ())),
                                 preferred_element_type=f32)
    new_dc = dc * f + ((1.0 - m) * dc_tot if masked else 0.0)
    if peephole:
        dpi_scr[:] += jnp.sum(dzi * c_prev, axis=0)[None, :]
        dpf_scr[:] += jnp.sum(dzf * c_prev, axis=0)[None, :]
        dpo_scr[:] += jnp.sum(dzo * c, axis=0)[None, :]
        # zi/zf peep at c_{t-1}: their grads flow into dc_prev
        new_dc = new_dc + dzi * pi_ref[0].astype(f32) \
            + dzf * pf_ref[0].astype(f32)
    new_dh = lax.dot_general(dz.astype(r_ref.dtype), r_ref[:],
                             (((1,), (1,)), ((), ())),
                             preferred_element_type=f32)
    if masked:
        new_dh = new_dh + (1.0 - m) * dh_tot
    dh_scr[:] = new_dh
    dc_scr[:] = new_dc
    # after the final (t==0) step these hold the initial-state cotangents
    dh0_ref[:] = new_dh.astype(dh0_ref.dtype)
    dc0_ref[:] = new_dc.astype(dc0_ref.dtype)

    @pl.when(r == T - 1)
    def _():
        dR_ref[:] = dR_scr[:].astype(dR_ref.dtype)
        if peephole:
            dpi_ref[:] = dpi_scr[:].astype(dpi_ref.dtype)
            dpf_ref[:] = dpf_scr[:].astype(dpf_ref.dtype)
            dpo_ref[:] = dpo_scr[:].astype(dpo_ref.dtype)


def _bwd_call(gates, cs, c_prev, h_prev, dhs, R, dhT, dcT, mask, peep=None):
    T, B, H4 = gates.shape
    H = H4 // 4
    f32 = jnp.float32
    io = gates.dtype
    rev = lambda w: pl.BlockSpec((1, B, w), lambda r: (T - 1 - r, 0, 0),
                                 memory_space=pltpu.VMEM)
    full = lambda: pl.BlockSpec(memory_space=pltpu.VMEM)
    const = lambda: pl.BlockSpec((B, H), lambda r: (0, 0),
                                 memory_space=pltpu.VMEM)
    peep_spec = lambda: pl.BlockSpec((1, H), lambda r: (0, 0),
                                     memory_space=pltpu.VMEM)
    out_shape = [
        jax.ShapeDtypeStruct((T, B, H4), io),    # dx_proj
        jax.ShapeDtypeStruct((B, H), io),        # dh0
        jax.ShapeDtypeStruct((B, H), io),        # dc0
        jax.ShapeDtypeStruct((H, H4), io),       # dR
    ]
    out_specs = [rev(H4), const(), const(),
                 pl.BlockSpec((H, H4), lambda r: (0, 0),
                              memory_space=pltpu.VMEM)]
    in_specs = [rev(H4), rev(H), rev(H), rev(H), rev(H), full(),
                const(), const()]
    args = [gates, cs, c_prev, h_prev, dhs, R, dhT, dcT]
    if mask is not None:
        in_specs.append(pl.BlockSpec((1, 1, B), lambda r: (T - 1 - r, 0, 0),
                                     memory_space=pltpu.VMEM))
        args.append(mask.reshape(T, 1, B))
    scratch = [pltpu.VMEM((B, H), f32), pltpu.VMEM((B, H), f32),
               pltpu.VMEM((H, H4), f32)]                 # dh, dc, dR accum
    if peep is not None:
        in_specs += [peep_spec()] * 3
        args += [p.reshape(1, H) for p in peep]
        out_shape += [jax.ShapeDtypeStruct((1, H), io)] * 3  # dpi dpf dpo
        out_specs += [peep_spec()] * 3
        scratch += [pltpu.VMEM((1, H), f32)] * 3
    with jax.named_scope(SCOPE):
        return pl.pallas_call(
            functools.partial(_bwd_body, peep is not None, mask is not None),
            name=BWD_NAME,
            grid=(T,),
            in_specs=in_specs,
            out_specs=out_specs,
            out_shape=out_shape,
            scratch_shapes=scratch,
            interpret=_interpret(),
        )(*args)


# -------------------------------------------------------------- custom VJP
# mask=None flows through the custom_vjp as an empty pytree, selecting the
# specialized unmasked kernels (no mask loads / blends in the hot loop)
@jax.custom_vjp
def _fused_lstm_m(x_proj, h0, c0, R, mask):
    hs, _, _, _, _, hT, cT = _fwd_call(x_proj, h0, c0, R, mask)
    return hs, (hT, cT)


def _fused_lstm_m_fwd(x_proj, h0, c0, R, mask):
    hs, gates, cs, c_prev, h_prev, hT, cT = _fwd_call(x_proj, h0, c0, R, mask)
    return (hs, (hT, cT)), (gates, cs, c_prev, h_prev, R, mask)


def _fused_lstm_m_bwd(res, cts):
    gates, cs, c_prev, h_prev, R, mask = res
    dhs, (dhT, dcT) = cts
    dxp, dh0, dc0, dR = _bwd_call(gates, cs, c_prev, h_prev, dhs, R, dhT,
                                  dcT, mask)
    return dxp, dh0, dc0, dR, None    # mask is non-differentiable


_fused_lstm_m.defvjp(_fused_lstm_m_fwd, _fused_lstm_m_bwd)


def fused_lstm(x_proj, h0, c0, R, mask=None):
    """Run the fused plain LSTM over time. x_proj: [T, B, 4H] precomputed
    input projections (+bias); mask: optional [T, B] (masked steps carry
    state through unchanged); returns (hs [T, B, H], (hT, cT))."""
    return _fused_lstm_m(x_proj, h0, c0, R, mask)


@jax.custom_vjp
def _fused_lstm_pm(x_proj, h0, c0, R, pi, pf, po, mask):
    hs, *_, hT, cT = _fwd_call(x_proj, h0, c0, R, mask, (pi, pf, po))
    return hs, (hT, cT)


def _fused_lstm_pm_fwd(x_proj, h0, c0, R, pi, pf, po, mask):
    hs, gates, cs, c_prev, h_prev, hT, cT = _fwd_call(x_proj, h0, c0, R,
                                                      mask, (pi, pf, po))
    return (hs, (hT, cT)), (gates, cs, c_prev, h_prev, R, pi, pf, po, mask)


def _fused_lstm_pm_bwd(res, cts):
    gates, cs, c_prev, h_prev, R, pi, pf, po, mask = res
    dhs, (dhT, dcT) = cts
    dxp, dh0, dc0, dR, dpi, dpf, dpo = _bwd_call(
        gates, cs, c_prev, h_prev, dhs, R, dhT, dcT, mask, (pi, pf, po))
    return (dxp, dh0, dc0, dR, dpi.reshape(-1), dpf.reshape(-1),
            dpo.reshape(-1), None)


_fused_lstm_pm.defvjp(_fused_lstm_pm_fwd, _fused_lstm_pm_bwd)


def fused_lstm_peephole(x_proj, h0, c0, R, pi, pf, po, mask=None):
    """Fused GravesLSTM (peephole) variant — reference GravesLSTM.java:47 /
    LSTMHelpers peephole terms. pi/pf/po: [H]; mask: optional [T, B]."""
    return _fused_lstm_pm(x_proj, h0, c0, R, pi, pf, po, mask)
