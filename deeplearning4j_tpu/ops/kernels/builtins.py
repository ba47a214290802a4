"""Builtin kernel registrations — the library's spine.

Each entry binds a kernel's fused Pallas impl, its XLA fallback, the
probe, the legacy env aliases, a PARITY PIN (the auto-generated tier-1
test per kernel lives in tests/test_kernel_registry.py — a kernel
registered here without a pin fails that suite), and a roofline model for
the below-bound flagging gauges.

Imported lazily by ``registry._ensure_builtins()`` so the pallas modules
themselves never see an import cycle.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .registry import KernelSpec, ParityPin, register

f32 = jnp.float32


# --------------------------------------------------------------- attention
def _attention_parity(seed: int):
    from .. import pallas_attention as pa
    from ...parallel.ring_attention import attention as xla_attention
    rng = np.random.default_rng(seed)
    B, H, T, D = 1, 2, 256, 64
    q, k, v = (jnp.asarray(rng.standard_normal((B, H, T, D)) * 0.3, f32)
               for _ in range(3))
    # Full-f32 matmuls on BOTH sides: at the TPU's default precision f32
    # operands reach the MXU as bf16, and the two algorithms round the
    # probabilities at different points (normalized p vs exp(s - m)), so
    # they differ by a bf16 ulp (1.5e-3 measured on a v5e) for reasons
    # that say nothing about the recurrence this pin checks.
    with jax.default_matmul_precision("highest"):
        fused = pa.flash_attention(q, k, v, causal=True)
        fb = xla_attention(q, k, v, causal=True)
    return [fused], [fb]


def _attention_roofline(shape_sig: str):
    B, H, T, D = (int(v) for v in shape_sig.split("x"))
    flops = 4.0 * B * H * T * T * D          # QK^T + PV
    nbytes = 4.0 * 4 * B * H * T * D         # q/k/v/o, f32 — VMEM-resident s
    return flops, nbytes


def _register_attention():
    from .. import pallas_attention as pa
    from ...parallel.ring_attention import attention as xla_attention
    register(KernelSpec(
        name="attention",
        fused=pa.flash_attention,
        fallback=xla_attention,
        applicable=pa.fused_attention_applicable,
        kill_aliases=("DL4J_TPU_FUSED_ATTENTION",),
        interpret_aliases=("DL4J_TPU_FUSED_ATTN_INTERPRET",),
        parity=ParityPin(run=_attention_parity, tol=2e-5,
                         note="online-softmax f32 recurrence vs one-shot "
                              "softmax: associativity-level error only"),
        roofline=_attention_roofline,
        tunable="(BQ, BK) score-tile sizes (DL4J_TPU_ATTN_BQ/BK env, "
                "autotune key T<T>, T<T>causal); causal default (256, 256)",
        default_choice=(512, 1024),
        notes="flash attention fwd+bwd; O(T) HBM traffic; causal calls "
              "visit 10 of 16 score tiles at T=1024 "
              "(pallas_attention.tile_schedule)",
    ))


# -------------------------------------------------------------------- lstm
def _lstm_scan_ref(xp, h0, c0, Rm):
    H = h0.shape[-1]

    def step(carry, x):
        h_prev, c_prev = carry
        gates = x + h_prev @ Rm
        i = jax.nn.sigmoid(gates[:, :H])
        fg = jax.nn.sigmoid(gates[:, H:2 * H])
        o = jax.nn.sigmoid(gates[:, 2 * H:3 * H])
        g = jnp.tanh(gates[:, 3 * H:])
        c = fg * c_prev + i * g
        h = o * jnp.tanh(c)
        return (h, c), h

    (hT, cT), hs = jax.lax.scan(step, (h0, c0), xp)
    return hs, (hT, cT)


def _lstm_parity(seed: int):
    from ..pallas_lstm import fused_lstm
    rng = np.random.default_rng(seed)
    T, B, H = 4, 8, 128
    xp = jnp.asarray(rng.standard_normal((T, B, 4 * H)) * 0.3, f32)
    h0 = jnp.asarray(rng.standard_normal((B, H)) * 0.1, f32)
    c0 = jnp.asarray(rng.standard_normal((B, H)) * 0.1, f32)
    Rm = jnp.asarray(rng.standard_normal((H, 4 * H)) * 0.1, f32)
    hs1, (hT1, cT1) = fused_lstm(xp, h0, c0, Rm)
    hs2, (hT2, cT2) = _lstm_scan_ref(xp, h0, c0, Rm)
    return [hs1, hT1, cT1], [hs2, hT2, cT2]


def _lstm_roofline(shape_sig: str):
    T, B, H = (int(v) for v in shape_sig.split("x"))
    flops = T * 2.0 * B * H * 4 * H          # the recurrent gemm chain
    nbytes = 4.0 * (16 * H * H + T * B * 4 * H + T * B * H)
    return flops, nbytes


def _register_lstm():
    from .. import pallas_lstm as pls
    register(KernelSpec(
        name="lstm",
        fused=pls.fused_lstm,
        fallback=_lstm_scan_ref,
        applicable=pls.fused_lstm_applicable,
        kill_aliases=("DL4J_TPU_FUSED_LSTM",),
        interpret_aliases=("DL4J_TPU_FUSED_LSTM_INTERPRET",),
        parity=ParityPin(run=_lstm_parity, tol=1e-5,
                         note="VMEM-resident recurrence vs lax.scan"),
        roofline=_lstm_roofline,
        tunable="none (R pinned whole in VMEM; H <= 512 gate)",
        notes="fused LSTM time loop, plain + peephole variants",
    ))


# -------------------------------------------------------- threshold_encode
def _encode_xla(residual, threshold):
    t = jnp.asarray(threshold, residual.dtype)
    s = jnp.where(jnp.abs(residual) >= t, jnp.sign(residual),
                  jnp.zeros((), residual.dtype))
    return s.astype(jnp.int8), residual - s * t


def _encode_parity(seed: int):
    from ..pallas_compression import threshold_encode_pallas
    rng = np.random.default_rng(seed)
    n = (1 << 16) + 777          # one full block + ragged tail
    r = jnp.asarray(rng.standard_normal((n,)) * 1e-3, f32)
    thr = 1e-3
    s1, nr1 = threshold_encode_pallas(r, thr)
    s2, nr2 = _encode_xla(r, thr)
    return [s1, nr1], [s2, nr2]


def _encode_roofline(shape_sig: str):
    n = int(shape_sig)
    return 3.0 * n, 9.0 * n      # compare+sub+mul; 4B in + 1B + 4B out


def _register_encode():
    from .. import pallas_compression as pc
    register(KernelSpec(
        name="threshold_encode",
        fused=pc.threshold_encode_pallas,
        fallback=_encode_xla,
        applicable=pc.fused_threshold_encode_applicable,
        kill_aliases=("DL4J_TPU_FUSED_ENCODE",),
        interpret_aliases=("DL4J_TPU_FUSED_ENCODE_INTERPRET",),
        parity=ParityPin(run=_encode_parity, tol=0.0,
                         note="bit-identical by construction (same "
                              "elementwise ops)"),
        roofline=_encode_roofline,
        tunable="block elements (fixed 64K; memory-bound, insensitive)",
        default_choice=(1 << 16,),
        notes="one-pass sign-map encode + residual update",
    ))


# ------------------------------------------------------------- int8_matmul
def _register_int8_matmul():
    from . import quantized as qz
    register(KernelSpec(
        name="int8_matmul",
        fused=qz.int8_matmul_pallas,
        fallback=qz.int8_matmul_xla,
        applicable=qz.int8_matmul_applicable,
        parity=ParityPin(run=qz._parity_run, tol=0.0,
                         note="exact int32 accumulation both paths"),
        roofline=qz.roofline,
        tunable="(BM, BN) = (32, 128) int8 tiles (K resident)",
        default_choice=(32, 128),
        notes="dynamic per-row activation scales x static per-channel "
              "weight scales, f32 rescale",
    ))


# -------------------------------------------------------- conv1x1_bias_relu
def _register_conv():
    from . import conv as cv
    register(KernelSpec(
        name="conv1x1_bias_relu",
        fused=cv.conv1x1_bias_relu,
        fallback=cv._conv1x1_xla,
        applicable=cv.conv1x1_bias_relu_applicable,
        parity=ParityPin(run=cv._parity_run, tol=1e-5,
                         note="same f32-accumulate recipe both paths"),
        roofline=cv.roofline,
        tunable="(BM, BN) pixel/channel blocks (256, 128)",
        default_choice=(256, 128),
        notes="pointwise conv + bias + relu in one HBM write; "
              "custom_vjp XLA backward",
    ))


# --------------------------------------------------------- paged_attention
def _paged_attention_parity(seed: int):
    from .. import pallas_paged_attention as ppa
    rng = np.random.default_rng(seed)
    S, H, Dh, blk, mb, L, layer = 5, 2, 64, 16, 8, 2, 1
    # an idle slot, one key, a page boundary from both sides, a full table
    lens = np.array([0, 1, 16, 17, mb * blk - 2], np.int32)
    nb = S * mb + 1
    k_pool, v_pool = (jnp.asarray(rng.standard_normal(
        (L, nb, blk, H * Dh)) * 0.5, f32) for _ in range(2))
    tables = jnp.asarray(
        1 + rng.permutation(nb - 1)[:S * mb].reshape(S, mb), jnp.int32)
    fused, fb = [], []
    # full-f32 matmuls on both sides, as for "attention" above
    with jax.default_matmul_precision("highest"):
        for W in (1, 3):            # the decode step, a verify window
            q = jnp.asarray(rng.standard_normal((S, H, W, Dh)) * 0.5, f32)
            args = (q, k_pool, v_pool, layer, tables, jnp.asarray(lens))
            fused.append(ppa.paged_attention_decode(*args))
            fb.append(ppa.paged_attention_reference(*args))
    return fused, fb


def _paged_attention_roofline(shape_sig: str):
    H, W, Dh, live, itemsize = (int(v) for v in shape_sig.split("x"))
    flops = 4.0 * W * H * Dh * live          # QK^T + PV over the live keys
    nbytes = 2.0 * live * H * Dh * itemsize  # each live key and value, once
    return flops, nbytes


def _register_paged_attention():
    from .. import pallas_paged_attention as ppa
    register(KernelSpec(
        name="paged_attention",
        fused=ppa.paged_attention_decode,
        fallback=ppa.paged_attention_reference,
        # plain pools always take it (the interpreter off the TPU): no
        # probe, no kill switch; the int8 tier never calls it
        applicable=lambda *a, **k: True,
        parity=ParityPin(run=_paged_attention_parity, tol=2e-5,
                         note="online softmax over page groups vs one-shot "
                              "softmax over the gathered table"),
        roofline=_paged_attention_roofline,
        tunable="keys folded per online-softmax step (their pages' DMAs "
                "are in flight together)",
        default_choice=(ppa._KEYS_PER_STEP,),
        notes="decode / verify attention reading the paged KV pool in "
              "place through the block table; memory-bound",
    ))


# ------------------------------------------------------------- moe_experts
def _moe_experts_args(seed: int):
    rng = np.random.default_rng(seed)
    N, d, F, E, k = 40, 128, 256, 8, 2
    mk = lambda *s: jnp.asarray(rng.standard_normal(s) * 0.1, f32)
    idx = jnp.asarray(np.stack([rng.permutation(E)[:k] for _ in range(N)]),
                      jnp.int32)
    return (jnp.asarray(rng.standard_normal((N, d)), f32), idx,
            jnp.asarray(rng.random((N, k)), f32), mk(E, d, F), mk(E, d, F),
            mk(E, F, d))


def _moe_experts_parity(seed: int):
    from .. import grouped_matmul as gm
    args = _moe_experts_args(seed)
    with jax.default_matmul_precision("highest"):
        fused = gm._expert_ffn(*args, first=0, fused=True,
                               interpret=gm._interpret())
        return [fused], [gm.expert_ffn_reference(*args)]


def _moe_experts_roofline(shape_sig: str):
    pairs, touched, d, F, itemsize = (int(v) for v in shape_sig.split("x"))
    flops = pairs * 2.0 * 3 * d * F
    nbytes = touched * 3.0 * d * F * itemsize + pairs * 2.0 * d * itemsize
    return flops, nbytes


def _register_moe_experts():
    from .. import grouped_matmul as gm
    register(KernelSpec(
        name="moe_experts",
        fused=gm.expert_ffn,
        fallback=gm.expert_ffn_reference,
        applicable=lambda x, idx, w, W1, *a, **k: gm.kernels_applicable(
            x.shape[1], W1.shape[2], x.dtype),
        parity=ParityPin(run=_moe_experts_parity, tol=1e-4,
                         note="pairs sorted by expert through two tiled "
                              "kernels vs every expert over every token, "
                              "masked"),
        roofline=_moe_experts_roofline,
        tunable="rows of a tile (row_tile: near an expert's mean run, "
                "16..256)",
        default_choice=(256,),
        notes="the experts of a mixture-of-experts layer over the pairs "
              "routed to them: compute bound in a prefill, a stream of the "
              "touched experts' weights in a decode step; ragged_dot off "
              "the TPU and under a gradient",
    ))


for _reg in (_register_attention, _register_lstm, _register_encode,
             _register_int8_matmul, _register_conv,
             _register_paged_attention, _register_moe_experts):
    _reg()
