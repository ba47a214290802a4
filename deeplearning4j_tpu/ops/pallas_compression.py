"""Fused threshold-encode Pallas kernel (TPU).

Reference: EncodingHandler.java:64-66 — the native ND4J thresholdEncode is
ONE pass over the gradient buffer. The XLA bounded-payload compaction path
(ops/compression.threshold_encode) costs mask + prefix-sum + scatter
passes (6.08ms on a 25M-element residual, 3.6x its 1.66ms HBM floor, on a
v5e before PR 1; not measured on today's code), which makes compressed DP pay more in encode than it saves on the
wire. This kernel restores the reference's single-pass cost for the DENSE
sign-map wire format (the EncodedAccumulator default): per block, read the
residual once and emit BOTH outputs — the packed int8 sign map (what a DCN
hop ships: 1 byte/elem vs 4) and the error-feedback residual — with no
intermediate f32 ``sent`` array materialized in HBM.

Traffic: 4B read + 1B signs + 4B residual = 9 bytes/element — the memory
floor for the op. Target (ISSUE 5): <= 2x that floor at 25M elements.

Same helper-probe-with-fallback seam as ops/pallas_attention.py /
pallas_lstm.py: callers probe ``fused_threshold_encode_applicable`` and
fall back to the XLA elementwise path (ops/compression.threshold_encode_
signs) when the kernel can't serve the call. The interpreter path
(DL4J_TPU_FUSED_ENCODE_INTERPRET=1, set by tests/conftest.py) exists for
CPU parity tests only. DL4J_TPU_FUSED_ENCODE=0 is the kill switch.

The array is 1-D (the flat gradient view); the grid tiles it in
``_BLOCK``-element chunks and Mosaic masks the ragged tail block (reads
past the edge are dropped on the store side), so arbitrary n needs no
host-side pad or reshape — the pad copy would itself cost a full extra
pass over the 100MB buffer.
"""
from __future__ import annotations

import functools
import os
from typing import Tuple

import jax
import jax.numpy as jnp

from .kernels import envutil as kenv

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# 64K elements/block: 256KB f32 in + 256KB out + 64KB signs in VMEM —
# comfortably inside the ~16MB budget with double buffering, and a
# multiple of every (sublane x 128-lane) tile shape f32/bf16 need.
_BLOCK = 1 << 16


def fused_threshold_encode_applicable(n: int, dtype) -> bool:
    """Probe: can the fused kernel serve a flat [n] residual? (Callers
    fall back to the XLA elementwise path when False.)"""
    if not kenv.fused_enabled("threshold_encode", ("DL4J_TPU_FUSED_ENCODE",)):
        return False
    dt = jnp.dtype(dtype)
    if dt not in (jnp.float32, jnp.dtype(jnp.bfloat16)):
        return False
    if n < _BLOCK:
        # below one block the pallas_call overhead beats the fusion win;
        # XLA fuses the tiny elementwise encode into its consumer anyway
        return False
    return kenv.backend_admits("threshold_encode", jax.default_backend(),
                               ("DL4J_TPU_FUSED_ENCODE_INTERPRET",))


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# the kernel's name in the compiled program and in a device trace (an
# outer scope takes a transformation's wrapping: see ops/pallas_attention.py)
SCOPE = "compression"
KERNEL_NAME = "threshold_encode"


def _encode_kernel(r_ref, signs_ref, res_ref, *, threshold):
    """One block: threshold compare + sign-pack + residual update, all in
    VMEM registers — the int8 sign map and the new residual are the only
    HBM writes."""
    r = r_ref[...]
    t = jnp.asarray(threshold, r.dtype)     # in-dtype compare, same as XLA
    s = jnp.where(jnp.abs(r) >= t, jnp.sign(r), jnp.zeros((), r.dtype))
    signs_ref[...] = s.astype(jnp.int8)
    res_ref[...] = r - s * t


def threshold_encode_pallas(residual: jnp.ndarray, threshold: float
                            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Dense-semantics threshold encode in ONE fused pass: returns
    ``(signs int8[n], new_residual)`` where ``signs[i]`` is the shipped
    quantum's sign ({-1, 0, +1}; the update peers apply is
    ``signs * threshold``) and ``new_residual`` carries the unsent mass
    (Strom error feedback). Bit-identical to the XLA elementwise path
    (``ops.compression.threshold_encode_signs``'s fallback branch) —
    pinned by tests/test_overlap_sync.py."""
    if residual.ndim != 1:
        raise ValueError(f"threshold_encode_pallas expects the flat 1-D "
                         f"gradient view, got shape {residual.shape}")
    n = residual.shape[0]
    grid = (pl.cdiv(n, _BLOCK),)
    kernel = functools.partial(_encode_kernel, threshold=float(threshold))
    with jax.named_scope(SCOPE):
        signs, new_res = pl.pallas_call(
            kernel,
            name=KERNEL_NAME,
            grid=grid,
            in_specs=[pl.BlockSpec((_BLOCK,), lambda i: (i,))],
            out_specs=[pl.BlockSpec((_BLOCK,), lambda i: (i,)),
                       pl.BlockSpec((_BLOCK,), lambda i: (i,))],
            out_shape=[jax.ShapeDtypeStruct((n,), jnp.int8),
                       jax.ShapeDtypeStruct((n,), residual.dtype)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=_interpret(),
        )(residual)
    return signs, new_res
