"""Fused 1x1-conv + bias + relu Pallas kernel — the ResNet bottleneck path.

A 1x1/stride-1 convolution IS a matmul over the channel axis
([N*H*W, C] @ [C, F]); XLA lowers it that way too but keeps the bias add
and relu as separate HBM round-trips over the [N,H,W,F] activation map
when fusion heuristics miss (the roofline gauges show the bf16 ResNet
forward at ~30% MFU with these boundaries). This kernel emits the
activation map ONCE: matmul (f32 accumulate on the MXU) + bias + relu in
VMEM, one HBM write.

The layer seam is ``nn/layers/conv.ConvolutionLayer.apply`` — the exact
place the reference probed its cuDNN helper (ConvolutionLayer.java:72) —
probing ``conv1x1_bias_relu_applicable`` and falling back to the stock
``lax.conv_general_dilated`` path. The fused forward carries a
``custom_vjp`` whose backward is plain XLA ops (recompute pre-activation,
mask, three matmuls), so training through the fused layer stays
grad-correct (gradcheck-covered by the parity tests).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from . import envutil as kenv

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

f32 = jnp.float32
_BM = 256           # rows (pixels) per block; f32 sublane tile is 8
_BN = 128           # output channels per block (lane tile)


def conv1x1_bias_relu_applicable(kernel_size, stride, dilation, padding,
                                 mode: str, has_bias: bool, activation,
                                 C: int, F: int, dtype) -> bool:
    """Probe (the helper seam): geometry must be a pure pointwise conv,
    channels tile-aligned, relu + bias present, f32/bf16, backend
    admitted. Everything else rides the stock XLA path."""
    if not kenv.fused_enabled("conv1x1_bias_relu"):
        return False
    if tuple(kernel_size) != (1, 1) or tuple(stride) != (1, 1) \
            or tuple(dilation) != (1, 1):
        return False
    # for a 1x1/stride-1 conv SAME pads nothing, so either mode is fine —
    # but explicit nonzero padding changes the output map
    if mode != "same" and tuple(padding) != (0, 0):
        return False
    if not has_bias or activation != "relu":
        return False
    dt = jnp.dtype(dtype)
    if dt not in (jnp.float32, jnp.dtype(jnp.bfloat16)):
        return False
    if C % 128 != 0 or F % _BN != 0:
        return False
    return kenv.backend_admits("conv1x1_bias_relu", jax.default_backend())


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# the kernel's name in the compiled program and in a device trace (an
# outer scope takes a transformation's wrapping: see ops/pallas_attention.py)
SCOPE = "conv1x1"
KERNEL_NAME = "conv1x1_bias_relu"


def _conv_kernel(x_ref, w_ref, b_ref, o_ref):
    acc = jax.lax.dot_general(
        x_ref[...], w_ref[...],
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=f32)
    y = acc + b_ref[...].astype(f32)
    o_ref[...] = jnp.maximum(y, 0.0).astype(o_ref.dtype)


def _conv1x1_pallas(xm, wm, b):
    """[M, C] @ [C, F] + b, relu — M may be ragged (Mosaic masks the tail
    block's store). The bias rides as a [1, F] row: a 1-D (128,) block
    falls under Mosaic's (8|16, 128) tiling of the last two dims."""
    M, C = xm.shape
    F = wm.shape[1]
    grid = (pl.cdiv(M, _BM), F // _BN)
    with jax.named_scope(SCOPE):
        return pl.pallas_call(
            _conv_kernel,
            name=KERNEL_NAME,
            grid=grid,
            in_specs=[
                pl.BlockSpec((_BM, C), lambda i, j: (i, 0)),
                pl.BlockSpec((C, _BN), lambda i, j: (0, j)),
                pl.BlockSpec((1, _BN), lambda i, j: (0, j)),
            ],
            out_specs=pl.BlockSpec((_BM, _BN), lambda i, j: (i, j)),
            out_shape=jax.ShapeDtypeStruct((M, F), xm.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel")),
            interpret=_interpret(),
        )(xm, wm, b[None, :])


def _conv1x1_xla(xm, wm, b):
    """Fallback with the kernel's exact precision recipe (f32 accumulate,
    add bias in f32, relu, cast) — the parity pin is tight."""
    acc = jax.lax.dot_general(
        xm, wm, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=f32)
    y = acc + b[None, :].astype(f32)
    return jnp.maximum(y, 0.0).astype(xm.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=())
def conv1x1_bias_relu(x, W, b):
    """relu(conv1x1(x, W) + b) for x [N,H,W,C], W [1,1,C,F], b [F]."""
    N, H, Wd, C = x.shape
    F = W.shape[-1]
    wm = W.reshape(C, F)
    y = _conv1x1_pallas(x.reshape(-1, C), wm, b)
    return y.reshape(N, H, Wd, F)


def _fwd(x, W, b):
    return conv1x1_bias_relu(x, W, b), (x, W, b)


def _bwd(res, dy):
    # plain XLA backward: recompute the pre-activation mask, then the
    # three standard GEMM gradients — cheap relative to the forward win
    # and numerically identical to differentiating the fallback
    x, W, b = res
    N, H, Wd, C = x.shape
    F = W.shape[-1]
    xm = x.reshape(-1, C)
    wm = W.reshape(C, F)
    pre = jax.lax.dot_general(
        xm, wm, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=f32) + b[None, :].astype(f32)
    dym = dy.reshape(-1, F).astype(f32) * (pre > 0)
    dx = (dym @ wm.astype(f32).T).astype(x.dtype).reshape(x.shape)
    dW = (xm.astype(f32).T @ dym).astype(W.dtype).reshape(W.shape)
    db = jnp.sum(dym, axis=0).astype(b.dtype)
    return dx, dW, db


conv1x1_bias_relu.defvjp(_fwd, _bwd)


# ------------------------------------------------------------- parity pin
def _parity_run(seed: int):
    import numpy as np
    rng = np.random.default_rng(seed)
    N, H, Wd, C, F = 2, 4, 4, 128, 128
    x = jnp.asarray(rng.standard_normal((N, H, Wd, C)), f32)
    W = jnp.asarray(rng.standard_normal((1, 1, C, F)) * 0.1, f32)
    b = jnp.asarray(rng.standard_normal((F,)) * 0.1, f32)
    fused = conv1x1_bias_relu(x, W, b)
    fb = _conv1x1_xla(x.reshape(-1, C), W.reshape(C, F), b).reshape(
        N, H, Wd, F)
    return [fused], [fb]


def roofline(shape_sig: str) -> Tuple[float, float]:
    """(flops, bytes) for M pixels, C in-channels, F out-channels."""
    M, C, F = (int(v) for v in shape_sig.split("x"))
    flops = 2.0 * M * C * F
    nbytes = 4.0 * (M * C + C * F + F + M * F)
    return flops, nbytes
