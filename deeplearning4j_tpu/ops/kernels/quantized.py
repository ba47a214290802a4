"""int8 dynamic-quantized matmul — the serving-tier quantization kernel.

Recipe (the standard dynamic-quantization serving recipe):
  weights     static per-OUTPUT-channel symmetric scales (amax/127 over
              the input dim) — each output column keeps its own range;
  activations dynamic per-ROW symmetric scales computed from the batch
              at hand (serving batches are small; one amax reduce);
  product     int8 x int8 accumulated EXACTLY in int32 on the MXU
              (``preferred_element_type=int32``), then ONE f32 rescale by
              row_scale x col_scale. Exact integer accumulation makes the
              fused Pallas path and the XLA fallback bit-identical — the
              registry parity pin for this kernel is tol=0.0.

Error vs the f32 matmul is bounded by the quantization step (amax/127 per
axis); the serving tests pin relative error on real layer shapes. Greedy
token *identity* is NOT guaranteed through an int8 forward — that gate
belongs to the quantized KV cache (which is exact w.r.t. its own stored
values), so the int8 forward tier ships with bounded-error pins instead
(README "Kernel library & quantized tier").

``int8_forward_fn(net)`` builds a ``serving.programs.ProgramSet``
``forward_fn`` that runs every Dense-family matmul through this kernel
and leaves every other layer on its stock ``apply``.
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
# int8 native tile is (32, 128) (pallas guide); the M block also serves
# the f32 scale column, so keep it a multiple of 8 too.
_BM, _BN = 32, 128


def quantize_weights(w) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """[K, N] f32 → (int8 [K, N], f32 scale [N]) — symmetric per-output-
    channel. Zero columns get scale 1 so dequantization stays finite."""
    amax = jnp.max(jnp.abs(w), axis=0)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0).astype(f32)
    q = jnp.clip(jnp.round(w / scale[None, :]), -127, 127).astype(jnp.int8)
    return q, scale


def quantize_rows(x) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """[M, K] f32 → (int8 [M, K], f32 scale [M]) — dynamic symmetric
    per-row (per-example) scales."""
    amax = jnp.max(jnp.abs(x), axis=1)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0).astype(f32)
    q = jnp.clip(jnp.round(x / scale[:, None]), -127, 127).astype(jnp.int8)
    return q, scale


def int8_matmul_applicable(M: int, K: int, N: int) -> bool:
    """Probe for the FUSED path (the registry-dispatch seam): tile-aligned
    shapes on an admitted backend. The XLA fallback serves everything."""
    if not kenv.fused_enabled("int8_matmul"):
        return False
    if M % _BM or K % 128 or N % _BN:
        return False
    return kenv.backend_admits("int8_matmul", jax.default_backend())


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# the kernel's name in the compiled program and in a device trace (an
# outer scope takes a transformation's wrapping: see ops/pallas_attention.py)
SCOPE = "quantized"
KERNEL_NAME = "int8_matmul"


def _matmul_kernel(xq_ref, wq_ref, xs_ref, ws_ref, o_ref):
    acc = jax.lax.dot_general(
        xq_ref[...], wq_ref[...],
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)
    o_ref[...] = acc.astype(f32) * xs_ref[...] * ws_ref[...]


def int8_matmul_pallas(x_q, w_q, x_scale, w_scale):
    """Fused int8 GEMM: [M,K]i8 @ [K,N]i8 → [M,N]f32, K resident per
    block (serving layer widths fit VMEM comfortably). The scales ride as
    a [M,1] column and a [1,N] row: Mosaic tiles the last two dims by
    (8, 128), and a 1-D (32,) or (128,) block falls under that tiling."""
    M, K = x_q.shape
    N = w_q.shape[1]
    grid = (M // _BM, N // _BN)
    with jax.named_scope(SCOPE):
        return pl.pallas_call(
            _matmul_kernel,
            name=KERNEL_NAME,
            grid=grid,
            in_specs=[
                pl.BlockSpec((_BM, K), lambda i, j: (i, 0)),
                pl.BlockSpec((K, _BN), lambda i, j: (0, j)),
                pl.BlockSpec((_BM, 1), lambda i, j: (i, 0)),
                pl.BlockSpec((1, _BN), lambda i, j: (0, j)),
            ],
            out_specs=pl.BlockSpec((_BM, _BN), lambda i, j: (i, j)),
            out_shape=jax.ShapeDtypeStruct((M, N), f32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel")),
            interpret=_interpret(),
        )(x_q, w_q, x_scale[:, None], w_scale[None, :])


def int8_matmul_xla(x_q, w_q, x_scale, w_scale):
    """XLA fallback — the same exact-int32 math, so parity is bitwise."""
    acc = jax.lax.dot_general(
        x_q, w_q, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)
    return acc.astype(f32) * x_scale[:, None] * w_scale[None, :]


def int8_matmul(x, w_q, w_scale):
    """Dynamic-quantized matmul: f32 activations [M,K] against
    pre-quantized weights — dispatches fused vs fallback through the
    registry probe."""
    x_q, x_scale = quantize_rows(x)
    M, K = x.shape
    N = w_q.shape[1]
    if int8_matmul_applicable(M, K, N):
        return int8_matmul_pallas(x_q, w_q, x_scale, w_scale)
    return int8_matmul_xla(x_q, w_q, x_scale, w_scale)


def int8_dense(params, x):
    """One Dense-family layer's pre_output with the matmul quantized:
    works for inputs of any leading rank ([..., K] @ [K, N] + b)."""
    w_q, w_scale = quantize_weights(params["W"])
    lead = x.shape[:-1]
    K = x.shape[-1]
    y = int8_matmul(x.reshape(-1, K), w_q, w_scale)
    y = y.reshape(lead + (y.shape[-1],))
    return y + params["b"]


def int8_forward_fn(net):
    """A ``ProgramSet`` forward_fn: the net's inference walk with every
    DenseLayer/OutputLayer matmul running through ``int8_matmul``
    (per-channel weight scales quantized in-program from the live params,
    so hot-swapped params re-quantize automatically). Non-dense layers
    run their stock ``apply``. f32 nets only — the int8 tier quantizes
    FROM full precision."""
    from ...nn.layers.core import DenseLayer

    if getattr(net.conf, "compute_dtype", None):
        raise ValueError("int8_forward_fn expects a full-precision net "
                         "(compute_dtype nets already run a reduced-"
                         "precision forward)")

    def forward(params, state, x):
        rng = jax.random.PRNGKey(0)
        for i, layer in enumerate(net.layers):
            pre = net.conf.preprocessor(i)
            if pre is not None:
                x = pre.apply(x)
            rng, sub = jax.random.split(rng)
            if isinstance(layer, DenseLayer):
                x = layer.act(int8_dense(params[i], x))
            else:
                x, _ = layer.apply(params[i], state[i], x,
                                   train=False, rng=sub)
        return x

    return forward


# ------------------------------------------------------------- parity pin
def _parity_run(seed: int):
    import numpy as np
    rng = np.random.default_rng(seed)
    M, K, N = 64, 256, 256
    x = jnp.asarray(rng.standard_normal((M, K)), f32)
    w = jnp.asarray(rng.standard_normal((K, N)), f32)
    w_q, w_s = quantize_weights(w)
    x_q, x_s = quantize_rows(x)
    fused = int8_matmul_pallas(x_q, w_q, x_s, w_s)
    fb = int8_matmul_xla(x_q, w_q, x_s, w_s)
    return [fused], [fb]


def roofline(shape_sig: str) -> Tuple[float, float]:
    """(flops, bytes) for one M,K,N GEMM — int8 reads, f32 writes."""
    M, K, N = (int(v) for v in shape_sig.split("x"))
    flops = 2.0 * M * K * N
    nbytes = float(M * K + K * N + 4 * M * N + 4 * (M + N))
    return flops, nbytes
