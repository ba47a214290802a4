"""Normalization layers: BatchNormalization, LocalResponseNormalization.

Reference parity:
- BatchNormalization -> nn/conf/layers/BatchNormalization.java +
  nn/layers/normalization/BatchNormalization.java (helper probe :56; cuDNN
  impl CudnnBatchNormalizationHelper). On TPU the fused form is what XLA
  emits natively — no helper seam needed; running stats live in the layer
  STATE pytree and are updated functionally at train time.
- LocalResponseNormalization -> nn/layers/normalization/
  LocalResponseNormalization.java (cross-channel window; k/n/alpha/beta
  defaults match the reference).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import ClassVar, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..conf.serde import register
from .base import LayerConf


@register
@dataclass
class BatchNormalization(LayerConf):
    n_out: Optional[int] = None        # feature/channel count (inferred)
    decay: float = 0.9                 # running-stat EMA decay (reference default)
    eps: float = 1e-5
    lock_gamma_beta: bool = False      # reference lockGammaBeta: fixed gamma/beta
    gamma_init: float = 1.0
    beta_init: float = 0.0

    param_order: ClassVar[Tuple[str, ...]] = ("gamma", "beta")
    weight_param_names: ClassVar[Tuple[str, ...]] = ()   # no l1/l2 on gamma/beta
    expected_input: ClassVar[str] = "any"

    def _nf(self, itype):
        if self.n_out:
            return self.n_out
        from ..inputs import InputTypeConvolutional
        if itype is None:
            raise ValueError(
                "BatchNormalization cannot infer its feature count: set "
                "n_out explicitly or provide an input type (set_input_type "
                "or n_in on the first layer)")
        if isinstance(itype, InputTypeConvolutional):
            return itype.channels
        return itype.size

    def init(self, rng, itype, dtype):
        nf = self._nf(itype)
        params = {}
        if not self.lock_gamma_beta:
            params = {"gamma": jnp.full((nf,), self.gamma_init, dtype),
                      "beta": jnp.full((nf,), self.beta_init, dtype)}
        state = {"mean": jnp.zeros((nf,), jnp.float32),
                 "var": jnp.ones((nf,), jnp.float32)}
        return params, state

    def apply(self, params, state, x, *, train=False, rng=None):
        axes = tuple(range(x.ndim - 1))  # all but channel/feature dim
        if train:
            # E[x^2]-E[x]^2: both reductions fuse into ONE pass over the
            # activation map (jnp.var re-reads x after computing the mean;
            # flax's default use_fast_variance does the same). Cancellation
            # can drive the difference slightly negative for large-mean/
            # small-variance activations — clamp so rsqrt(var+eps) stays
            # finite (precision in that regime is limited either way).
            mean = jnp.mean(x, axis=axes)
            var = jnp.maximum(jnp.mean(x * x, axis=axes) - mean * mean, 0.0)
            new_state = {
                "mean": self.decay * state["mean"] + (1 - self.decay) * mean.astype(jnp.float32),
                "var": self.decay * state["var"] + (1 - self.decay) * var.astype(jnp.float32),
            }
        else:
            mean, var = state["mean"].astype(x.dtype), state["var"].astype(x.dtype)
            new_state = state
        inv = lax.rsqrt(var.astype(x.dtype) + jnp.asarray(self.eps, x.dtype))
        y = (x - mean.astype(x.dtype)) * inv
        if not self.lock_gamma_beta:
            y = y * params["gamma"] + params["beta"]
        else:
            y = y * self.gamma_init + self.beta_init
        return self.act(y), new_state


@register
@dataclass
class LayerNormalization(LayerConf):
    """Per-example layer norm over the FEATURE axis (net-new beyond the
    reference — its era predates transformers; required by the pre-LN
    transformer blocks in models.transformer_lm). Works on [B,F] and
    [B,T,F]; gain/bias per feature; no running stats (stateless, unlike
    BatchNormalization — nothing to desynchronize across a mesh)."""
    n_out: Optional[int] = None        # feature count (inferred)
    eps: float = 1e-5

    param_order: ClassVar[Tuple[str, ...]] = ("gain", "bias")
    weight_param_names: ClassVar[Tuple[str, ...]] = ()
    expected_input: ClassVar[str] = "any"

    def init(self, rng, itype, dtype):
        nf = self.n_out or (itype.size if itype is not None else None)
        if not nf:
            raise ValueError("LayerNormalization cannot infer its feature "
                             "count: set n_out or provide an input type")
        self.n_out = nf
        return {"gain": jnp.ones((nf,), dtype),
                "bias": jnp.zeros((nf,), dtype)}, {}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        mean = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.maximum(jnp.mean(x * x, axis=-1, keepdims=True)
                          - mean * mean, 0.0)
        inv = lax.rsqrt(var + jnp.asarray(self.eps, x.dtype))
        y = (x - mean) * inv * params["gain"] + params["bias"]
        return self.act(y), state


@register
@dataclass
class LocalResponseNormalization(LayerConf):
    """Cross-channel LRN over NHWC (reference defaults k=2, n=5, alpha=1e-4,
    beta=0.75)."""
    k: float = 2.0
    n: int = 5
    alpha: float = 1e-4
    beta: float = 0.75

    expected_input: ClassVar[str] = "cnn"

    def apply(self, params, state, x, *, train=False, rng=None):
        half = self.n // 2
        sq = x * x
        # windowed sum over the channel (last) dim
        summed = lax.reduce_window(sq, 0.0, lax.add,
                                   (1, 1, 1, self.n), (1, 1, 1, 1),
                                   ((0, 0), (0, 0), (0, 0), (half, half)))
        denom = (self.k + self.alpha * summed) ** self.beta
        return x / denom, state


@register
@dataclass
class RMSNorm(LayerConf):
    """Root-mean-square norm over the FEATURE axis, one gain a feature and
    no bias: ``x / sqrt(mean(x^2) + eps) * gain``. The statistics are taken
    in float32 whatever the activations' type (a bfloat16 mean of squares
    over 2,048 features loses the third digit)."""
    n_out: Optional[int] = None        # feature count (inferred)
    eps: float = 1e-5

    param_order: ClassVar[Tuple[str, ...]] = ("gain",)
    weight_param_names: ClassVar[Tuple[str, ...]] = ()
    expected_input: ClassVar[str] = "any"

    def init(self, rng, itype, dtype):
        nf = self.n_out or (itype.size if itype is not None else None)
        if not nf:
            raise ValueError("RMSNorm cannot infer its feature count: set "
                             "n_out or provide an input type")
        self.n_out = nf
        return {"gain": jnp.ones((nf,), dtype)}, {}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        return self.act(rms_norm(x, params["gain"], self.eps)), state


def rms_norm(x, gain, eps: float):
    """``x / sqrt(mean(x^2) + eps) * gain`` over the last axis, statistics
    in float32, result in x's dtype."""
    xf = x.astype(jnp.float32)
    inv = lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * inv * gain.astype(jnp.float32)).astype(x.dtype)
