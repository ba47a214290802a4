"""Gated layers of the hybrid convolution / attention / mixture-of-experts
language models (net-new, beyond reference parity): a gated (SwiGLU) MLP, a
gated short convolution whose decode state is two rows, and a
mixture-of-experts layer with a sigmoid router.

None carries a bias. All work on [B,T,F] (and the MLPs on [N,F]).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import ClassVar, Optional, Tuple

import jax
import jax.numpy as jnp

from ..conf.serde import register
from ..inputs import InputTypeFeedForward, InputTypeRecurrent
from .base import LayerConf, maybe_dropout, resolve_ff_size


def _same_kind(itype, n_out: int):
    if isinstance(itype, InputTypeRecurrent):
        return InputTypeRecurrent(n_out, itype.timestep_length)
    return InputTypeFeedForward(n_out)


@register
@dataclass
class GatedMLP(LayerConf):
    """``W2 (silu(x W1) * (x W3))``: ``n_hidden`` wide inside, ``n_out``
    (default the input's width) out."""
    n_in: Optional[int] = None
    n_out: int = 0
    n_hidden: int = 0

    param_order: ClassVar[Tuple[str, ...]] = ("W1", "W3", "W2")
    weight_param_names: ClassVar[Tuple[str, ...]] = ("W1", "W3", "W2")
    expected_input: ClassVar[str] = "any"

    def output_type(self, itype):
        return _same_kind(itype, self.n_out or resolve_ff_size(itype))

    def init(self, rng, itype, dtype):
        n_in = self.n_in or resolve_ff_size(itype)
        self.n_in = n_in
        self.n_out = self.n_out or n_in
        if not self.n_hidden:
            raise ValueError("GatedMLP needs n_hidden")
        k1, k2, k3 = jax.random.split(rng, 3)
        F = self.n_hidden
        return {"W1": self._winit(k1, (n_in, F), n_in, F, dtype),
                "W3": self._winit(k2, (n_in, F), n_in, F, dtype),
                "W2": self._winit(k3, (F, self.n_out), F, self.n_out,
                                  dtype)}, {}

    def apply(self, params, state, x, *, train=False, rng=None):
        x = maybe_dropout(x, self.dropout, rng, train)
        h = jax.nn.silu(x @ params["W1"]) * (x @ params["W3"])
        return self.act(h @ params["W2"]), state


@register
@dataclass
class GatedShortConvLayer(LayerConf):
    """Gated short convolution, [B,T,d] -> [B,T,d]:

        [b, c, x] = split3(u W_in);  z = b * x
        conv_t = sum_j k[:, j] * z_{t - (K-1) + j}   (depthwise, causal)
        out = (c * conv) W_out

    ``kernel`` taps (3 in the published models). The state a decode step
    needs is the last ``kernel - 1`` rows of ``z``: ``[B, kernel-1, d]``,
    oldest first. ``apply_with_final_state`` carries it, so the layer is
    recurrent to ``rnn_time_step``, tBPTT and the decode specification
    alike; ``state_at`` gives it at a row of each sequence's own (a padded
    prompt's true end)."""
    n_in: Optional[int] = None
    n_out: int = 0
    kernel: int = 3

    param_order: ClassVar[Tuple[str, ...]] = ("W_in", "k", "W_out")
    weight_param_names: ClassVar[Tuple[str, ...]] = ("W_in", "W_out")
    expected_input: ClassVar[str] = "rnn"

    def output_type(self, itype):
        return _same_kind(itype, self.n_out or resolve_ff_size(itype))

    def init(self, rng, itype, dtype):
        n_in = self.n_in or resolve_ff_size(itype)
        self.n_in = n_in
        self.n_out = d = self.n_out or n_in
        if self.kernel < 2:
            raise ValueError("GatedShortConvLayer needs kernel >= 2")
        k1, k2, k3 = jax.random.split(rng, 3)
        return {"W_in": self._winit(k1, (n_in, 3 * d), n_in, 3 * d, dtype),
                "k": self._winit(k2, (d, self.kernel), self.kernel, 1, dtype),
                "W_out": self._winit(k3, (d, d), d, d, dtype)}, {}

    def zero_state(self, batch: int, dtype):
        return jnp.zeros((batch, self.kernel - 1, self.n_out), dtype)

    def _gates(self, params, u):
        b, c, x = jnp.split(u @ params["W_in"], 3, axis=-1)
        return b * x, c

    def state_at(self, params, u, lengths):
        """The state after ``lengths`` [B] rows of u [B,T,d] (the layer's
        input): z at rows ``lengths - (kernel-1) .. lengths - 1``, zeros
        for rows before the sequence began. z is position-wise, so only
        those rows go through ``W_in``."""
        K = self.kernel
        rows = lengths[:, None] - (K - 1) + jnp.arange(K - 1)[None, :]
        took = jnp.take_along_axis(u, jnp.maximum(rows, 0)[:, :, None],
                                   axis=1)                   # [B,K-1,d]
        z, _ = self._gates(params, took)
        return jnp.where((rows >= 0)[:, :, None], z, jnp.zeros((), z.dtype))

    def apply_with_final_state(self, params, state, x, *, train=False,
                               rng=None, mask=None, initial_state=None):
        """(out [B,T,d], the state after the last row)."""
        x = maybe_dropout(x, self.dropout, rng, train)
        B, T, _ = x.shape
        K = self.kernel
        z, c = self._gates(params, x)
        if initial_state is None:
            initial_state = self.zero_state(B, z.dtype)
        zp = jnp.concatenate([initial_state.astype(z.dtype), z], axis=1)
        k = params["k"]
        conv = sum(zp[:, j:j + T] * k[:, j] for j in range(K))
        out = self.act((c * conv) @ params["W_out"])
        return out, zp[:, T:]

    def apply(self, params, state, x, *, train=False, rng=None):
        out, _ = self.apply_with_final_state(params, state, x, train=train,
                                             rng=rng)
        return out, state


@register
@dataclass
class MixtureOfExpertsLayer(LayerConf):
    """Mixture of gated-MLP experts with a sigmoid (or, by ``score``, a
    softmax) router, [..., d] -> [..., d].

        s = sigmoid(x Wg)                       over all ``n_experts``
                                                (``score="softmax"``:
                                                softmax over them)
        chosen = top_k of (s + bias)            the bias takes part in the
                                                choice only
        w = s[chosen] / (sum s[chosen] + eps)   (``norm_topk``) * scale
        out = sum_e w_e W2[e] (silu(x W1[e]) * (x W3[e]))

    Every chosen pair is computed: no capacity, no dropped token.
    ``norm_eps`` is the family's: 1e-6 in one published code, 1e-20 in
    another. A shared expert, where a model has one, is a ``GatedMLP``
    vertex of the graph added to this layer's output: it belongs to no
    ``held`` range and is counted once.

    ``held`` = (first, count) names the experts whose weights this layer
    holds (default all). The router always scores all ``n_experts``; the
    layer computes the part of the sum its held experts give and leaves the
    rest out, so the shares of layers that hold disjoint ranges add up to
    the whole layer's output. On one chip it holds all and exchanges
    nothing; the exchange over a mesh will wrap this layer
    (``parallel/expert_parallel.py`` is the older top-1 capacity router).

    The experts' matmuls are ``ops.grouped_matmul.expert_ffn``."""
    n_in: Optional[int] = None
    n_out: int = 0
    n_experts: int = 0
    top_k: int = 1
    n_hidden: int = 0
    held: Optional[Tuple[int, int]] = None
    norm_topk: bool = True
    norm_eps: float = 1e-6
    routed_scaling_factor: float = 1.0
    # the router's score function over the logits x Wg, by name: "sigmoid"
    # (each expert for itself) or "softmax" (over all ``n_experts``); the
    # choice, the normalisation and the scale that follow are the same
    score: str = "sigmoid"

    param_order: ClassVar[Tuple[str, ...]] = ("Wg", "bias", "W1", "W3", "W2")
    weight_param_names: ClassVar[Tuple[str, ...]] = ("Wg", "W1", "W3", "W2")
    expected_input: ClassVar[str] = "any"

    def output_type(self, itype):
        return _same_kind(itype, self.n_out or resolve_ff_size(itype))

    def held_range(self) -> Tuple[int, int]:
        first, count = self.held if self.held else (0, self.n_experts)
        if first < 0 or count < 1 or first + count > self.n_experts:
            raise ValueError(f"held={self.held} is not a range of the "
                             f"{self.n_experts} experts")
        return int(first), int(count)

    def init(self, rng, itype, dtype):
        n_in = self.n_in or resolve_ff_size(itype)
        self.n_in = n_in
        self.n_out = d = self.n_out or n_in
        if d != n_in:
            raise ValueError("MixtureOfExpertsLayer keeps its width "
                             f"(n_in={n_in}, n_out={d})")
        if not (self.n_experts and self.n_hidden
                and 1 <= self.top_k <= self.n_experts):
            raise ValueError("MixtureOfExpertsLayer needs n_experts, "
                             "n_hidden and 1 <= top_k <= n_experts")
        if self.score not in ("sigmoid", "softmax"):
            raise ValueError(f"score={self.score!r}: the router scores by "
                             "'sigmoid' or 'softmax'")
        _, E = self.held_range()
        F = self.n_hidden
        kg, k1, k2, k3 = jax.random.split(rng, 4)
        return {"Wg": self._winit(kg, (n_in, self.n_experts), n_in,
                                  self.n_experts, dtype),
                "bias": jnp.zeros((self.n_experts,), dtype),
                "W1": self._winit(k1, (E, n_in, F), n_in, F, dtype),
                "W3": self._winit(k2, (E, n_in, F), n_in, F, dtype),
                "W2": self._winit(k3, (E, F, d), F, d, dtype)}, {}

    def route(self, params, x):
        """x [N, d] -> (idx [N, top_k] int32 expert ids, w [N, top_k]
        float32 weights). The scores are accumulated in float32: a choice
        between near-equal experts should not hang on a bfloat16 sum."""
        logits = jnp.matmul(x, params["Wg"],
                            preferred_element_type=jnp.float32)
        s = jax.nn.softmax(logits, axis=-1) if self.score == "softmax" \
            else jax.nn.sigmoid(logits)
        _, idx = jax.lax.top_k(s + params["bias"].astype(jnp.float32),
                               self.top_k)
        idx = idx.astype(jnp.int32)
        w = jnp.take_along_axis(s, idx, axis=-1)
        if self.norm_topk:
            w = w / (jnp.sum(w, axis=-1, keepdims=True) + self.norm_eps)
        return idx, w * self.routed_scaling_factor

    def apply(self, params, state, x, *, train=False, rng=None):
        from ...ops.grouped_matmul import expert_ffn
        x = maybe_dropout(x, self.dropout, rng, train)
        flat = x.reshape(-1, x.shape[-1])
        idx, w = self.route(params, flat)
        first, _ = self.held_range()
        y = expert_ffn(flat, idx, w, params["W1"], params["W3"],
                       params["W2"], first=first, kernels=not train)
        return self.act(y.reshape(x.shape)), state
