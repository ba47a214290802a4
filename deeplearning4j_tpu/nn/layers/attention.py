"""Self-attention layer (net-new, beyond reference parity).

The reference's sequence story is LSTM-only (SURVEY.md §5.7 explicitly notes
no attention exists). This layer adds the modern long-context primitive in
the framework's own layer SPI: multi-head softmax self-attention over
[B,T,F], mask-aware, causal-optional — single-device math in
parallel/ring_attention.attention, and the time axis is mesh-shardable via
parallel/ring_attention.ring_attention_sharded (sequence/context
parallelism over ICI).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, ClassVar, Optional, Tuple

import jax
import jax.numpy as jnp

from ..conf.serde import register
from ..inputs import InputTypeRecurrent
from .base import LayerConf, maybe_dropout, resolve_ff_size


@register
@dataclass
class SelfAttentionLayer(LayerConf):
    """Multi-head self-attention, [B,T,F] -> [B,T,n_out].

    ``n_out`` must be divisible by ``n_heads``. With ``causal`` each position
    attends only to itself and earlier steps. A [B,T] feature mask excludes
    padded timesteps as attention KEYS (queries at masked positions produce
    outputs that downstream masked losses ignore, matching the framework's
    masking convention).
    """
    n_in: Optional[int] = None
    n_out: int = 0
    n_heads: int = 4
    causal: bool = False
    project_out: bool = True
    # grouped-query attention: ``n_kv_heads`` key/value heads, each read by
    # ``n_heads // n_kv_heads`` query heads (query head i reads key-value
    # head ``i // group``). None = as many as query heads.
    n_kv_heads: Optional[int] = None
    # RMS norm over each head's values of q and k, one gain of head size
    # shared by the heads, before the rotation
    qk_norm: bool = False
    qk_norm_eps: float = 1e-5
    # rotary positions over the whole head, rotate-half form; None = none
    # (the positions then come from a PositionalEmbeddingLayer, or nowhere)
    rope_theta: Optional[float] = None
    bias: bool = True                  # the output projection's bias

    param_order: ClassVar[Tuple[str, ...]] = ("Wq", "Wk", "Wv", "Wo", "b",
                                              "q_gain", "k_gain")
    weight_param_names: ClassVar[Tuple[str, ...]] = ("Wq", "Wk", "Wv", "Wo")
    expected_input: ClassVar[str] = "rnn"
    accepts_mask: ClassVar[bool] = True

    def output_type(self, itype):
        t = itype.timestep_length if isinstance(itype, InputTypeRecurrent) else -1
        return InputTypeRecurrent(self.n_out, t)

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def head_dim(self) -> int:
        return self.n_out // self.n_heads

    def init(self, rng, itype, dtype):
        n_in = self.n_in or resolve_ff_size(itype)
        self.n_in = n_in
        if self.n_out % self.n_heads:
            raise ValueError(f"n_out={self.n_out} must be divisible by "
                             f"n_heads={self.n_heads}")
        if self.n_heads % self.kv_heads:
            raise ValueError(f"n_heads={self.n_heads} must be divisible by "
                             f"n_kv_heads={self.kv_heads}")
        ks = jax.random.split(rng, 4)
        d = self.n_out
        dkv = self.kv_heads * self.head_dim
        params = {
            "Wq": self._winit(ks[0], (n_in, d), n_in, d, dtype),
            "Wk": self._winit(ks[1], (n_in, dkv), n_in, dkv, dtype),
            "Wv": self._winit(ks[2], (n_in, dkv), n_in, dkv, dtype),
            "Wo": self._winit(ks[3], (d, d), d, d, dtype),
        }
        if self.bias:
            params["b"] = self._binit((d,), dtype)
        if self.qk_norm:
            params["q_gain"] = jnp.ones((self.head_dim,), dtype)
            params["k_gain"] = jnp.ones((self.head_dim,), dtype)
        return params, {}

    def _rotate(self, x, positions):
        """Rotary positions, rotate-half form: x [B,T,H,Dh], positions
        [B,T]; angles in float32."""
        half = self.head_dim // 2
        inv = self.rope_theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
        ang = positions.astype(jnp.float32)[:, :, None, None] * inv
        cos, sin = jnp.cos(ang), jnp.sin(ang)
        xf = x.astype(jnp.float32)
        x1, x2 = xf[..., :half], xf[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                               axis=-1).astype(x.dtype)

    def project_qkv(self, params, x, positions=None):
        """x [B,T,F] -> (q [B,T,Hq,Dh], k [B,T,Hkv,Dh], v [B,T,Hkv,Dh]) as
        attention takes them and a cache keeps them: q and k normed and
        rotated where the layer says so. ``positions`` [B,T] are the rows'
        positions in their sequences (default 0..T-1)."""
        B, T, _ = x.shape
        Dh = self.head_dim
        q = (x @ params["Wq"]).reshape(B, T, self.n_heads, Dh)
        k = (x @ params["Wk"]).reshape(B, T, self.kv_heads, Dh)
        v = (x @ params["Wv"]).reshape(B, T, self.kv_heads, Dh)
        if self.qk_norm:
            from .norm import rms_norm
            q = rms_norm(q, params["q_gain"], self.qk_norm_eps)
            k = rms_norm(k, params["k_gain"], self.qk_norm_eps)
        if self.rope_theta is not None:
            if positions is None:
                positions = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
            q, k = self._rotate(q, positions), self._rotate(k, positions)
        return q, k, v

    def project_output(self, params, out):
        """The heads' output [B,T,H*Dh] through Wo (and its bias) and the
        layer's activation."""
        if self.project_out:
            out = out @ params["Wo"]
            if self.bias:
                out = out + params["b"]
        return self.act(out)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        from ...ops.pallas_attention import (flash_attention,
                                             fused_attention_applicable)
        from ...parallel.ring_attention import attention
        x = maybe_dropout(x, self.dropout, rng, train)
        q, k, v = self.project_qkv(params, x)
        group = self.n_heads // self.kv_heads
        if group > 1:
            # the kernels take equal heads: each key-value head repeated
            # for the query heads that read it (a cache keeps kv_heads)
            k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
        q, k, v = (a.transpose(0, 2, 1, 3) for a in (q, k, v))
        B, H, T, Dh = q.shape
        if fused_attention_applicable(B, H, T, Dh, q.dtype):
            # fused Pallas path: O(T) HBM traffic (ops/pallas_attention.py)
            out = flash_attention(q, k, v, causal=self.causal, key_mask=mask)
        else:
            out = attention(q, k, v, causal=self.causal, key_mask=mask)
        out = out.transpose(0, 2, 1, 3).reshape(B, T, H * Dh)
        return self.project_output(params, out), state
