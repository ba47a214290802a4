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
import numpy as np

from ..conf.serde import register
from ..inputs import InputTypeRecurrent
from .base import LayerConf, maybe_dropout, resolve_ff_size


def rotate_half(x, positions, theta: float):
    """Rotary positions over the whole head, rotate-half form: x
    [B,T,H,Dh], positions [B,T]; frequencies ``theta^(-2i/Dh)`` made in
    the program, angles in float32."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, :, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :half], xf[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


@register
@dataclass
class SelfAttentionLayer(LayerConf):
    """Multi-head self-attention, [B,T,F] -> [B,T,n_out].

    ``n_out`` must be divisible by ``n_heads`` unless the heads have a size
    of their own (``head_size``). With ``causal`` each position attends
    only to itself and earlier steps. A [B,T] feature mask excludes padded
    timesteps as attention KEYS (queries at masked positions produce
    outputs that downstream masked losses ignore, matching the framework's
    masking convention).

    The fields below ``bias`` are off by default and leave a layer that
    sets none of them its parameters, its program and its numbers:

        q = x Wq -> [H, Dh];  k = x Wk, v = x Wv -> [Hkv, Dh]
        rotate the first ``rotary_dim`` values of each head of q and k
        o_h = softmax over the keys s with t - window < s <= t
        g = sigmoid(x Wg) -> [H]              (``head_gate``)
        out = concat_h(g_h o_h) Wo            ([H * Dh, n_out])

    ``out_gate``: the gate is as wide as the heads' output, ``sigmoid(x
    Wg) -> [H * Dh]``, one value a feature. ``sparse``: a block-sparse
    SELECTION (``ops/sparse_select.py``): a query position attends to the
    key blocks its own list names (the first, the nearest, and the best of
    the rest by its key-value group's scores against compressed keys);
    causal only, dense where the context is short.
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
    # a head size apart from ``n_out // n_heads``: the projections are then
    # ``n_in -> H x head_size`` (q), ``n_in -> Hkv x head_size`` (k, v) and
    # ``H x head_size -> n_out`` (Wo)
    head_size: Optional[int] = None
    # sliding window: position t sees the keys s with t - window < s <= t
    # (itself included); causal only
    window: Optional[int] = None
    # one sigmoid gate a head and token from the layer's input (``Wg:
    # n_in -> H``, no bias), on the head's output before ``Wo``
    head_gate: bool = False
    # rotate the first ``rotary_dim`` values of a head (rotate-half form
    # within them) and pass the rest through; None = the whole head
    rotary_dim: Optional[int] = None
    # how the rotation's frequencies are scaled; today the YaRN rule:
    # {"rope_type": "yarn", "factor", "original_max_position_embeddings",
    # "beta_fast", "beta_slow"[, "attention_factor"]}
    rope_scaling: Optional[dict] = None
    # one sigmoid gate a FEATURE of the heads' output (``Wg: n_in -> H x
    # head size``, no bias), before ``Wo``; not beside ``head_gate``
    out_gate: bool = False
    # block-sparse selection: the sizes of ``ops.sparse_select.Selection``
    # ({"block", "kernel", "stride", "topk", "init_blocks", "local_blocks",
    # "dense_len"}); None = every key a position may see
    sparse: Optional[dict] = None

    param_order: ClassVar[Tuple[str, ...]] = ("Wq", "Wk", "Wv", "Wo", "b",
                                              "q_gain", "k_gain", "Wg")
    weight_param_names: ClassVar[Tuple[str, ...]] = ("Wq", "Wk", "Wv", "Wo",
                                                     "Wg")
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
        return self.head_size or self.n_out // self.n_heads

    def init(self, rng, itype, dtype):
        n_in = self.n_in or resolve_ff_size(itype)
        self.n_in = n_in
        if self.head_size is None and self.n_out % self.n_heads:
            raise ValueError(f"n_out={self.n_out} must be divisible by "
                             f"n_heads={self.n_heads}")
        if self.n_heads % self.kv_heads:
            raise ValueError(f"n_heads={self.n_heads} must be divisible by "
                             f"n_kv_heads={self.kv_heads}")
        if self.window is not None and (self.window < 1 or not self.causal):
            raise ValueError("a sliding window is positive and causal")
        if (self.rotary_dim or 0) % 2 or \
                (self.rotary_dim or 0) > self.head_dim:
            raise ValueError(f"rotary_dim={self.rotary_dim} must be even "
                             f"and at most the head size {self.head_dim}")
        if self.rope_theta is not None:
            self.rope_frequencies()        # a rule it does not know: now
        elif self.rotary_dim or self.rope_scaling:
            raise ValueError("rotary_dim and rope_scaling need a rope_theta")
        if self.head_gate and self.out_gate:
            raise ValueError("head_gate and out_gate are two forms of one "
                             "gate: set one")
        if self.sparse is not None:
            if not self.causal or self.window is not None:
                raise ValueError("a block-sparse selection is causal and "
                                 "takes no sliding window")
            self.selection                 # sizes it cannot take: now
        ks = jax.random.split(rng, 5 if self.head_gate or self.out_gate
                              else 4)
        d = self.n_out
        dq = self.n_heads * self.head_dim  # == d unless head_size is set
        dkv = self.kv_heads * self.head_dim
        params = {
            "Wq": self._winit(ks[0], (n_in, dq), n_in, dq, dtype),
            "Wk": self._winit(ks[1], (n_in, dkv), n_in, dkv, dtype),
            "Wv": self._winit(ks[2], (n_in, dkv), n_in, dkv, dtype),
            "Wo": self._winit(ks[3], (dq, d), dq, d, dtype),
        }
        if self.bias:
            params["b"] = self._binit((d,), dtype)
        if self.qk_norm:
            params["q_gain"] = jnp.ones((self.head_dim,), dtype)
            params["k_gain"] = jnp.ones((self.head_dim,), dtype)
        if self.head_gate:
            params["Wg"] = self._winit(ks[4], (n_in, self.n_heads), n_in,
                                       self.n_heads, dtype)
        if self.out_gate:
            params["Wg"] = self._winit(ks[4], (n_in, dq), n_in, dq, dtype)
        return params, {}

    @property
    def selection(self):
        """The block-sparse selection's sizes, or None."""
        from ...ops.sparse_select import Selection
        return None if self.sparse is None else Selection.of(self.sparse)

    def rope_frequencies(self):
        """(inv_freq float32 numpy [rotated / 2], the factor on cos and
        sin): computed once, on the host, in float64 rounded to float32.
        The default is ``theta^(-2i/R)`` and 1; the YaRN rule blends, pair
        by pair, those frequencies with the same divided by ``factor``:
        pairs that turn more than ``beta_fast`` times over the original
        length keep theirs, pairs that turn fewer than ``beta_slow`` times
        are interpolated, a linear ramp between, and cos and sin carry
        ``0.1 ln(factor) + 1``."""
        R = self.rotary_dim or self.head_dim
        f = float(self.rope_theta) ** (-np.arange(R // 2, dtype=np.float64)
                                       / (R // 2))
        sc = self.rope_scaling
        if not sc:
            return f.astype(np.float32), 1.0
        kind = sc.get("rope_type", sc.get("type"))
        if kind != "yarn":
            raise ValueError(f"rope_scaling of type {kind!r} is not "
                             "supported (yarn is)")
        factor = float(sc["factor"])
        orig = float(sc["original_max_position_embeddings"])

        def turns_at(n_rot):             # the pair that turns n_rot times
            return R * np.log(orig / (n_rot * 2 * np.pi)) \
                / (2 * np.log(float(self.rope_theta)))
        low = max(int(np.floor(turns_at(float(sc.get("beta_fast", 32))))), 0)
        high = min(int(np.ceil(turns_at(float(sc.get("beta_slow", 1))))),
                   R - 1)
        ramp = np.clip((np.arange(R // 2, dtype=np.float64) - low)
                       / max(high - low, 1e-3), 0.0, 1.0)
        inv = ramp * f / factor + (1.0 - ramp) * f
        att = sc.get("attention_factor")
        att = 0.1 * np.log(factor) + 1.0 if att is None else float(att)
        return inv.astype(np.float32), float(att)

    def _rotate(self, x, positions):
        """Rotary positions, rotate-half form: x [B,T,H,Dh], positions
        [B,T]; angles in float32."""
        if self.rotary_dim is None and self.rope_scaling is None:
            # the default form as it always traced (the frequencies made in
            # the program): the accepted families' programs are held to it
            return rotate_half(x, positions, self.rope_theta)
        # part of the head, scaled frequencies, or both: rotate-half within
        # the first ``R`` values, the rest passes through
        R = self.rotary_dim or self.head_dim
        inv, factor = self.rope_frequencies()
        ang = positions.astype(jnp.float32)[:, :, None, None] * jnp.asarray(inv)
        cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
        xf = x.astype(jnp.float32)
        x1, x2 = xf[..., :R // 2], xf[..., R // 2:R]
        return jnp.concatenate(
            [x1 * cos - x2 * sin, x2 * cos + x1 * sin, xf[..., R:]],
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

    def project_output(self, params, out, x=None):
        """The heads' output [B,T,H*Dh] through Wo (and its bias) and the
        layer's activation. ``x`` [B,T,F], the layer's input, is what a
        ``head_gate`` is computed from."""
        if self.head_gate:
            g = jax.nn.sigmoid(x @ params["Wg"])               # [B,T,H]
            B, T, _ = out.shape
            out = (out.reshape(B, T, self.n_heads, self.head_dim)
                   * g[..., None]).reshape(B, T, -1)
        if self.out_gate:
            out = out * jax.nn.sigmoid(x @ params["Wg"])
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
        if self.window is not None:
            return self.project_output(
                params, self._windowed(q, k, v, train, mask), x), state
        sel = self.selection
        if sel is not None and x.shape[1] > sel.dense_len \
                and x.shape[1] > sel.topk * sel.block:
            # somewhere in the sequence a list is shorter than the context
            return self.project_output(
                params, self._selected(q, k, v, train, mask), x), state
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
        return self.project_output(params, out, x), state

    def _windowed(self, q, k, v, train, mask):
        """Attention under the sliding window: q [B,T,H,Dh], k/v
        [B,T,Hkv,Dh] -> [B,T,H*Dh]. The windowed flash kernel is forward
        only, takes no key mask and reads grouped key-value heads in
        place; a training step or a masked batch runs the XLA path with
        the window in its mask."""
        from ...ops.pallas_attention import (flash_attention,
                                             fused_attention_applicable)
        from ...parallel.ring_attention import attention
        q, k, v = (a.transpose(0, 2, 1, 3) for a in (q, k, v))
        B, H, T, Dh = q.shape
        if not train and mask is None and \
                fused_attention_applicable(B, H, T, Dh, q.dtype):
            out = flash_attention(q, k, v, causal=True, window=self.window)
        else:
            group = H // k.shape[1]
            if group > 1:
                k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
            out = attention(q, k, v, causal=True, key_mask=mask,
                            window=self.window)
        return out.transpose(0, 2, 1, 3).reshape(B, T, H * Dh)


    def _selected(self, q, k, v, train, mask):
        """Attention under the block-sparse selection: q [B,T,H,Dh], k/v
        [B,T,Hkv,Dh] -> [B,T,H*Dh]. The choice is made per query position
        (``chosen_mask``); the sparse flash kernel is forward only, takes no
        key mask and reads grouped key-value heads in place; a training
        step, a masked batch and the CPU run the XLA path under the same
        mask of chosen blocks."""
        from ...ops.pallas_attention import (flash_attention_sparse,
                                             fused_attention_applicable)
        from ...ops.sparse_select import (chosen_mask, compress_keys,
                                          sparse_attention_xla)
        sel = self.selection
        B, T, H, Dh = q.shape
        scale = float(Dh) ** -0.5
        pad = -T % sel.block
        kc = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else k
        chosen = chosen_mask(q, compress_keys(kc, sel), sel, scale)
        q, k, v = (a.transpose(0, 2, 1, 3) for a in (q, k, v))
        if not train and mask is None and T % sel.block == 0 and \
                fused_attention_applicable(B, H, T, Dh, q.dtype):
            out = flash_attention_sparse(q, k, v, chosen, scale=scale)
        else:
            out = sparse_attention_xla(q, k, v, chosen, sel, scale,
                                       key_mask=mask)
        return out.transpose(0, 2, 1, 3).reshape(B, T, H * Dh)


@register
@dataclass
class LightningAttentionLayer(LayerConf):
    """Lightning (decayed linear) attention, [B,T,F] -> [B,T,n_out]: a
    recurrent sequence mixer whose state is a MATRIX a head.

        q = rope(rms_head(x Wq)), k = rope(rms_head(x Wk)), v = x Wv
                                      each [H, Dh]; the norm before the
                                      rotation, the rotation over the whole
                                      head (rotate-half)
        S_t = lam_h S_{t-1} + k_t^T v_t      [Dh, Dh] float32, S_{-1} = 0
        o_t = q_t S_t / sqrt(Dh)
        y = (rms(o; o_gain) * sigmoid(x Wz)) Wo      the norm over all H x Dh

    ``lam_h = exp(-2^(-8 (h + 1) / H))``, the same in every layer. A
    sequence runs in chunks (``ops/pallas_linear_attention.py``: the Pallas
    kernel where it applies and the call is not training, else the XLA
    form), a decode step is the recurrence. The state is float32 whatever
    the model's dtype: ``zero_state`` says so, and a serving cache keeps it
    in a pool of its own kind. ``state_at`` gives the state at a row of
    each sequence's own (a padded prompt's true end)."""
    n_in: Optional[int] = None
    n_out: int = 0
    n_heads: int = 4
    head_size: Optional[int] = None
    norm_eps: float = 1e-6
    rope_theta: float = 10000.0

    param_order: ClassVar[Tuple[str, ...]] = ("Wq", "Wk", "Wv", "Wz", "Wo",
                                              "q_gain", "k_gain", "o_gain")
    weight_param_names: ClassVar[Tuple[str, ...]] = ("Wq", "Wk", "Wv", "Wz",
                                                     "Wo")
    expected_input: ClassVar[str] = "rnn"

    def output_type(self, itype):
        t = itype.timestep_length if isinstance(itype, InputTypeRecurrent) else -1
        return InputTypeRecurrent(self.n_out, t)

    @property
    def head_dim(self) -> int:
        return self.head_size or self.n_out // self.n_heads

    @property
    def scale(self) -> float:
        return float(self.head_dim) ** -0.5

    def slopes(self):
        from ...ops.pallas_linear_attention import slopes
        return slopes(self.n_heads)

    def init(self, rng, itype, dtype):
        n_in = self.n_in or resolve_ff_size(itype)
        self.n_in = n_in
        self.n_out = d = self.n_out or n_in
        if self.head_dim % 2:
            raise ValueError("the head size must be even (it rotates in "
                             "halves)")
        dq = self.n_heads * self.head_dim
        ks = jax.random.split(rng, 5)
        params = {n: self._winit(k, (n_in, dq), n_in, dq, dtype)
                  for n, k in zip(("Wq", "Wk", "Wv", "Wz"), ks)}
        params["Wo"] = self._winit(ks[4], (dq, d), dq, d, dtype)
        params["q_gain"] = jnp.ones((self.head_dim,), dtype)
        params["k_gain"] = jnp.ones((self.head_dim,), dtype)
        params["o_gain"] = jnp.ones((dq,), dtype)
        return params, {}

    def zero_state(self, batch: int, dtype=None):
        """[batch, H, Dh, Dh], float32 whatever ``dtype``."""
        return jnp.zeros((batch, self.n_heads, self.head_dim, self.head_dim),
                         jnp.float32)

    def project_qkv(self, params, x, positions=None):
        """x [B,T,F] -> q, k, v [B,T,H,Dh], q and k normed then rotated."""
        from .norm import rms_norm
        B, T, _ = x.shape
        q, k, v = ((x @ params[n]).reshape(B, T, self.n_heads, self.head_dim)
                   for n in ("Wq", "Wk", "Wv"))
        q = rms_norm(q, params["q_gain"], self.norm_eps)
        k = rms_norm(k, params["k_gain"], self.norm_eps)
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
        return (rotate_half(q, positions, self.rope_theta),
                rotate_half(k, positions, self.rope_theta), v)

    def project_output(self, params, o, x):
        """The heads' output [B,T,H*Dh] normed, gated by the layer's input
        and through Wo."""
        from .norm import rms_norm
        o = rms_norm(o, params["o_gain"], self.norm_eps)
        return self.act((o * jax.nn.sigmoid(x @ params["Wz"])) @ params["Wo"])

    def state_at(self, params, u, lengths):
        """The state after ``lengths`` [B] rows of u [B,T,d] (the layer's
        input): one contraction over T a head, the rows from a sequence's
        true end on at weight 0."""
        from ...ops.pallas_linear_attention import lightning_state_at
        _, k, v = self.project_qkv(params, u)
        return lightning_state_at(k, v, self.slopes(), lengths)

    def apply_with_final_state(self, params, state, x, *, train=False,
                               rng=None, mask=None, initial_state=None):
        """(out [B,T,d], the state after the last row [B,H,Dh,Dh]
        float32)."""
        from ...ops.pallas_linear_attention import lightning_attention_xla
        x = maybe_dropout(x, self.dropout, rng, train)
        B, T, _ = x.shape
        q, k, v = (a.transpose(0, 2, 1, 3)
                   for a in self.project_qkv(params, x))
        o, final = lightning_attention_xla(
            q, k, v, self.slopes(), scale=self.scale,
            initial_state=initial_state)
        o = o.transpose(0, 2, 1, 3).reshape(B, T, -1)
        return self.project_output(params, o, x), final

    def apply(self, params, state, x, *, train=False, rng=None):
        from ...ops.pallas_linear_attention import (
            lightning_attention_fwd, lightning_kernel_applicable)
        B, T, _ = x.shape
        if train or not lightning_kernel_applicable(
                T, self.head_dim, self.head_dim, x.dtype):
            out, _ = self.apply_with_final_state(params, state, x,
                                                 train=train, rng=rng)
            return out, state
        q, k, v = (a.transpose(0, 2, 1, 3)
                   for a in self.project_qkv(params, x))
        o = lightning_attention_fwd(q, k, v, self.slopes(), scale=self.scale)
        o = o.transpose(0, 2, 1, 3).reshape(B, T, -1)
        return self.project_output(params, o, x), state

    def decode_step(self, params, x, positions, pool, index: int, active):
        """One row a slot against the states a serving cache keeps: x
        [S,1,d] at ``positions`` [S,1]; ``pool`` [layers, slots + 1, H, Dh,
        Dh] float32, this layer's states at ``pool[index]``; ``active`` [S]
        (idle slots keep their state). Returns (out [S,1,d], the pool:
        updated in place on the TPU, so rebind it)."""
        from ...ops import pallas_linear_attention as la
        S = x.shape[0]
        q, k, v = (a[:, 0] for a in self.project_qkv(params, x, positions))
        step = la.lightning_decode_xla if la._interpret() \
            else la.lightning_decode
        o, pool = step(q, k, v, pool, index, active, self.slopes(),
                       scale=self.scale)
        return self.project_output(params, o.reshape(S, 1, -1), x), pool


@register
@dataclass
class LatentAttentionLayer(LayerConf):
    """Multi-head latent attention, [B,T,F] -> [B,T,n_out]: keys and values
    of every head are expanded from ONE low-rank latent a token, and a
    cache keeps that latent (normed) beside one rotated key part that all
    heads share, not the heads.

        q = x Wq                    -> heads x [q_nope | q_rope]
        [c' | r] = x Wkva;  c = rms(c'; kv_gain)
        [k_nope_h | v_h] = c Wkvb   -> heads x (qk_nope_dim + v_dim)
        s_h,t = (q_nope_h . k_nope_h,t + rope(q_rope_h) . rope(r_t)) * scale
        out = [softmax(s_h) v_h]_h Wo

    ``scale`` is 1 / sqrt(qk_nope_dim + qk_rope_dim). The rotation takes
    interleaved pairs (x_2i, x_2i+1); only ``q_rope . r`` enters a score,
    so the rotated halves are kept un-interleaved (first components, then
    second) on both sides. No bias anywhere.

    ``apply`` is this expanded form (flash attention with a score size of
    ``qk_nope_dim + qk_rope_dim`` and a value size of ``v_dim`` where it
    applies and the call is not training, else the XLA path). A decode
    step takes the ABSORBED form, the same numbers with ``Wkvb`` moved
    onto the query and the output so that attention runs over the cached
    rows themselves: ``cache_rows`` is what the cache keeps of a token
    (``[c | rope(r)]``, ``row_lanes`` wide), ``absorbed_queries`` the
    queries in the rows' space, ``unabsorb`` the way back to the heads'
    values."""
    n_in: Optional[int] = None
    n_out: int = 0
    n_heads: int = 4
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_dim: int = 128
    kv_rank: int = 512
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    causal: bool = True

    param_order: ClassVar[Tuple[str, ...]] = ("Wq", "Wkva", "kv_gain",
                                              "Wkvb", "Wo")
    weight_param_names: ClassVar[Tuple[str, ...]] = ("Wq", "Wkva", "Wkvb",
                                                     "Wo")
    expected_input: ClassVar[str] = "rnn"
    accepts_mask: ClassVar[bool] = True

    def output_type(self, itype):
        t = itype.timestep_length if isinstance(itype, InputTypeRecurrent) else -1
        return InputTypeRecurrent(self.n_out, t)

    @property
    def qk_dim(self) -> int:
        return self.qk_nope_dim + self.qk_rope_dim

    @property
    def scale(self) -> float:
        return float(self.qk_dim) ** -0.5

    @property
    def row_width(self) -> int:
        """Values a cache row carries: the latent and the shared key part."""
        return self.kv_rank + self.qk_rope_dim

    @property
    def row_lanes(self) -> int:
        """A cache row as laid out: padded with zeros to whole 128-lane
        tiles (576 -> 640). The TPU's compiler lays a 576-wide row out on
        640 lanes whatever the shape says, and Mosaic refuses a DMA of a
        partial tile, so the padding is in the shape, where the bytes can
        be counted."""
        return -(-self.row_width // 128) * 128

    def init(self, rng, itype, dtype):
        n_in = self.n_in or resolve_ff_size(itype)
        self.n_in = n_in
        self.n_out = d = self.n_out or n_in
        if self.qk_rope_dim % 2:
            raise ValueError("qk_rope_dim must be even (it rotates in pairs)")
        H, r = self.n_heads, self.kv_rank
        nq, nkva = H * self.qk_dim, r + self.qk_rope_dim
        nkvb, no = H * (self.qk_nope_dim + self.v_dim), H * self.v_dim
        ks = jax.random.split(rng, 4)
        return {"Wq": self._winit(ks[0], (n_in, nq), n_in, nq, dtype),
                "Wkva": self._winit(ks[1], (n_in, nkva), n_in, nkva, dtype),
                "kv_gain": jnp.ones((r,), dtype),
                "Wkvb": self._winit(ks[2], (r, nkvb), r, nkvb, dtype),
                "Wo": self._winit(ks[3], (no, d), no, d, dtype)}, {}

    def _rotate(self, x, positions):
        """Rotary positions over interleaved pairs: x [B,T,...,R] with
        positions [B,T]; pair i is (x_2i, x_2i+1) at angle ``pos *
        theta^(-2i/R)``; returns [first components | second components].
        Angles in float32."""
        half = x.shape[-1] // 2
        inv = self.rope_theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
        ang = positions.astype(jnp.float32).reshape(
            positions.shape + (1,) * (x.ndim - 2)) * inv
        cos, sin = jnp.cos(ang), jnp.sin(ang)
        xf = x.astype(jnp.float32)
        x1, x2 = xf[..., 0::2], xf[..., 1::2]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                               axis=-1).astype(x.dtype)

    @staticmethod
    def _positions(x, positions):
        B, T = x.shape[:2]
        return jnp.broadcast_to(jnp.arange(T)[None], (B, T)) \
            if positions is None else positions

    def project_q(self, params, x, positions=None):
        """x [B,T,F] -> (q_nope [B,T,H,qk_nope_dim], q_rope
        [B,T,H,qk_rope_dim] rotated)."""
        B, T, _ = x.shape
        q = (x @ params["Wq"]).reshape(B, T, self.n_heads, self.qk_dim)
        return q[..., :self.qk_nope_dim], self._rotate(
            q[..., self.qk_nope_dim:], self._positions(x, positions))

    def latent(self, params, x, positions=None):
        """x [B,T,F] -> (c [B,T,kv_rank] the normed latent, r
        [B,T,qk_rope_dim] the rotated key part all heads share)."""
        from .norm import rms_norm
        cr = x @ params["Wkva"]
        c = rms_norm(cr[..., :self.kv_rank], params["kv_gain"], self.norm_eps)
        return c, self._rotate(cr[..., self.kv_rank:],
                               self._positions(x, positions))

    def _on_lanes(self, x):
        """[..., row_width] -> [..., row_lanes], zeros behind the values."""
        pad = [(0, 0)] * (x.ndim - 1) + [(0, self.row_lanes - self.row_width)]
        return jnp.pad(x, pad)

    def cache_rows(self, params, x, positions=None):
        """What a cache keeps of each token: [B,T,1,row_lanes] = [c | r |
        zeros], one "head" of ``row_lanes`` as a paged pool takes it."""
        c, r = self.latent(params, x, positions)
        return self._on_lanes(jnp.concatenate([c, r], axis=-1))[:, :, None, :]

    def _wkvb(self, params):
        """Wkvb by head: (W_UK [r,H,qk_nope_dim], W_UV [r,H,v_dim])."""
        w = params["Wkvb"].reshape(self.kv_rank, self.n_heads,
                                   self.qk_nope_dim + self.v_dim)
        return w[..., :self.qk_nope_dim], w[..., self.qk_nope_dim:]

    def absorbed_queries(self, params, x, positions=None):
        """x [B,T,F] -> [B,T,H,row_lanes]: each head's query in the cache
        rows' space, ``[W_UK,h^T q_nope_h | q_rope_h | zeros]``, so that
        its dot with a cache row is the head's score before the scale."""
        q_nope, q_rope = self.project_q(params, x, positions)
        w_uk, _ = self._wkvb(params)
        return self._on_lanes(jnp.concatenate(
            [jnp.einsum("bthn,rhn->bthr", q_nope, w_uk), q_rope], axis=-1))

    def unabsorb(self, params, o):
        """o [B,T,H,kv_rank], each head's softmax-weighted sum of latents
        -> the heads' values [B,T,H*v_dim]."""
        _, w_uv = self._wkvb(params)
        out = jnp.einsum("bthr,rhv->bthv", o, w_uv)
        return out.reshape(out.shape[:2] + (self.n_heads * self.v_dim,))

    def project_output(self, params, out):
        """The heads' values [B,T,H*v_dim] through Wo and the activation."""
        return self.act(out @ params["Wo"])

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        from ...ops.pallas_attention import (flash_attention,
                                             fused_attention_applicable)
        from ...parallel.ring_attention import attention
        x = maybe_dropout(x, self.dropout, rng, train)
        B, T, _ = x.shape
        H = self.n_heads
        q_nope, q_rope = self.project_q(params, x)
        c, r = self.latent(params, x)
        kv = (c @ params["Wkvb"]).reshape(B, T, H,
                                          self.qk_nope_dim + self.v_dim)
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        k = jnp.concatenate(
            [kv[..., :self.qk_nope_dim],
             jnp.broadcast_to(r[:, :, None, :], (B, T, H, self.qk_rope_dim))],
            axis=-1)
        v = kv[..., self.qk_nope_dim:]
        q, k, v = (a.transpose(0, 2, 1, 3) for a in (q, k, v))
        # the kernels' backward pass takes one head size: training this
        # layer runs the XLA path
        fused = not train and fused_attention_applicable(
            B, H, T, self.qk_dim, q.dtype, self.v_dim)
        out = (flash_attention if fused else attention)(
            q, k, v, causal=self.causal, scale=self.scale, key_mask=mask)
        out = out.transpose(0, 2, 1, 3).reshape(B, T, H * self.v_dim)
        return self.project_output(params, out), state
