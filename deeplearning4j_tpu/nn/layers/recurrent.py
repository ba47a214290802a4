"""Recurrent layers: LSTM, GravesLSTM (peepholes), GravesBidirectionalLSTM.

Reference: nn/layers/recurrent/LSTMHelpers.java (fwd time loop :184, gemm
:201-207, bwd loop :466), nn/conf/layers/GravesLSTM.java:47 (peephole
connections, forgetGateBiasInit, gateActivationFn sigmoid default),
GravesBidirectionalLSTM.java (fwd+bwd outputs SUMMED, activateOutput).

TPU-first: the time loop is ONE ``lax.scan`` — the input projection
x @ W for ALL timesteps is hoisted out of the scan as a single [B*T, 4H]
matmul (MXU-shaped), only the recurrent h @ R matmul lives in the carry loop.
Masking multiplies state updates so padded steps carry state through
unchanged (the reference zeroes activations via maskArray; carrying state is
equivalent for right-padded sequences and keeps rnn_time_step consistent).

Layout: [B, T, F] (batch-major; the reference uses [B, F, T] NCW).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, ClassVar, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..conf.serde import register
from ..activations import get_activation
from ..inputs import InputTypeRecurrent
from .base import LayerConf, maybe_dropout


def _lstm_scan(x_proj, h0, c0, R, act, gate_act, peepholes=None, mask=None,
               reverse=False, activation_names=("", "")):
    """Run an LSTM over time: fused Pallas kernel when applicable, else scan.

    x_proj: [T, B, 4H] precomputed input projections (+bias).
    Gate order along the 4H axis: [i, f, o, g].
    peepholes: None or (p_i, p_f, p_o) each [H] (Graves variant).
    mask: [T, B, 1] or None.
    activation_names: (activation, gate_activation) strings for the fused-path
    probe. Returns h sequence [T, B, H] and final (h, c).

    The fused path is the reference's accelerated-helper seam
    (ConvolutionLayer.java:72 reflection probe for cuDNN) done the TPU way:
    ops/pallas_lstm.py pins the recurrent matrix in VMEM across the whole
    time loop.
    """
    H = h0.shape[-1]
    from ...ops.pallas_lstm import (fused_lstm, fused_lstm_applicable,
                                    fused_lstm_peephole)
    # probe with reverse=False: THIS dispatcher implements reverse by
    # flipping inputs/outputs around the forward-only kernels
    if fused_lstm_applicable(h0.shape[0], H, x_proj.dtype,
                             peepholes=peepholes, mask=mask, reverse=False,
                             activation=activation_names[0],
                             gate_activation=activation_names[1]):
        m2d = None if mask is None else mask[:, :, 0].astype(x_proj.dtype)
        if reverse:
            # a reverse LSTM is a forward LSTM over the flipped sequence
            # (the backward half of GravesBidirectionalLSTM)
            x_proj = jnp.flip(x_proj, 0)
            m2d = None if m2d is None else jnp.flip(m2d, 0)
        if peepholes is not None:
            hs, final = fused_lstm_peephole(x_proj, h0, c0, R, *peepholes,
                                            mask=m2d)
        else:
            hs, final = fused_lstm(x_proj, h0, c0, R, mask=m2d)
        return (jnp.flip(hs, 0) if reverse else hs), final

    def step(carry, inp):
        h_prev, c_prev = carry
        xp, m = inp
        gates = xp + h_prev @ R
        zi, zf, zo, zg = (gates[..., :H], gates[..., H:2 * H],
                          gates[..., 2 * H:3 * H], gates[..., 3 * H:])
        if peepholes is not None:
            p_i, p_f, p_o = peepholes
            zi = zi + c_prev * p_i
            zf = zf + c_prev * p_f
        i = gate_act(zi)
        f = gate_act(zf)
        g = act(zg)
        c = f * c_prev + i * g
        if peepholes is not None:
            zo = zo + c * p_o
        o = gate_act(zo)
        h = o * act(c)
        if m is not None:
            h = m * h + (1 - m) * h_prev
            c = m * c + (1 - m) * c_prev
        return (h, c), h

    ms = mask if mask is not None else jnp.ones((x_proj.shape[0], 1, 1), x_proj.dtype)
    (hT, cT), hs = lax.scan(step, (h0, c0), (x_proj, ms), reverse=reverse)
    return hs, (hT, cT)


@register
@dataclass
class LSTM(LayerConf):
    """Standard LSTM without peepholes (reference nn/conf/layers/LSTM.java)."""
    n_in: Optional[int] = None
    n_out: int = 0
    forget_gate_bias_init: float = 1.0
    gate_activation: str = "sigmoid"

    param_order: ClassVar[Tuple[str, ...]] = ("W", "R", "b")
    weight_param_names: ClassVar[Tuple[str, ...]] = ("W", "R")
    expected_input: ClassVar[str] = "rnn"
    accepts_mask: ClassVar[bool] = True
    has_peepholes: ClassVar[bool] = False

    def __post_init__(self):
        if self.activation is None:
            self.activation = "tanh"

    def output_type(self, itype):
        return InputTypeRecurrent(self.n_out, getattr(itype, "timestep_length", -1))

    def init(self, rng, itype, dtype):
        n_in = self.n_in or itype.size
        H = self.n_out
        k1, k2 = jax.random.split(rng)
        W = self._winit(k1, (n_in, 4 * H), n_in, H, dtype)
        R = self._winit(k2, (H, 4 * H), H, H, dtype)
        b = jnp.zeros((4 * H,), dtype)
        # forget-gate bias init (reference forgetGateBiasInit default 1.0)
        b = b.at[H:2 * H].set(jnp.asarray(self.forget_gate_bias_init, dtype))
        params = {"W": W, "R": R, "b": b}
        if self.has_peepholes:
            params.update({"pi": jnp.zeros((H,), dtype),
                           "pf": jnp.zeros((H,), dtype),
                           "po": jnp.zeros((H,), dtype)})
        return params, {}

    def _peepholes(self, params):
        return (params["pi"], params["pf"], params["po"]) if self.has_peepholes else None

    def apply(self, params, state, x, *, train=False, rng=None, mask=None,
              initial_state=None):
        x = maybe_dropout(x, self.dropout, rng, train)
        B, T, _ = x.shape
        H = self.n_out
        act = get_activation(self.activation or "tanh")
        gate_act = get_activation(self.gate_activation)
        # hoist the input projection out of the scan: one big MXU matmul
        x_proj = (x @ params["W"] + params["b"]).transpose(1, 0, 2)  # [T,B,4H]
        if initial_state is not None:
            h0, c0 = initial_state
        else:
            h0 = jnp.zeros((B, H), x.dtype)
            c0 = jnp.zeros((B, H), x.dtype)
        m = None if mask is None else mask.astype(x.dtype).T[..., None]  # [T,B,1]
        hs, (hT, cT) = _lstm_scan(x_proj, h0, c0, params["R"], act, gate_act,
                                  self._peepholes(params), m,
                                  activation_names=(self.activation or "tanh",
                                                    self.gate_activation))
        out = hs.transpose(1, 0, 2)  # [B,T,H]
        return out, state

    def apply_with_final_state(self, params, state, x, *, train=False, rng=None,
                               mask=None, initial_state=None):
        """Like apply but also returns (h_T, c_T) — used by tBPTT and
        rnn_time_step (reference RecurrentLayer rnnTimeStep/tBpttState APIs)."""
        x = maybe_dropout(x, self.dropout, rng, train)
        B, T, _ = x.shape
        H = self.n_out
        act = get_activation(self.activation or "tanh")
        gate_act = get_activation(self.gate_activation)
        x_proj = (x @ params["W"] + params["b"]).transpose(1, 0, 2)
        if initial_state is None:
            initial_state = (jnp.zeros((B, H), x.dtype), jnp.zeros((B, H), x.dtype))
        m = None if mask is None else mask.astype(x.dtype).T[..., None]
        hs, final = _lstm_scan(x_proj, initial_state[0], initial_state[1],
                               params["R"], act, gate_act,
                               self._peepholes(params), m,
                               activation_names=(self.activation or "tanh",
                                                 self.gate_activation))
        return hs.transpose(1, 0, 2), final


@register
@dataclass
class GravesLSTM(LSTM):
    """LSTM with peephole connections (reference GravesLSTM.java:47,
    LSTMHelpers peephole terms)."""
    param_order: ClassVar[Tuple[str, ...]] = ("W", "R", "b", "pi", "pf", "po")
    has_peepholes: ClassVar[bool] = True


@register
@dataclass
class GravesBidirectionalLSTM(LayerConf):
    """Bidirectional Graves LSTM; forward and backward outputs are SUMMED
    (reference GravesBidirectionalLSTM.activateOutput 'sum outputs')."""
    n_in: Optional[int] = None
    n_out: int = 0
    forget_gate_bias_init: float = 1.0
    gate_activation: str = "sigmoid"

    param_order: ClassVar[Tuple[str, ...]] = ("Wf", "Rf", "bf", "pif", "pff", "pof",
                                              "Wb", "Rb", "bb", "pib", "pfb", "pob")
    weight_param_names: ClassVar[Tuple[str, ...]] = ("Wf", "Rf", "Wb", "Rb")
    expected_input: ClassVar[str] = "rnn"
    accepts_mask: ClassVar[bool] = True

    def __post_init__(self):
        if self.activation is None:
            self.activation = "tanh"

    def output_type(self, itype):
        return InputTypeRecurrent(self.n_out, getattr(itype, "timestep_length", -1))

    def init(self, rng, itype, dtype):
        n_in = self.n_in or itype.size
        H = self.n_out
        keys = jax.random.split(rng, 4)
        params = {}
        for d, (kw, kr) in zip("fb", [(keys[0], keys[1]), (keys[2], keys[3])]):
            W = self._winit(kw, (n_in, 4 * H), n_in, H, dtype)
            R = self._winit(kr, (H, 4 * H), H, H, dtype)
            b = jnp.zeros((4 * H,), dtype).at[H:2 * H].set(
                jnp.asarray(self.forget_gate_bias_init, dtype))
            params.update({f"W{d}": W, f"R{d}": R, f"b{d}": b,
                           f"pi{d}": jnp.zeros((H,), dtype),
                           f"pf{d}": jnp.zeros((H,), dtype),
                           f"po{d}": jnp.zeros((H,), dtype)})
        return params, {}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        x = maybe_dropout(x, self.dropout, rng, train)
        B, T, _ = x.shape
        H = self.n_out
        act = get_activation(self.activation or "tanh")
        gate_act = get_activation(self.gate_activation)
        m = None if mask is None else mask.astype(x.dtype).T[..., None]
        outs = []
        for d, reverse in (("f", False), ("b", True)):
            x_proj = (x @ params[f"W{d}"] + params[f"b{d}"]).transpose(1, 0, 2)
            h0 = jnp.zeros((B, H), x.dtype)
            c0 = jnp.zeros((B, H), x.dtype)
            peep = (params[f"pi{d}"], params[f"pf{d}"], params[f"po{d}"])
            hs, _ = _lstm_scan(x_proj, h0, c0, params[f"R{d}"], act, gate_act,
                               peep, m, reverse=reverse,
                               activation_names=(self.activation or "tanh",
                                                 self.gate_activation))
            outs.append(hs.transpose(1, 0, 2))
        return outs[0] + outs[1], state


@register
@dataclass
class LastTimeStepLayer(LayerConf):
    """[B,T,F] -> [B,F] (reference recurrent/LastTimeStep wrapper semantics)."""
    expected_input: ClassVar[str] = "rnn"
    accepts_mask: ClassVar[bool] = True

    def output_type(self, itype):
        from ..inputs import InputTypeFeedForward
        return InputTypeFeedForward(itype.size)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        if mask is not None:
            idx = jnp.maximum(jnp.sum(mask.astype(jnp.int32), axis=1) - 1, 0)
            return x[jnp.arange(x.shape[0]), idx], state
        return x[:, -1], state
