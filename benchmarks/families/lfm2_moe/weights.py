"""LFM2-MoE weights from a seed: made on the device in one jitted call a
leaf, in the type they are served in. Keys are ``<vertex>/<param>`` in the
names ``build.py`` gives its vertices (plain strings: nothing of the
program is imported).

Scales: matrices normal with 1/sqrt(fan-in) (an RMS norm before every
matrix keeps its input near unit variance, so outputs are near unit
variance too), residual projections (``W_out``, ``Wo``, the MLPs' ``W2``)
divided by sqrt(2 x layers), gains 1 + 0.02 noise, taps normal 0.5, the
embedding normal 1.0 and the head normal 1/sqrt(d).

The router: ``Wg`` normal 1/sqrt(d), so the scores before the sigmoid have a
standard deviation of 1 over tokens (the 4 chosen of 64 score 0.82-0.92),
and the selection bias ``b`` normal 0.02 over experts: about the distance
between neighbouring scores near the top, so it tilts the choice and does
not make it. That leaves the loads uneven and no expert empty: over a
prefill of 8,192 tokens the fullest expert of a layer gets about 1.7
times the mean and the emptiest a third of it, and a decode step of 32
slots touches about 86% of a layer's experts (reckoned from the scales on
the host; the chip's readings are in ``PERF.md``). A first try (1.5 and
0.15; my chip run, PR 38) let the bias make the choice: the fullest expert
got 8.5 times the mean, a decode step touched 48% and some experts got
nothing.
"""
from __future__ import annotations

import gc
from functools import partial
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from benchmarks.families.gpt2.weights import key_from_seed  # noqa: F401


def shapes(cfg: Dict) -> Dict[str, Tuple[int, ...]]:
    V, d = cfg["vocab_size"], cfg["hidden_size"]
    Dh = cfg.get("head_dim") or d // cfg["num_attention_heads"]
    dkv = cfg["num_key_value_heads"] * Dh
    E, Fe, Fd = (cfg["num_experts"], cfg["moe_intermediate_size"],
                 cfg["intermediate_size"])
    K = cfg["conv_L_cache"]
    s = {"embed/W": (V, d), "norm_f/gain": (d,), "head/W": (d, V),
         "head/b": (V,)}
    for i, kind in enumerate(cfg["layer_types"]):
        b = f"l{i}_"
        s.update({b + "norm1/gain": (d,), b + "norm2/gain": (d,)})
        if kind == "conv":
            s.update({b + "mixer/W_in": (d, 3 * d), b + "mixer/k": (d, K),
                      b + "mixer/W_out": (d, d)})
        else:
            s.update({b + "mixer/Wq": (d, d), b + "mixer/Wk": (d, dkv),
                      b + "mixer/Wv": (d, dkv), b + "mixer/Wo": (d, d),
                      b + "mixer/q_gain": (Dh,), b + "mixer/k_gain": (Dh,)})
        if i < cfg["num_dense_layers"]:
            s.update({b + "ffn/W1": (d, Fd), b + "ffn/W3": (d, Fd),
                      b + "ffn/W2": (Fd, d)})
        else:
            s.update({b + "ffn/Wg": (d, E), b + "ffn/bias": (E,),
                      b + "ffn/W1": (E, d, Fe), b + "ffn/W3": (E, d, Fe),
                      b + "ffn/W2": (E, Fe, d)})
    return s


def _std(name: str, shape, n_layers: int) -> Tuple[float, float]:
    """(mean, standard deviation) of a leaf."""
    leaf = name.split("/")[1]
    resid = (2.0 * n_layers) ** -0.5
    if leaf in ("gain", "q_gain", "k_gain"):
        return 1.0, 0.02
    if name == "head/b":
        return 0.0, 0.0                    # the family has no bias anywhere
    if name == "embed/W":
        return 0.0, 1.0
    if leaf == "k":
        return 0.0, 0.5
    if leaf == "bias":
        return 0.0, 0.02
    fan_in = shape[-2]
    if leaf == "Wg":
        return 0.0, fan_in ** -0.5
    if leaf in ("W_out", "Wo", "W2"):
        return 0.0, resid * fan_in ** -0.5
    return 0.0, fan_in ** -0.5


@partial(jax.jit, static_argnames=("shape", "mean", "std", "dtype"))
def _leaf(key, shape, mean, std, dtype):
    if std == 0.0:
        return jnp.full(shape, mean, dtype)
    return (mean + std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def make(cfg: Dict, seed: int, role: str) -> Dict[str, jax.Array]:
    """``role`` is ``train`` or ``serve``: the type the program keeps them
    in. One jitted call a leaf, so that the float32 draw of the largest
    (an expert matrix of 64 x 2,048 x 1,536) is the only float32 copy
    alive. The harness makes the weights a second time for the reference
    once the window has closed and it has dropped the engine: the engine
    and its net sit in reference cycles, so what they held (10.6 GB at the
    published widths, which does not fit twice) is collected first."""
    gc.collect()
    s = shapes(cfg)
    key = key_from_seed(seed)
    dtype = cfg["precision"][role]["dtype"]
    n_layers = len(cfg["layer_types"])
    out = {}
    for i, name in enumerate(sorted(s)):
        mean, std = _std(name, s[name], n_layers)
        out[name] = _leaf(jax.random.fold_in(key, i), s[name], mean, std,
                          dtype)
    return out
