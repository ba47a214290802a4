"""Laguna-family weights from a seed: made on the device in one jitted call
a leaf, in the type they are served in. Keys are ``<vertex>/<param>`` in
the names ``build.py`` gives its vertices (plain strings: nothing of the
program is imported).

Scales as ``families/lfm2_moe/weights.py`` has them and for its reasons:
matrices normal with 1/sqrt(fan-in), residual projections (``Wo``, the
MLPs' and the experts' ``W2``) divided by sqrt(2 x layers), gains 1 + 0.02
noise, the embedding normal 1.0, the head normal 1/sqrt(d). The attention
output gate ``attn/Wg`` normal 1/sqrt(d): gates of 0.27-0.73.

The router ``ffn/Wg`` normal ``ROUTER_SCALE``/sqrt(d), so that the logits
have a spread of ``ROUTER_SCALE`` over tokens. With 256 sigmoid scores the
8th and 9th lie closest together in SCORE where the logits' spread is far
from 1 either way (a wider spread pushes the top scores into the
sigmoid's flat end, a narrower one packs the logits): reckoned from the
normal order statistics, the mean gap is 0.0056 at 0.5, 0.0064 at 1 and
0.0026 at 2. It stays at 1. The selection bias is held at ZERO: the
configuration names none (``assumed.router``)."""
from __future__ import annotations

import gc
from typing import Dict, Tuple

import jax

from benchmarks.families.gpt2.weights import key_from_seed
from benchmarks.families.lfm2_moe.weights import _leaf, _std

ROUTER_SCALE = 1.0


def shapes(cfg: Dict) -> Dict[str, Tuple[int, ...]]:
    V, d, Dh = cfg["vocab_size"], cfg["hidden_size"], cfg["head_dim"]
    dkv = cfg["num_key_value_heads"] * Dh
    E, Fe, Fd, Fs = (cfg["num_experts"], cfg["moe_intermediate_size"],
                     cfg["intermediate_size"],
                     cfg["shared_expert_intermediate_size"])
    s = {"embed/W": (V, d), "norm_f/gain": (d,), "head/W": (d, V),
         "head/b": (V,)}
    for i in range(cfg["num_hidden_layers"]):
        b = f"l{i}_"
        H = cfg["num_attention_heads_per_layer"][i]
        s.update({b + "norm1/gain": (d,), b + "norm2/gain": (d,),
                  b + "attn/Wq": (d, H * Dh), b + "attn/Wk": (d, dkv),
                  b + "attn/Wv": (d, dkv), b + "attn/Wo": (H * Dh, d)})
        if cfg["gating"]:
            s[b + "attn/Wg"] = (d, H)
        if cfg["mlp_layer_types"][i] == "dense":
            s.update({b + "ffn/W1": (d, Fd), b + "ffn/W3": (d, Fd),
                      b + "ffn/W2": (Fd, d)})
        else:
            s.update({b + "ffn/Wg": (d, E), b + "ffn/bias": (E,),
                      b + "ffn/W1": (E, d, Fe), b + "ffn/W3": (E, d, Fe),
                      b + "ffn/W2": (E, Fe, d),
                      b + "shared/W1": (d, Fs), b + "shared/W3": (d, Fs),
                      b + "shared/W2": (Fs, d)})
    return s


def make(cfg: Dict, seed: int, role: str) -> Dict[str, jax.Array]:
    """``role`` is ``train`` or ``serve``: the type the program keeps them
    in. One jitted call a leaf. The harness makes the weights a second
    time for the reference once it has dropped the engine, which sits in
    reference cycles: what it held (7.7 GB at the published widths beside
    6 GB of pools and rings) is collected first."""
    gc.collect()
    s = shapes(cfg)
    key = key_from_seed(seed)
    dtype = cfg["precision"][role]["dtype"]
    out = {}
    for i, name in enumerate(sorted(s)):
        mean, std = _std(name, s[name], cfg["num_hidden_layers"])
        if name.endswith("ffn/bias"):
            std = 0.0                     # no selection bias in this family
        elif name.endswith("ffn/Wg"):
            std *= ROUTER_SCALE
        out[name] = _leaf(jax.random.fold_in(key, i), s[name], mean, std,
                          dtype)
    return out
