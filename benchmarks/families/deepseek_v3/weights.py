"""DeepSeek-V3-family weights from a seed: made on the device in one jitted
call a leaf, in the type they are served in. Keys are ``<vertex>/<param>``
in the names ``build.py`` gives its vertices (plain strings: nothing of the
program is imported).

Scales as ``families/lfm2_moe/weights.py`` has them and for its reasons:
matrices normal with 1/sqrt(fan-in), residual projections (``Wo``, the
MLPs' and the experts' ``W2``) divided by sqrt(2 x layers), gains 1 + 0.02
noise, the embedding normal 1.0, the head normal 1/sqrt(d); the router
``Wg`` normal 1/sqrt(d) and the selection bias normal 0.02, which tilts
the choice of the 6 of 128 and does not make it.
"""
from __future__ import annotations

import gc
from typing import Dict, Tuple

import jax

from benchmarks.families.gpt2.weights import key_from_seed
from benchmarks.families.lfm2_moe.weights import _leaf, _std


def shapes(cfg: Dict) -> Dict[str, Tuple[int, ...]]:
    V, d, H = cfg["vocab_size"], cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv, r = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"], cfg["kv_lora_rank"])
    E, Fe, Fd = (cfg["n_routed_experts"], cfg["moe_intermediate_size"],
                 cfg["intermediate_size"])
    Fs = cfg["n_shared_experts"] * Fe
    s = {"embed/W": (V, d), "norm_f/gain": (d,), "head/W": (d, V),
         "head/b": (V,)}
    for i in range(cfg["num_hidden_layers"]):
        b = f"l{i}_"
        s.update({b + "norm1/gain": (d,), b + "norm2/gain": (d,),
                  b + "attn/Wq": (d, H * (dn + dr)),
                  b + "attn/Wkva": (d, r + dr), b + "attn/kv_gain": (r,),
                  b + "attn/Wkvb": (r, H * (dn + dv)),
                  b + "attn/Wo": (H * dv, d)})
        if i < cfg["first_k_dense_replace"]:
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
    reference cycles: what it held (7.6 GB at the published widths beside
    a 6 GB pool) is collected first."""
    gc.collect()
    s = shapes(cfg)
    key = key_from_seed(seed)
    dtype = cfg["precision"][role]["dtype"]
    out = {}
    for i, name in enumerate(sorted(s)):
        mean, std = _std(name.replace("kv_gain", "gain"), s[name],
                         cfg["num_hidden_layers"])
        out[name] = _leaf(jax.random.fold_in(key, i), s[name], mean, std,
                          dtype)
    return out
