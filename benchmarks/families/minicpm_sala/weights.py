"""MiniCPM-SALA-family weights from a seed: made on the device in one
jitted call a leaf, in the type they are served in. Keys are
``<vertex>/<param>`` in the names ``build.py`` gives its vertices (plain
strings: nothing of the program is imported).

Every matrix is normal with 1/sqrt(fan-in), the residual projections too:
the model's own ``scale_depth / sqrt(32)`` on every mixer's and MLP's
output is its depth scaling, so nothing is divided by sqrt(2 x layers) as
the other families' weights are. The gains are 1 + 0.02 noise. The model
multiplies the embedding by ``scale_emb`` and divides the final norm's
output by ``hidden_size / dim_model_base``; the embedding is normal with
1/scale_emb and the head with (hidden_size / dim_model_base) /
sqrt(hidden_size), so that the residual stream starts at unit scale (a
lightning layer's or an MLP's output is then a tenth of it) and the logits
have unit variance, as in the other cells.

**The first ``minicpm4`` layer is made to bear on the logits**
(``SPARSE``): with q and k normed a head and gains of 1 a score ``q . k /
sqrt(128)`` has unit variance, a softmax over some 4,096 chosen random
keys is all but uniform, and the layer's output is a four-hundredth of the
residual stream, under bfloat16's own noise: the cell's ``correct`` could
not see the mechanism it exists for. So that layer's q and k gains are 2
(a score's standard deviation is 4: the largest of 4,096 keys takes a
third of a softmax, the sum of its squared weights is a fifth; a score
against a compressed key, the mean of 32, has 0.7) and its ``Wv`` and
``Wo`` are drawn twice as wide: the layer then adds a quarter of the
stream, twice what any other layer adds, and an attention that read only
the forced blocks moves a logit by tenths (``reference.py``'s second
control).

**Why the first alone.** Over random keys a block's score says little of
the weight its keys carry, so the blocks at the selection's cut are as
likely as any to hold a head's heaviest key, and bfloat16 hidden states
flip the cut at a third of the positions of the first layer (reckoned on
the CPU at T = 24,576: float32 against bfloat16 operands; a flip moves
that layer's output by 7% at the median and by 60% at most). The
comparison therefore leaves out the positions whose margin at the cut is
under the cell's ``routing_margin``: on the chip no flip that moved a
logit lay past a margin of 0.0011, and 0.004 keeps a tenth of the
positions (``limits/minicpm-sala-serve-longctx.reasons.txt``). The LAST layer's input carries seven layers of bfloat16
noise: its cut flips at 40% of the positions and a margin that no flip
passes keeps under a hundredth, so a second bearing layer would leave
nothing to compare. It keeps gains of 1 and plain widths: its flips move
nothing, and ``correct`` sees the mechanism (the selection, both kernels,
the compressed rows, prefill and decode) through the first layer."""
from __future__ import annotations

import gc
from typing import Dict, Tuple

import jax

from benchmarks.families.gpt2.weights import key_from_seed
from benchmarks.families.lfm2_moe.weights import _leaf


def shapes(cfg: Dict) -> Dict[str, Tuple[int, ...]]:
    V, d, F = cfg["vocab_size"], cfg["hidden_size"], cfg["intermediate_size"]
    Dh = cfg["head_dim"]
    dq, dkv = cfg["num_attention_heads"] * Dh, cfg["num_key_value_heads"] * Dh
    Dl = cfg["lightning_head_dim"]
    dl = cfg["lightning_nh"] * Dl
    s = {"embed/W": (V, d), "norm_f/gain": (d,), "head/W": (d, V),
         "head/b": (V,)}
    for i, kind in enumerate(cfg["mixer_types"]):
        b = f"l{i}_"
        s.update({b + "norm1/gain": (d,), b + "norm2/gain": (d,),
                  b + "ffn/W1": (d, F), b + "ffn/W3": (d, F),
                  b + "ffn/W2": (F, d)})
        if kind == "minicpm4":
            s.update({b + "mixer/Wq": (d, dq), b + "mixer/Wk": (d, dkv),
                      b + "mixer/Wv": (d, dkv), b + "mixer/Wg": (d, dq),
                      b + "mixer/Wo": (dq, d), b + "mixer/q_gain": (Dh,),
                      b + "mixer/k_gain": (Dh,)})
        else:
            s.update({b + "mixer/Wq": (d, dl), b + "mixer/Wk": (d, dl),
                      b + "mixer/Wv": (d, dl), b + "mixer/Wz": (d, dl),
                      b + "mixer/Wo": (dl, d), b + "mixer/q_gain": (Dl,),
                      b + "mixer/k_gain": (Dl,), b + "mixer/o_gain": (dl,)})
    return s


# what makes the first ``bearing_layers`` minicpm4 layers' attention peaked and
# their output a quarter of the residual stream (the module's docstring)
SPARSE = {"bearing_layers": 1, "qk_gain": 2.0, "value_and_output": 2.0}


def _std(cfg: Dict, name: str, shape) -> Tuple[float, float]:
    """(mean, standard deviation) of a leaf."""
    vertex, leaf = name.split("/")
    kinds = cfg["mixer_types"]
    sparse = vertex.endswith("_mixer") and int(vertex[1:-6]) in [
        i for i, kind in enumerate(kinds) if kind == "minicpm4"
    ][:SPARSE["bearing_layers"]]
    if sparse and leaf in ("q_gain", "k_gain"):
        return SPARSE["qk_gain"], 0.02
    if sparse and leaf in ("Wv", "Wo"):
        return 0.0, SPARSE["value_and_output"] * shape[-2] ** -0.5
    if leaf.endswith("gain"):
        return 1.0, 0.02
    if name == "head/b":
        return 0.0, 0.0                    # the family has no bias anywhere
    if name == "embed/W":
        return 0.0, 1.0 / float(cfg["scale_emb"])
    if name == "head/W":
        return 0.0, (cfg["hidden_size"] / float(cfg["dim_model_base"])) \
            * shape[0] ** -0.5
    return 0.0, shape[-2] ** -0.5


def make(cfg: Dict, seed: int, role: str) -> Dict[str, jax.Array]:
    """``role`` is ``train`` or ``serve``: the type the program keeps them
    in. One jitted call a leaf. The harness makes the weights a second
    time for the reference once it has dropped the engine, which sits in
    reference cycles: what it held (5.6 GB at the published widths beside
    the pages and the states) is collected first."""
    gc.collect()
    s = shapes(cfg)
    key = key_from_seed(seed)
    dtype = cfg["precision"][role]["dtype"]
    out = {}
    for i, name in enumerate(sorted(s)):
        mean, std = _std(cfg, name, s[name])
        out[name] = _leaf(jax.random.fold_in(key, i), s[name], mean, std,
                          dtype)
    return out
