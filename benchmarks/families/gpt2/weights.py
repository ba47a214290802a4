"""GPT-2 weights from a seed: made on the device in one jitted call, in
the type they are served in. Keys are ``<vertex>/<param>`` in the names
``models.transformer_lm`` gives its vertices (plain strings: nothing of
the program is imported). GPT-2's own init (normal 0.02, residual
projections scaled by 1/sqrt(2L)) with small noise on biases and gains so
that every leaf takes part in the comparison with the reference."""
from __future__ import annotations

from functools import partial
from typing import Dict, Tuple

import jax
import jax.numpy as jnp


def shapes(cfg: Dict) -> Dict[str, Tuple[int, ...]]:
    V, d, L, T = cfg["vocab_size"], cfg["n_embd"], cfg["n_layer"], cfg["n_ctx"]
    s = {"embed/W": (V, d), "pos/P": (T, d), "ln_f/gain": (d,),
         "ln_f/bias": (d,), "head/W": (d, V), "head/b": (V,)}
    for i in range(L):
        b = f"b{i}_"
        s.update({b + "ln1/gain": (d,), b + "ln1/bias": (d,),
                  b + "attn/Wq": (d, d), b + "attn/Wk": (d, d),
                  b + "attn/Wv": (d, d), b + "attn/Wo": (d, d),
                  b + "attn/b": (d,), b + "ln2/gain": (d,),
                  b + "ln2/bias": (d,), b + "ff1/W": (d, 4 * d),
                  b + "ff1/b": (4 * d,), b + "ff2/W": (4 * d, d),
                  b + "ff2/b": (d,)})
    return s


def key_from_seed(seed: int):
    """Any whole number (the driver's seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


@partial(jax.jit, static_argnames=("names", "shape_list", "n_layer", "dtype"))
def _make(key, names, shape_list, n_layer, dtype):
    out = {}
    resid = 0.02 / (2.0 * n_layer) ** 0.5
    for i, (name, shape) in enumerate(zip(names, shape_list)):
        k = jax.random.fold_in(key, i)
        leaf = name.split("/")[1]
        if leaf == "gain":
            x = 1.0 + 0.02 * jax.random.normal(k, shape, jnp.float32)
        elif name.endswith("attn/Wo") or name.endswith("ff2/W"):
            x = resid * jax.random.normal(k, shape, jnp.float32)
        else:
            x = 0.02 * jax.random.normal(k, shape, jnp.float32)
        out[name] = x.astype(dtype)
    return out


def make(cfg: Dict, seed: int, role: str) -> Dict[str, jax.Array]:
    """``role`` is ``train`` or ``serve``: the type the program keeps them in."""
    s = shapes(cfg)
    names = tuple(sorted(s))
    return _make(key_from_seed(seed), names, tuple(s[n] for n in names),
                 cfg["n_layer"], cfg["precision"][role]["dtype"])
