"""Token sampling, jit-carried PRNG: greedy / temperature / top-k.

Runs INSIDE the compiled prefill/decode programs — per-request temperature
and top-k are runtime arrays, so changing them never recompiles, and the
PRNG key threads through the programs as a carried device array (split
in-program; the host never touches randomness on the decode path).

Greedy (temperature <= 0) is ``argmax`` over the model-dtype logits — the
exact comparison the naive full-recompute reference makes, which is what
lets the bit-exactness pin hold in bf16 as well as f32.

The sampler does the work its batch asks for. The float32 scaling, the
top-k threshold, the mask and the categorical draw sit under a
``lax.cond`` on "some row has a temperature": a batch of greedy rows runs
the ``argmax`` and the key's split and nothing else. Where a row does
sample, its top-k threshold is the k-th largest scaled logit found by
exact selection (:func:`kth_largest`: 32 counting passes over the row, no
sort), so the mask, and with it the draw under the same key, is the one a
descending sort of the whole vocabulary gives.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ...ops.kth_largest import kth_largest  # noqa: F401  (its older home)

def sample_tokens(logits, key, temperature, top_k):
    """logits [N,V] (pre-activation, model dtype); temperature [N] f32
    (<=0 -> greedy); top_k [N] int32 (<=0 -> full vocab). Returns
    (tokens [N] int32, new key). The key is split whether or not any row
    samples, so the carried key does not depend on who shares the batch."""
    V = logits.shape[-1]
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    key, sub = jax.random.split(key)

    def draw():
        scaled = logits.astype(jnp.float32) \
            / jnp.maximum(temperature, 1e-6)[:, None]
        kk = jnp.clip(jnp.where(top_k <= 0, V, top_k), 1, V)
        masked = jnp.where(scaled >= kth_largest(scaled, kk), scaled,
                           -jnp.inf)
        sampled = jax.random.categorical(sub, masked, axis=-1)
        return jnp.where(temperature <= 0.0, greedy,
                         sampled.astype(jnp.int32))

    return lax.cond(jnp.any(temperature > 0.0), draw, lambda: greedy), key
