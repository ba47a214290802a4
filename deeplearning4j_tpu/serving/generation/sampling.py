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
import numpy as np
from jax import lax

_SIGN = np.int32(-2 ** 31)
_REST = np.int32(2 ** 31 - 1)


def _ordered(bits):
    """float32 bit patterns (as int32) <-> int32 keys that compare as the
    floats do (-0.0 just under +0.0): negatives have every bit but the
    sign flipped. Its own inverse."""
    return jnp.where(bits < 0, bits ^ _REST, bits)


def kth_largest(x, k):
    """x [N,V] float32, k [N] int32 in 1..V -> [N,1] float32: per row the
    k-th largest element of ``x``, exactly (an element of the row, ties
    counted as often as they occur).

    The answer's key is built from the top bit down: a bit stays set if at
    least ``k`` of the row's keys are no smaller than the candidate. One
    compare and one row sum over [N,V] a bit. The candidate is kept with
    its sign bit flipped (``t``), which makes its unsigned order the
    keys' signed one."""
    keys = _ordered(lax.bitcast_convert_type(x, jnp.int32))

    def one_bit(i, t):
        cand = t | lax.shift_right_logical(_SIGN, jnp.int32(i))
        enough = jnp.sum(keys >= (cand ^ _SIGN)[:, None], axis=-1,
                         dtype=jnp.int32) >= k
        return jnp.where(enough, cand, t)

    t = lax.fori_loop(0, 32, one_bit, jnp.zeros(x.shape[:1], jnp.int32))
    return lax.bitcast_convert_type(_ordered(t ^ _SIGN), jnp.float32)[:, None]


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
