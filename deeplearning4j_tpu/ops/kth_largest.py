"""Exact selection without a sort: the k-th largest element of every row.

A sort of a row of a few hundred to a hundred thousand values is slow on
the TPU (a top-k of 64 out of 512 over 65,536 rows took 98 ms where the
scores it chose among took 7: ``PERF.md``, PR 46); a threshold found bit by
bit is 32 compares and row sums. The serving sampler's top-k mask
(``serving/generation/sampling.py``, PR 39) and the block-sparse
selection's choice (``ops/sparse_select.py``) both cut at it.
"""
from __future__ import annotations

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
