"""serving/generation/sampling: the sampler does the work its batch asks
for, and what it emits is the old sampler's (ISSUE 39).

``_sample_by_sort`` is the sampler as it stood before: a descending sort
of the whole vocabulary for the top-k threshold and a categorical draw for
every row, greedy rows included. ``sample_tokens`` runs the draw under a
``lax.cond`` on "a row has a temperature" and finds the threshold by exact
selection; every case below holds it to the old one EXACTLY, tokens and
returned key.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.serving.generation.sampling import (kth_largest,
                                                            sample_tokens)


def _sample_by_sort(logits, key, temperature, top_k):
    V = logits.shape[-1]
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    key, sub = jax.random.split(key)
    lf = logits.astype(jnp.float32)
    scaled = lf / jnp.maximum(temperature, 1e-6)[:, None]
    kk = jnp.clip(jnp.where(top_k <= 0, V, top_k), 1, V)
    sorted_desc = -jnp.sort(-scaled, axis=-1)
    thr = jnp.take_along_axis(sorted_desc, (kk - 1)[:, None], axis=-1)
    masked = jnp.where(scaled >= thr, scaled, -jnp.inf)
    sampled = jax.random.categorical(sub, masked, axis=-1).astype(jnp.int32)
    return jnp.where(temperature <= 0.0, greedy, sampled), key


_OLD, _NEW = jax.jit(_sample_by_sort), jax.jit(sample_tokens)
_KEYS = (0, 39, 2 ** 31 - 5)

# (rows, vocabulary, dtype of the logits): GPT-2's and LFM2's
# vocabularies at the cells' slot counts, one row alone, and vocabularies
# under one lane tile
_SHAPES = [(16, 50257, "bfloat16"), (1, 50257, "float32"),
           (32, 65536, "bfloat16"), (16, 65536, "float32"),
           (16, 100, "float32"), (32, 100, "bfloat16"), (1, 7, "float32")]


def _logits(N, V, seed):
    r = np.random.default_rng(seed)
    return (r.normal(size=(N, V)) * 3).astype(np.float32), r


def _rows(N, V, values):
    """[N] from a cycle of ``values``; V and V + 7 by name."""
    named = {"V": V, "V+7": V + 7}
    return np.array([named.get(v, v) for v in
                     (values * (N // len(values) + 1))[:N]])


def _case(name, N, V):
    """-> (logits [N,V] float32 before the cast, temperature, top_k)."""
    x, r = _logits(N, V, seed=len(name) * 1000 + N + V)
    temp = np.full(N, 0.7, np.float32)
    topk = np.zeros(N, np.int32)
    if name == "all_greedy":
        temp[:] = 0.0
        topk = _rows(N, V, [0, 4, "V+7"])
    elif name == "top_k_per_row":
        temp = _rows(N, V, [0.3, 1.0, 2.5]).astype(np.float32)
        topk = _rows(N, V, [0, 1, 4, 50, "V", "V+7", 2, -3])
    elif name.startswith("top_k_"):
        k = name[len("top_k_"):]
        topk = _rows(N, V, [k if k.startswith("V") else int(k)])
    elif name == "mixed_greedy_and_sampled":
        temp = _rows(N, V, [0.0, 0.9, -1.0, 0.0, 1.3]).astype(np.float32)
        if N == 1:
            temp[:] = 0.9
        topk = _rows(N, V, [40, 0, 3])
    elif name == "ties_at_threshold":
        # the value at the k-th place repeated on both sides of it, so
        # that more than k elements survive the mask
        k = min(4, V - 2)
        order = np.argsort(-x, axis=-1)
        tie = np.take_along_axis(x, order[:, k - 1:k], axis=-1)
        np.put_along_axis(x, order[:, max(k - 2, 0):k + 3], tie, axis=-1)
        topk[:] = k
    elif name == "signed_zeros_at_threshold":
        # k - 1 positives, then +0.0 and -0.0 beside each other at the
        # k-th place, the rest negative: -0.0 >= +0.0 keeps both
        k = min(5, V - 1)
        x = -np.abs(x) - 1.0
        x[:, :k - 1] = np.abs(x[:, :k - 1])
        x[:, k - 1:k + 3] = np.array([0.0, -0.0, -0.0, 0.0],
                                     np.float32)[:x[:, k - 1:k + 3].shape[1]]
        x = np.take_along_axis(x, r.permuted(
            np.tile(np.arange(V), (N, 1)), axis=-1), axis=-1)
        topk = _rows(N, V, [k, k + 1])
    elif name == "all_negative":
        x = -np.abs(x) - 0.5
        topk = _rows(N, V, [0, 3, 50])
    elif name == "rows_with_neg_inf":
        # a banned half of the vocabulary; top_k reaches into it on
        # every third row (the threshold is then -inf itself)
        x[:, r.permutation(V)[:V // 2]] = -np.inf
        topk = _rows(N, V, [2, 0, "V"])
    elif name == "tiny_temperature":
        temp[:] = 1e-7                      # under the 1e-6 clamp
        temp[::2] = 1e-6
        topk = _rows(N, V, [0, 50])
    elif name == "hot_temperature":
        temp[:] = 50.0
    else:
        raise AssertionError(name)
    return x, temp, np.asarray(topk, np.int32)


_CASES = ["all_greedy", "top_k_0", "top_k_1", "top_k_4", "top_k_50",
          "top_k_V", "top_k_V+7", "top_k_per_row",
          "mixed_greedy_and_sampled", "ties_at_threshold",
          "signed_zeros_at_threshold", "all_negative", "rows_with_neg_inf",
          "tiny_temperature", "hot_temperature"]


@pytest.mark.parametrize("case", _CASES)
@pytest.mark.parametrize("N,V,dtype", _SHAPES,
                         ids=[f"{n}x{v}-{d}" for n, v, d in _SHAPES])
def test_sample_tokens_is_the_sort_based_sampler(N, V, dtype, case):
    x, temp, topk = _case(case, N, V)
    logits = jnp.asarray(x).astype(dtype)
    # three keys at a vocabulary that sorts fast; at the cells' one key a
    # case, and the cases walk through the three
    keys = _KEYS if V < 128 else (_KEYS[_CASES.index(case) % 3],)
    for seed in keys:
        key = jax.random.PRNGKey(seed)
        want, want_key = _OLD(logits, key, temp, topk)
        got, got_key = _NEW(logits, key, temp, topk)
        assert got.dtype == jnp.int32 and got.shape == (N,)
        np.testing.assert_array_equal(got, want)
        np.testing.assert_array_equal(got_key, want_key)
        assert not np.array_equal(got_key, key)
    if case == "all_greedy":
        np.testing.assert_array_equal(got, np.argmax(
            np.asarray(logits.astype(jnp.float32)), axis=-1))
    if case == "top_k_1":
        # one survivor: the draw is the argmax whatever the key (where
        # the maximum is not tied, as a bfloat16 row's can be)
        lf = np.asarray(logits.astype(jnp.float32))
        single = (lf == lf.max(-1, keepdims=True)).sum(-1) == 1
        np.testing.assert_array_equal(np.asarray(got)[single],
                                      lf.argmax(-1)[single])


def test_a_sampled_row_does_not_move_its_greedy_neighbours():
    """Rows are drawn independently: what a greedy row and a sampled row
    emit, and the key carried on, are the same whoever shares the batch."""
    x, _ = _logits(8, 100, seed=5)
    key = jax.random.PRNGKey(11)
    zi = np.zeros(8, np.int32)
    all_greedy, key_g = _NEW(x, key, np.zeros(8, np.float32), zi)
    one = np.zeros(8, np.float32)
    one[3] = 1.0
    one_sampled, key_1 = _NEW(x, key, one, zi)
    every, key_a = _NEW(x, key, np.ones(8, np.float32), zi)
    keep = np.arange(8) != 3
    np.testing.assert_array_equal(np.asarray(one_sampled)[keep],
                                  np.asarray(all_greedy)[keep])
    assert int(one_sampled[3]) == int(every[3])
    np.testing.assert_array_equal(key_g, key_1)
    np.testing.assert_array_equal(key_g, key_a)


# ------------------------------------------------------- the selection
def _selection_rows(kind, V, r):
    x = (r.normal(size=(200, V)) * 4).astype(np.float32)
    if kind == "ties":
        x = np.round(x)                       # about 30 distinct values
        x[x == 0] = r.choice(np.array([0.0, -0.0], np.float32),
                             size=int((x == 0).sum()))
    elif kind == "infinities_and_denormals":
        x[r.random(x.shape) < 0.2] = -np.inf
        x[r.random(x.shape) < 0.01] = np.inf
        tiny = r.random(x.shape) < 0.05
        x[tiny] = (x[tiny] * 1e-42).astype(np.float32)
    elif kind == "one_value":
        x[:] = -2.5
    elif kind != "normal":
        raise AssertionError(kind)
    return x


@pytest.mark.parametrize("kind", ["normal", "ties",
                                  "infinities_and_denormals", "one_value"])
@pytest.mark.parametrize("V", [50257, 100])
def test_kth_largest_is_partitions_kth_largest(V, kind):
    """200 random (row, k), k over the whole of 1..V, both ends included:
    the threshold is the element ``np.partition`` puts at the k-th place
    from the top, to the bit (the sign of a zero aside, which no float
    comparison reads)."""
    r = np.random.default_rng(V + len(kind))
    x = _selection_rows(kind, V, r)
    k = r.integers(1, V + 1, size=200).astype(np.int32)
    k[:4] = [1, V, 2, V - 1]
    thr = np.asarray(jax.jit(kth_largest)(x, k))
    assert thr.shape == (200, 1) and thr.dtype == np.float32
    want = np.array([np.partition(row, V - kk)[V - kk]
                     for row, kk in zip(x, k)])
    np.testing.assert_array_equal(thr[:, 0], want)
    # and as many elements reach it as the sort's threshold lets through
    kept = (x >= thr).sum(-1)
    assert (kept >= k).all()
    assert ((x > thr).sum(-1) < k).all()
