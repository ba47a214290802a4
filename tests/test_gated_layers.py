"""The layers the hybrid convolution / attention / mixture-of-experts models
brought (ISSUE 38): RMS norm, gated MLP, gated short convolution,
grouped-query attention with a q/k norm and rotary positions, the
mixture-of-experts layer and the grouped gated matmul under it. Toy widths,
float32, CPU."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn.conf import serde
from deeplearning4j_tpu.nn.conf.config import NeuralNetConfiguration
from deeplearning4j_tpu.nn.inputs import InputType
from deeplearning4j_tpu.nn.layers import (GatedMLP, GatedShortConvLayer,
                                          MixtureOfExpertsLayer, RMSNorm,
                                          RnnOutputLayer, SelfAttentionLayer)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.ops import grouped_matmul as gm
from deeplearning4j_tpu.ops import pallas_paged_attention as ppa

f32 = jnp.float32
D = 32

LAYERS = {
    "rms_norm": lambda: RMSNorm(eps=1e-5),
    "gated_mlp": lambda: GatedMLP(n_hidden=48),
    "gated_short_conv": lambda: GatedShortConvLayer(kernel=3),
    "grouped_query_attention": lambda: SelfAttentionLayer(
        n_out=D, n_heads=4, n_kv_heads=2, causal=True, qk_norm=True,
        rope_theta=1e6, bias=False),
    "mixture_of_experts": lambda: MixtureOfExpertsLayer(
        n_experts=8, top_k=2, n_hidden=16),
    "mixture_of_experts_held_half": lambda: MixtureOfExpertsLayer(
        n_experts=8, top_k=2, n_hidden=16, held=(4, 4)),
}


def _net(layer):
    conf = (NeuralNetConfiguration(seed=3, dtype="float32")
            .list(layer, RnnOutputLayer(n_out=5, activation="softmax",
                                        loss="mcxent"))
            .set_input_type(InputType.recurrent(D, 12)).build())
    return MultiLayerNetwork(conf)


@pytest.mark.parametrize("name", sorted(LAYERS))
def test_layer_round_trips_and_takes_one_finite_fit_step(name):
    """Registered for serde (the configuration that comes back builds the
    same parameters) and usable by ``fit``: one step, a finite score, every
    parameter moved or held finite."""
    layer = LAYERS[name]()
    back = serde.from_json(serde.to_json(layer))
    assert type(back) is type(layer)
    for f in dataclasses.fields(layer):
        a, b = getattr(layer, f.name), getattr(back, f.name)
        assert (list(a) if isinstance(a, tuple) else a) == \
            (list(b) if isinstance(b, (tuple, list)) else b), f.name
    net = _net(layer).init()
    again = _net(back).init()
    assert jax.tree.map(lambda a: a.shape, net.params) == \
        jax.tree.map(lambda a: a.shape, again.params)
    rng = np.random.default_rng(0)
    x = rng.standard_normal((4, 12, D)).astype(np.float32)
    y = np.eye(5, dtype=np.float32)[rng.integers(0, 5, (4, 12))]
    before = jax.tree.map(np.asarray, net.params)
    net.fit(x, y, epochs=1, batch_size=4)
    assert np.isfinite(net.score(x, y))
    moved = jax.tree.map(lambda a, b: float(np.abs(np.asarray(a) - b).max()),
                         net.params, before)
    assert all(np.isfinite(v) for v in jax.tree.leaves(moved))
    assert max(jax.tree.leaves(moved[0])) > 0      # the new layer learns


def test_rms_norm_is_the_formula():
    layer = RMSNorm(eps=1e-5)
    p, _ = layer.init(jax.random.PRNGKey(0), InputType.recurrent(D, 4), f32)
    p = {"gain": jnp.linspace(0.5, 1.5, D)}
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 4, D)) * 3.0
    y, _ = layer.apply(p, {}, x)
    want = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-5) * p["gain"]
    np.testing.assert_allclose(y, want, rtol=1e-6, atol=1e-6)


def _conv():
    layer = GatedShortConvLayer(kernel=3)
    p, _ = layer.init(jax.random.PRNGKey(0), InputType.recurrent(D, 9), f32)
    return layer, p


def test_short_convolution_is_causal_and_its_state_carries_it():
    """The whole sequence at once equals the sequence fed in pieces with
    the state carried, a row never sees a later one, and the state is the
    last two rows of z = B * X."""
    layer, p = _conv()
    x = jax.random.normal(jax.random.PRNGKey(2), (3, 9, D))
    whole, final = layer.apply_with_final_state(p, {}, x)
    # causal: changing the last rows leaves the earlier ones alone
    other, _ = layer.apply_with_final_state(p, {}, x.at[:, 5:].set(0.0))
    np.testing.assert_array_equal(whole[:, :5], other[:, :5])
    # carried: 4 rows, then one row at a time
    out, st = layer.apply_with_final_state(p, {}, x[:, :4])
    outs = [out]
    for t in range(4, 9):
        o, st = layer.apply_with_final_state(p, {}, x[:, t:t + 1],
                                             initial_state=st)
        outs.append(o)
    np.testing.assert_allclose(jnp.concatenate(outs, 1), whole, rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(st, final, rtol=1e-6, atol=1e-7)
    b, _, xx = jnp.split(x @ p["W_in"], 3, axis=-1)
    np.testing.assert_allclose(final, (b * xx)[:, -2:], rtol=1e-6, atol=1e-7)


def test_short_convolution_state_at_each_sequences_own_length():
    """``state_at`` reads the state at ``lengths``, not at the padded end:
    equal to running each sequence cut to its length, zero rows where the
    sequence had not begun."""
    layer, p = _conv()
    x = jax.random.normal(jax.random.PRNGKey(3), (4, 9, D))
    lengths = jnp.asarray([9, 4, 1, 2], jnp.int32)
    got = layer.state_at(p, x, lengths)
    for i, n in enumerate([9, 4, 1, 2]):
        _, want = layer.apply_with_final_state(p, {}, x[i:i + 1, :n])
        np.testing.assert_allclose(got[i], want[0], rtol=1e-6, atol=1e-7)
    assert not np.any(np.asarray(got[2, 0]))       # length 1: row -1 is zero
    assert np.any(np.asarray(got[2, 1]))


def _reference_gqa(layer, p, x):
    """Grouped-query attention written out: per-head RMS norm on q and k,
    rotate-half rotary positions, query head i reading key-value head
    i // group."""
    B, T, _ = x.shape
    H, Hkv, Dh = layer.n_heads, layer.kv_heads, layer.head_dim
    q = (x @ p["Wq"]).reshape(B, T, H, Dh)
    k = (x @ p["Wk"]).reshape(B, T, Hkv, Dh)
    v = (x @ p["Wv"]).reshape(B, T, Hkv, Dh)

    def norm(a, g):
        return a / jnp.sqrt(jnp.mean(a * a, -1, keepdims=True) + 1e-5) * g

    def rope(a):
        half = Dh // 2
        inv = layer.rope_theta ** (-jnp.arange(half) / half)
        ang = jnp.arange(T)[:, None, None] * inv
        a1, a2 = a[..., :half], a[..., half:]
        return jnp.concatenate([a1 * jnp.cos(ang) - a2 * jnp.sin(ang),
                                a2 * jnp.cos(ang) + a1 * jnp.sin(ang)], -1)

    q, k = rope(norm(q, p["q_gain"])), rope(norm(k, p["k_gain"]))
    out = []
    for h in range(H):
        s = jnp.einsum("btd,bsd->bts", q[:, :, h], k[:, :, h // (H // Hkv)])
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s / np.sqrt(Dh),
                      -jnp.inf)
        out.append(jnp.einsum("bts,bsd->btd", jax.nn.softmax(s, -1),
                              v[:, :, h // (H // Hkv)]))
    return jnp.concatenate(out, -1) @ p["Wo"]


def test_grouped_query_attention_is_the_equations():
    layer = LAYERS["grouped_query_attention"]()
    p, _ = layer.init(jax.random.PRNGKey(0), InputType.recurrent(D, 10), f32)
    assert p["Wk"].shape == (D, 16) and "b" not in p
    p = dict(p, q_gain=jnp.linspace(0.8, 1.2, 8), k_gain=jnp.linspace(1.1, 0.9, 8))
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 10, D))
    with jax.default_matmul_precision("highest"):
        got, _ = layer.apply(p, {}, x)
        want = _reference_gqa(layer, p, x)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


def test_plain_attention_keeps_its_parameters_and_path():
    """Without the new options the layer is what it was: equal heads, a
    bias on the output projection, no gains."""
    layer = SelfAttentionLayer(n_out=D, n_heads=4, causal=True)
    p, _ = layer.init(jax.random.PRNGKey(0), InputType.recurrent(D, 6), f32)
    assert sorted(p) == ["Wk", "Wo", "Wq", "Wv", "b"]
    assert layer.kv_heads == layer.n_heads and layer.rope_theta is None
    assert p["Wk"].shape == (D, D)


@pytest.mark.parametrize("W", [1, 3])
def test_paged_attention_kernel_reads_grouped_heads(W):
    """8 query heads over 2 key-value heads: the kernel (interpreter here)
    against the gathered reference; the pool holds 2 heads."""
    rng = np.random.default_rng(W)
    S, Hq, H, Dh, blk, mb, L = 4, 8, 2, 64, 16, 4, 2
    lens = jnp.asarray([0, 1, 17, mb * blk - W], jnp.int32)
    nb = S * mb + 1
    k_pool, v_pool = (jnp.asarray(rng.standard_normal(
        (L, nb, blk, H * Dh)) * 0.5, f32) for _ in range(2))
    tables = jnp.asarray(
        1 + rng.permutation(nb - 1)[:S * mb].reshape(S, mb), jnp.int32)
    q = jnp.asarray(rng.standard_normal((S, Hq, W, Dh)) * 0.5, f32)
    with jax.default_matmul_precision("highest"):
        got = ppa.paged_attention_decode(q, k_pool, v_pool, 1, tables, lens)
        want = ppa.paged_attention_reference(q, k_pool, v_pool, 1, tables,
                                             lens)
    assert got.shape == (S, Hq, W, Dh)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


# ------------------------------------------------------------ the experts
def _experts(seed=0, N=40, d=128, F=256, E=8, k=2):
    rng = np.random.default_rng(seed)
    mk = lambda *s: jnp.asarray(rng.standard_normal(s) * 0.1, f32)
    x = jnp.asarray(rng.standard_normal((N, d)), f32)
    idx = jnp.asarray(np.stack([rng.permutation(E)[:k] for _ in range(N)]),
                      jnp.int32)
    w = jnp.asarray(rng.random((N, k)), f32)
    return x, idx, w, mk(E, d, F), mk(E, d, F), mk(E, F, d)


@pytest.mark.parametrize("path", ["ragged_dot", "pallas_interpreter"])
@pytest.mark.parametrize("N", [3, 40, 200])
def test_grouped_matmul_is_the_sum_over_the_routed_pairs(path, N, monkeypatch):
    """Both implementations of ``expert_ffn`` against the dense sum over
    every expert masked to its pairs: few rows an expert (a decode step),
    many (a prefill), fewer rows than experts."""
    if path == "pallas_interpreter":
        monkeypatch.setenv("DL4J_TPU_KERNEL_MOE_EXPERTS_INTERPRET", "1")
    x, idx, w, W1, W3, W2 = _experts(N, N=N)
    assert gm.kernels_applicable(128, 256, f32) == (path != "ragged_dot")
    with jax.default_matmul_precision("highest"):
        got = gm.expert_ffn(x, idx, w, W1, W3, W2)
        want = gm.expert_ffn_reference(x, idx, w, W1, W3, W2)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_an_expert_nobody_chose_is_not_computed_and_adds_nothing(monkeypatch):
    """The kernels never fetch an expert without a pair: its weights may
    hold anything. (``ragged_dot`` on the CPU is a masked dense product,
    where NaN x 0 shows: the property is the kernels'.)"""
    monkeypatch.setenv("DL4J_TPU_KERNEL_MOE_EXPERTS_INTERPRET", "1")
    x, idx, w, W1, W3, W2 = _experts(5)
    idx = idx % 3                       # experts 3..7 get no pair
    poisoned = [W.at[3:].set(jnp.nan) for W in (W1, W3, W2)]
    with jax.default_matmul_precision("highest"):
        got = gm.expert_ffn(x, idx, w, *poisoned)
        want = gm.expert_ffn_reference(x, idx, w, W1[:3], W3[:3], W2[:3])
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_row_tiles_follow_the_pairs_an_expert_gets():
    assert gm.row_tile(32 * 4, 64) == 16            # a decode step
    assert gm.row_tile(512 * 4, 64) == 32
    assert gm.row_tile(8192 * 4, 64) == 256         # the largest prefill


def _moe(held=None, E=8, k=2, d=32, F=16):
    layer = MixtureOfExpertsLayer(n_experts=E, top_k=k, n_hidden=F, held=held)
    p, _ = layer.init(jax.random.PRNGKey(1), InputType.recurrent(d, 6), f32)
    return layer, p


def test_the_eight_shares_add_up_to_the_whole_layer_and_the_reference():
    """Eight layers that hold one expert each (of 8; the published model's
    eight shares hold 8 of 64) route over all experts, compute their own
    part and leave the rest out: the parts add up to the layer that holds
    all, and to the benchmark's uncut reference equations."""
    whole, p = _moe()
    p = dict(p, bias=jnp.linspace(-0.2, 0.2, 8),
             Wg=p["Wg"] * 4.0)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 6, 32))
    with jax.default_matmul_precision("highest"):
        want, _ = whole.apply(p, {}, x)
        total = 0.0
        for e in range(8):
            share, _ = _moe(held=(e, 1))
            part, _ = share.apply(
                dict(p, W1=p["W1"][e:e + 1], W3=p["W3"][e:e + 1],
                     W2=p["W2"][e:e + 1]), {}, x)
            total = total + part
        # the equations, written out
        flat = x.reshape(-1, 32)
        s = jax.nn.sigmoid(flat @ p["Wg"])
        _, chosen = jax.lax.top_k(s + p["bias"], 2)
        sc = jnp.take_along_axis(s, chosen, -1)
        wts = sc / (sc.sum(-1, keepdims=True) + 1e-6)
        ref = jnp.zeros_like(flat)
        for n in range(flat.shape[0]):
            for j in range(2):
                e = int(chosen[n, j])
                h = jax.nn.silu(flat[n] @ p["W1"][e]) * (flat[n] @ p["W3"][e])
                ref = ref.at[n].add(wts[n, j] * (h @ p["W2"][e]))
    np.testing.assert_allclose(total, want, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(want.reshape(-1, 32), ref, rtol=1e-4,
                               atol=1e-6)


def test_the_selection_bias_moves_the_choice_and_not_the_weights():
    layer, p = _moe()
    x = jax.random.normal(jax.random.PRNGKey(6), (16, 32))
    idx0, w0 = layer.route(p, x)
    assert idx0.shape == (16, 2) and idx0.dtype == jnp.int32
    np.testing.assert_allclose(w0.sum(-1), 1.0, rtol=1e-5)
    pushed = dict(p, bias=p["bias"].at[7].set(10.0))
    idx1, w1 = layer.route(pushed, x)
    assert bool(jnp.all(idx1[:, 0] == 7))           # expert 7 always first
    s = jax.nn.sigmoid(x @ p["Wg"])
    got = jnp.take_along_axis(s, idx1, -1)
    np.testing.assert_allclose(w1, got / (got.sum(-1, keepdims=True) + 1e-6),
                               rtol=1e-5)           # weights ignore the bias


def test_held_must_be_a_range_of_the_experts():
    with pytest.raises(ValueError, match="not a range"):
        _moe(held=(6, 4))
