"""Ring attention (sequence parallelism) + SelfAttentionLayer — net-new
long-context capability (SURVEY.md §5.7: shardable sequence axis)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from deeplearning4j_tpu import (InputType, MultiLayerNetwork,
                                NeuralNetConfiguration)
from deeplearning4j_tpu.nn.layers.attention import SelfAttentionLayer
from deeplearning4j_tpu.nn.layers import RnnOutputLayer
from deeplearning4j_tpu.optimize.updaters import Adam, Sgd
from deeplearning4j_tpu.parallel.mesh import make_mesh
from deeplearning4j_tpu.parallel.ring_attention import (attention,
                                                        ring_attention_sharded,
                                                        sequence_sharding)
from deeplearning4j_tpu.util.gradcheck import check_gradients

R = np.random.default_rng(41)


def _qkv(B=2, H=2, T=16, D=8):
    return (jnp.asarray(R.normal(size=(B, H, T, D)).astype(np.float32)),
            jnp.asarray(R.normal(size=(B, H, T, D)).astype(np.float32)),
            jnp.asarray(R.normal(size=(B, H, T, D)).astype(np.float32)))


def test_reference_attention_is_softmax():
    q, k, v = _qkv(T=6)
    out = attention(q, k, v)
    s = np.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(8)
    p = np.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    want = np.einsum("bhqk,bhkd->bhqd", p, v)
    np.testing.assert_allclose(np.asarray(out), want, atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_full_attention(causal):
    """The 8-device ring with online softmax must equal single-device full
    attention on the gathered sequence."""
    mesh = make_mesh((8,), ("seq",))
    q, k, v = _qkv(B=2, H=2, T=32, D=8)
    want = np.asarray(attention(q, k, v, causal=causal))
    fn = ring_attention_sharded(mesh, "seq", causal=causal)
    sh = sequence_sharding(mesh, "seq")
    qs, ks, vs = (jax.device_put(t, sh) for t in (q, k, v))
    got = np.asarray(jax.device_get(fn(qs, ks, vs)))
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_ring_attention_memory_layout_stays_sharded():
    mesh = make_mesh((8,), ("seq",))
    fn = ring_attention_sharded(mesh, "seq")
    sh = sequence_sharding(mesh, "seq")
    q, k, v = _qkv(T=64)
    out = fn(*(jax.device_put(t, sh) for t in (q, k, v)))
    assert out.sharding.spec == P(None, None, "seq", None)


def test_self_attention_layer_gradients():
    conf = (NeuralNetConfiguration(seed=3, updater=Sgd(0.1), dtype="float64")
            .list(SelfAttentionLayer(n_in=4, n_out=8, n_heads=2,
                                     activation="identity"),
                  RnnOutputLayer(n_out=2, activation="softmax", loss="mcxent"))
            .set_input_type(InputType.recurrent(4, 5)).build())
    net = MultiLayerNetwork(conf).init()
    x = R.normal(size=(3, 5, 4))
    y = np.eye(2)[(x.sum(-1) > 0).astype(int)]
    assert check_gradients(net, x, y, subset=120, print_results=True)


def test_self_attention_layer_masking_and_causal():
    layer = SelfAttentionLayer(n_in=4, n_out=8, n_heads=2, causal=True,
                               activation="identity", weight_init="xavier")
    import jax
    params, _ = layer.init(jax.random.PRNGKey(0), None, jnp.float32)
    x = jnp.asarray(R.normal(size=(2, 6, 4)).astype(np.float32))
    out_full, _ = layer.apply(params, {}, x)
    # causal: output at step t must not change when the future changes
    x2 = x.at[:, 4:].set(0.0)
    out_trunc, _ = layer.apply(params, {}, x2)
    np.testing.assert_allclose(np.asarray(out_full[:, :4]),
                               np.asarray(out_trunc[:, :4]), atol=1e-5)
    # masking: padded keys don't affect earlier outputs
    mask = jnp.asarray([[1, 1, 1, 1, 0, 0], [1, 1, 1, 1, 1, 1]], jnp.float32)
    out_masked, _ = layer.apply(params, {}, x, mask=mask)
    assert np.isfinite(np.asarray(out_masked)).all()


def test_attention_classifier_trains():
    conf = (NeuralNetConfiguration(seed=9, updater=Adam(5e-3), dtype="float32")
            .list(SelfAttentionLayer(n_out=16, n_heads=4, activation="identity"),
                  RnnOutputLayer(n_out=2, activation="softmax", loss="mcxent"))
            .set_input_type(InputType.recurrent(6, 10)).build())
    net = MultiLayerNetwork(conf).init()
    x = R.normal(size=(32, 10, 6)).astype(np.float32)
    y = np.eye(2, dtype=np.float32)[(np.cumsum(x.sum(-1), 1) > 0).astype(int)]
    s0 = net.score(x, y)
    net.fit(x, y, epochs=20, batch_size=32)
    assert net.score(x, y) < s0


def test_ring_attention_is_trainable():
    """Gradients flow through the ring (lax.scan, not fori_loop): the
    sharded backward must match single-device full-attention gradients."""
    mesh = make_mesh((8,), ("seq",))
    q, k, v = _qkv(B=1, H=2, T=16, D=4)
    fn = ring_attention_sharded(mesh, "seq", causal=True)
    sh = sequence_sharding(mesh, "seq")

    def ring_loss(q, k, v):
        return jnp.sum(fn(q, k, v) ** 2)

    def full_loss(q, k, v):
        return jnp.sum(attention(q, k, v, causal=True) ** 2)

    qs, ks, vs = (jax.device_put(t, sh) for t in (q, k, v))
    g_ring = jax.grad(ring_loss, argnums=(0, 1, 2))(qs, ks, vs)
    g_full = jax.grad(full_loss, argnums=(0, 1, 2))(q, k, v)
    for gr, gf in zip(g_ring, g_full):
        np.testing.assert_allclose(np.asarray(jax.device_get(gr)),
                                   np.asarray(gf), atol=5e-5)


def test_layer_normalization_gradients_and_shapes():
    """LayerNormalization (net-new; required by transformer_lm): [B,T,F]
    and [B,F] shapes, f64 central-difference gradient check."""
    import numpy as np

    from deeplearning4j_tpu import (InputType, MultiLayerNetwork,
                                    NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.layers import (DenseLayer, LayerNormalization,
                                              OutputLayer)
    from deeplearning4j_tpu.optimize.updaters import Sgd
    from deeplearning4j_tpu.util.gradcheck import check_gradients

    R = np.random.default_rng(5)
    conf = (NeuralNetConfiguration(seed=1, updater=Sgd(0.1), dtype="float64")
            .list(DenseLayer(n_out=6, activation="tanh"),
                  LayerNormalization(),
                  OutputLayer(n_out=3, activation="softmax", loss="mcxent"))
            .set_input_type(InputType.feed_forward(4)).build())
    net = MultiLayerNetwork(conf).init()
    x = R.normal(size=(6, 4))
    y = np.eye(3)[R.integers(0, 3, 6)]
    assert check_gradients(net, x, y, print_results=True)
    # normalization actually happened
    ln = LayerNormalization(n_out=8)
    p, _ = ln.init(jax.random.PRNGKey(0), InputType.feed_forward(8),
                   jnp.float64)
    z = jnp.asarray(R.normal(size=(3, 5, 8)) * 10 + 4)
    out, _ = ln.apply(p, {}, z)
    np.testing.assert_allclose(np.asarray(out.mean(-1)), 0.0, atol=1e-6)
    np.testing.assert_allclose(np.asarray(out.std(-1)), 1.0, atol=1e-3)


def test_transformer_lm_zoo_model_trains():
    """The transformer_lm zoo model builds, serde-round-trips, and learns
    the shift-by-one task (flash kernels on TPU; XLA fallback here)."""
    import numpy as np

    from deeplearning4j_tpu.models import transformer_lm
    from deeplearning4j_tpu.nn.conf.graph_conf import (
        ComputationGraphConfiguration)
    from deeplearning4j_tpu.nn.graph.graph import ComputationGraph

    from deeplearning4j_tpu.optimize.updaters import Adam as _Adam
    V, T, B = 12, 32, 8
    net = transformer_lm(vocab_size=V, d_model=32, n_heads=2, n_blocks=2,
                        max_length=T, updater=_Adam(3e-3)).init()
    r = np.random.default_rng(0)
    ids = r.integers(0, V, (B, T))
    x = np.eye(V, dtype=np.float32)[ids]
    y = np.eye(V, dtype=np.float32)[np.roll(ids, 1, axis=1)]
    assert np.asarray(net.output(x)).shape == (B, T, V)
    s0 = net.score(x, y)
    net.fit(x, y, epochs=60)
    assert net.score(x, y) < 0.5 * s0
    # config JSON round-trip preserves the whole block structure
    conf2 = ComputationGraphConfiguration.from_json(net.conf.to_json())
    net2 = ComputationGraph(conf2).init()
    assert net2.num_params() == net.num_params()
    # position-awareness: swapping two tokens in the PREFIX must change the
    # prediction at a later step (a position-blind decoder could not tell)
    xa = x[:1].copy()
    xb = xa.copy()
    xb[0, [2, 5]] = xb[0, [5, 2]]
    if not np.allclose(xa, xb):     # tokens actually differ at those slots
        oa = np.asarray(net.output(xa))[0, 10]
        ob = np.asarray(net.output(xb))[0, 10]
        assert not np.allclose(oa, ob, atol=1e-6), \
            "decoder is position-blind"


@pytest.mark.slow
def test_transformer_lm_token_input_trains():
    """token_input=True feeds [B,T] int ids through the
    EmbeddingSequenceLayer gather and learns the same shift-by-one task
    (the TPU-first input path the benchmark's GPT-2 configuration takes).

    Slow lane (tier-1 budget): the token-input path is trained in tier-1
    by tests/test_tensor_parallel.py's mesh-parity fits and decoded all
    through tests/test_generation.py; the learns-shift-by-one pin stays
    via test_transformer_lm_zoo_model_trains (one-hot path)."""
    import numpy as np

    from deeplearning4j_tpu.models import transformer_lm
    from deeplearning4j_tpu.nn.conf.graph_conf import (
        ComputationGraphConfiguration)
    from deeplearning4j_tpu.nn.graph.graph import ComputationGraph
    from deeplearning4j_tpu.optimize.updaters import Adam as _Adam

    V, T, B = 12, 32, 8
    net = transformer_lm(vocab_size=V, d_model=32, n_heads=2, n_blocks=2,
                         max_length=T, updater=_Adam(3e-3),
                         token_input=True).init()
    r = np.random.default_rng(0)
    ids = r.integers(0, V, (B, T)).astype(np.int32)
    y = np.eye(V, dtype=np.float32)[np.roll(ids, 1, axis=1)]
    assert np.asarray(net.output(ids)).shape == (B, T, V)
    s0 = net.score(ids, y)
    net.fit(ids, y, epochs=60)
    assert net.score(ids, y) < 0.5 * s0
    # serde round-trip preserves the structure
    conf2 = ComputationGraphConfiguration.from_json(net.conf.to_json())
    net2 = ComputationGraph(conf2).init()
    assert net2.num_params() == net.num_params()
    # cross-path invariant: the gather embed carries V*d weights but no
    # bias, so it sits exactly d_model params under the one-hot Dense path
    onehot = transformer_lm(vocab_size=V, d_model=32, n_heads=2, n_blocks=2,
                            max_length=T, token_input=False).init()
    assert net.num_params() == onehot.num_params() - 32


# non-causal variant in the slow lane (tier-1 budget): the causal case is
# the production LM path and keeps the fused-vs-full contract pinned here
@pytest.mark.parametrize("causal", [
    pytest.param(False, marks=pytest.mark.slow), True])
def test_fused_ring_matches_full_attention(causal):
    """The Pallas carry-emitting ring (flash_block_update per hop +
    lax.switch causality) must equal single-device full attention —
    forward AND gradients (the custom_vjp runs the FlashAttention-2
    per-hop backward with rotating dk/dv accumulators)."""
    from deeplearning4j_tpu.ops.pallas_attention import fused_ring_applicable

    mesh = make_mesh((8,), ("seq",))
    T, D = 1024, 64
    assert fused_ring_applicable(T // 8, D, jnp.float32)
    r = np.random.default_rng(7)
    q, k, v = (jnp.asarray(r.normal(size=(1, 2, T, D)) * 0.2, jnp.float32)
               for _ in range(3))
    want = np.asarray(attention(q, k, v, causal=causal))
    fn = ring_attention_sharded(mesh, "seq", causal=causal, use_fused=True)
    sh = sequence_sharding(mesh, "seq")
    qs, ks, vs = (jax.device_put(t, sh) for t in (q, k, v))
    got = np.asarray(jax.device_get(fn(qs, ks, vs)))
    np.testing.assert_allclose(got, want, atol=2e-5)

    def ring_loss(q, k, v):
        return jnp.sum(fn(q, k, v) ** 2)

    def full_loss(q, k, v):
        return jnp.sum(attention(q, k, v, causal=causal) ** 2)

    g_ring = jax.grad(ring_loss, argnums=(0, 1, 2))(qs, ks, vs)
    g_full = jax.grad(full_loss, argnums=(0, 1, 2))(q, k, v)
    for name, gr, gf in zip("qkv", g_ring, g_full):
        rel = (np.max(np.abs(np.asarray(jax.device_get(gr)) - np.asarray(gf)))
               / (np.max(np.abs(np.asarray(gf))) + 1e-9))
        assert rel < 1e-4, (name, rel)


def test_fused_ring_auto_probe_engages():
    """use_fused=None auto-selects the fused body exactly when the local
    block qualifies (helper-seam contract)."""
    from deeplearning4j_tpu.ops.pallas_attention import fused_ring_applicable
    assert fused_ring_applicable(128, 64, jnp.float32)
    assert fused_ring_applicable(256, 128, jnp.bfloat16)
    assert not fused_ring_applicable(100, 64, jnp.float32)   # t_local % 128
    assert not fused_ring_applicable(128, 80, jnp.float32)   # odd head dim
    # the auto path produces the same numbers as the XLA ring
    mesh = make_mesh((8,), ("seq",))
    r = np.random.default_rng(3)
    q, k, v = (jnp.asarray(r.normal(size=(1, 1, 1024, 64)) * 0.2, jnp.float32)
               for _ in range(3))
    sh = sequence_sharding(mesh, "seq")
    qs, ks, vs = (jax.device_put(t, sh) for t in (q, k, v))
    auto = ring_attention_sharded(mesh, "seq", causal=True)
    xla = ring_attention_sharded(mesh, "seq", causal=True, use_fused=False)
    np.testing.assert_allclose(np.asarray(jax.device_get(auto(qs, ks, vs))),
                               np.asarray(jax.device_get(xla(qs, ks, vs))),
                               atol=2e-5)


def test_use_fused_explicit_misuse_is_a_targeted_error():
    """Regression (ADVICE r5): forcing use_fused=True on an ineligible
    local block must raise a targeted error naming t_local and the
    128-multiple constraint at the misuse site — not a confusing
    'T not a multiple of 128' from inside the Pallas block sizing."""
    mesh = make_mesh((2,), ("seq",), jax.devices()[:2])
    fn = ring_attention_sharded(mesh, "seq", causal=True, use_fused=True)
    q, k, v = _qkv(B=1, H=2, T=64, D=64)     # t_local = 32: not 128-aligned
    sh = sequence_sharding(mesh, "seq")
    with pytest.raises(ValueError, match=r"t_local.*multiple of 128"):
        fn(*(jax.device_put(t, sh) for t in (q, k, v)))


def test_fused_ring_zero_mass_row_degrades_to_zero_not_nan(monkeypatch):
    """Regression (ADVICE r5): a q row that accumulated NO probability
    mass (every hop skipped — a future key_mask case) must normalize to
    zeros via the epsilon guard, matching the XLA ring body, instead of
    emitting 0/0 NaN. Simulated by stubbing the hop kernel to a no-op."""
    from deeplearning4j_tpu.ops import pallas_attention as pa
    from deeplearning4j_tpu.parallel import ring_attention as ra
    from deeplearning4j_tpu.parallel.mesh import shard_map

    monkeypatch.setattr(pa, "flash_block_update",
                        lambda acc, m, l, q, k, v, **kw: (acc, m, l))
    mesh = make_mesh((2,), ("seq",), jax.devices()[:2])
    spec = P(None, "seq", None)

    def body(q3, k3, v3):
        o, _ = ra._ring_fused_fwd(q3, k3, v3, "seq", 2, False, 0.125)
        return o

    fn = shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                   out_specs=spec, check_vma=False)
    q3 = jnp.asarray(R.normal(size=(2, 256, 64)).astype(np.float32))
    out = np.asarray(jax.device_get(fn(q3, q3, q3)))
    assert np.all(np.isfinite(out)), "zero-mass rows produced NaN/inf"
    np.testing.assert_array_equal(out, np.zeros_like(out))
