"""int8-quantized KV cache + int8 serving forward (ISSUE 17).

Pins:
  - capacity: the int8 pool holds >= 1.9x the tokens per byte of the f32
    pool at the same ``num_blocks`` (the acceptance currency), measured
    BOTH ways: raw ``pool_bytes`` on ``make_pools`` output and the
    published ``kv_bytes_per_token`` engine row/gauge;
  - determinism: quantize-on-write is one deterministic expression, so
    quantized greedy decode is self-consistent — repeated runs identical,
    prefix-cache hit == miss token-for-token, speculative == plain
    token-for-token (each against its OWN quantized baseline — the int8
    tier never promises f32 token identity);
  - zero steady-state recompiles under concurrent quantized decode (the
    QuantizedPool is a pytree: the warmed programs, donation and COW all
    run unchanged);
  - config validation: only None/'int8' dtypes; the state adapter (no
    token-addressed pool) rejects the quantized tier;
  - the int8 dynamic-quantized serving forward stays within the
    bounded-error tier vs the f32 forward on a dense net.
"""
import threading

import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models.decode import truncated_draft
from deeplearning4j_tpu.models.zoo_extra import (text_generation_lstm,
                                                 transformer_lm)
from deeplearning4j_tpu.serving import (GenerationEngine,
                                        xla_compile_count)
from deeplearning4j_tpu.serving.generation.kvcache import (
    QuantizedPool, kv_dequantize, kv_quantize, make_pools, pool_bytes)
from deeplearning4j_tpu.serving.generation.programs import GenerationConfig
from deeplearning4j_tpu.telemetry import RecompileDetector

R = np.random.default_rng(1717)


def _lm(seed=123, vocab=128, d_model=64, n_heads=2, n_blocks=2,
        max_length=64):
    return transformer_lm(vocab_size=vocab, d_model=d_model,
                          n_heads=n_heads, n_blocks=n_blocks,
                          max_length=max_length, seed=seed,
                          dtype="float32", token_input=True).init()


def _engine(net, **kw):
    kw.setdefault("block_len", 16)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("decode_slots", 4)
    kw.setdefault("prefill_batches", (1, 2))
    return GenerationEngine(net, model_name="lm", kv_cache_dtype="int8",
                            **kw)


@pytest.fixture(scope="module")
def lm_net():
    return _lm()


@pytest.fixture(scope="module")
def eng8(lm_net):
    """ONE warmed int8 engine shared by the behavioural tests (AOT warm
    is the expensive part; every test below reads deltas, not absolute
    counters, so sharing is safe)."""
    eng = _engine(lm_net, draft=truncated_draft(lm_net, 1), spec_k=3,
                  prompt_rungs=(16, 64), prefix_cache=True)
    yield eng
    eng.stop()


# ------------------------------------------------------------ quantization
def test_kv_quantize_roundtrip_bound_and_determinism():
    x = jnp.asarray(R.standard_normal((3, 16, 4, 32)) * 2.0, jnp.float32)
    q1, s1 = kv_quantize(x)
    q2, s2 = kv_quantize(x)
    assert q1.dtype == jnp.int8 and s1.dtype == jnp.float32
    assert np.array_equal(np.asarray(q1), np.asarray(q2))
    assert np.array_equal(np.asarray(s1), np.asarray(s2))
    deq = kv_dequantize(q1, s1, jnp.float32)
    # symmetric rounding: per-vector error <= half a quantization step
    step = np.asarray(s1)[..., None]
    assert np.all(np.abs(np.asarray(deq) - np.asarray(x)) <= step * 0.5 + 1e-7)
    # zero vectors stay exactly zero (scale clamps to 1, codes to 0)
    qz, sz = kv_quantize(jnp.zeros((2, 4)))
    assert np.all(np.asarray(qz) == 0) and np.all(np.asarray(sz) == 1.0)


@pytest.mark.parametrize("head_dim", [32, 64, 128])
def test_pool_capacity_per_byte(head_dim):
    """ISSUE 17 acceptance, from shapes alone: >= 1.9x tokens per byte vs
    the f32 pool at identical geometry. A token costs 8 * head_dim f32
    bytes against 2 * (head_dim + 4) int8 bytes (codes plus one f32 scale)
    per layer and head: 3.56x at 32, 3.76x at the cells' 64, 3.88x at
    128; against a bfloat16 pool half of that."""
    geom = dict(n_layers=2, num_blocks=8, block_len=16, n_heads=2,
                head_dim=head_dim)
    kf, vf = make_pools(dtype=jnp.float32, **geom)
    kq, vq = make_pools(dtype=jnp.float32, quantized=True, **geom)
    assert isinstance(kq, QuantizedPool) and isinstance(vq, QuantizedPool)
    ratio = (pool_bytes(kf) + pool_bytes(vf)) / \
        (pool_bytes(kq) + pool_bytes(vq))
    assert ratio == pytest.approx(4 * head_dim / (head_dim + 4))
    assert ratio >= 1.9, ratio
    # the same blocks: the plain pool lays a token's heads side by side
    # (the attention kernel's page), the codes keep them apart for the
    # per-(token, head) scale
    assert kq.q.shape == kf.shape[:3] + (2, head_dim)
    assert kf.shape[3] == 2 * head_dim
    assert kq.scale.shape == kq.q.shape[:-1]


def test_kv_bytes_per_token_row_gauge_and_ratio(lm_net, eng8):
    # warm=False: the row is geometry-derived, no need to AOT-compile
    eng32 = GenerationEngine(lm_net, model_name="lm", block_len=16,
                             max_seq_len=64, decode_slots=4,
                             prefill_batches=(1, 2), warm=False)
    try:
        b8 = eng8.models()["lm"]["kv_bytes_per_token"]
        b32 = eng32.models()["lm"]["kv_bytes_per_token"]
        # d_model 64 / 2 heads -> head_dim 32: 2 layers * 2 heads *
        # (8*32) = 1024 f32 vs * (32+4)*2 = 288 int8
        assert b32 == 1024.0 and b8 == 288.0
        assert b32 / b8 >= 1.9
        assert eng8.models()["lm"]["kv_cache_dtype"] == "int8"
        assert eng8.metrics()["lm"]["kv_bytes_per_token"] == b8
    finally:
        eng32.stop()


def test_config_validation():
    with pytest.raises(ValueError, match="kv_cache_dtype"):
        GenerationConfig(kv_cache_dtype="fp8")
    net = text_generation_lstm(vocab_size=40, hidden=32, seed=5).init()
    with pytest.raises(ValueError, match="paged"):
        GenerationEngine(net, model_name="lstm", warm=False,
                         kv_cache_dtype="int8")


# ------------------------------------------------------------- determinism
def test_quantized_greedy_deterministic_across_runs(eng8):
    prompt = R.integers(1, 128, size=8).tolist()
    eng8.generate(prompt, max_tokens=4, temperature=0.0)   # settle
    c0 = xla_compile_count()
    runs = [eng8.generate(prompt, max_tokens=16, temperature=0.0)
            for _ in range(3)]
    toks = [t for t, _ in runs]
    assert toks[0] == toks[1] == toks[2]
    assert len(toks[0]) == 16
    assert xla_compile_count() == c0     # steady-state: zero recompiles


def test_prefix_cache_hit_matches_miss_quantized(eng8):
    """The fake-quantized prefill (QuantSimStore) is the load-bearing
    part: a prefix-cache HIT replays the suffix through the decode
    program against dequantized int8 blocks, so prefill must have sampled
    from the SAME numbers — hit and miss decode identical tokens."""
    prompt = R.integers(1, 128, size=20).tolist()   # 1 full block + 4
    base, _ = eng8.generate(prompt, max_tokens=12, temperature=0.0)
    m0 = eng8.metrics()["lm"]["prefix"]
    c0 = xla_compile_count()
    hit, _ = eng8.generate(prompt, max_tokens=12, temperature=0.0)
    m1 = eng8.metrics()["lm"]["prefix"]
    assert hit == base
    assert m1["hits"] > m0["hits"]
    assert xla_compile_count() == c0     # the hit replay stays warmed


def test_speculative_matches_plain_quantized(eng8):
    prompt = R.integers(1, 128, size=8).tolist()
    c0 = xla_compile_count()
    plain, _ = eng8.generate(prompt, max_tokens=16, temperature=0.0,
                             speculative=False)
    spec, _ = eng8.generate(prompt, max_tokens=16, temperature=0.0,
                            speculative=True)
    assert spec == plain
    snap = eng8.metrics()["lm"]
    assert snap["speculative"]["verify_steps"] > 0
    assert xla_compile_count() == c0     # both paths fully warmed


def test_zero_steady_state_recompiles_concurrent_quantized(eng8):
    compiles0 = xla_compile_count()
    work = [(8, 6, 0.0), (8, 6, 0.0), (20, 5, 0.0), (20, 5, 0.0),
            (3, 8, 0.7), (13, 6, 0.0)]
    res = {}

    def client(i):
        plen, mx, temp = work[i]
        p = [(j * 7 + 1) % 120 + 1 for j in range(plen)]
        res[i] = eng8.generate(p, max_tokens=mx, temperature=temp)

    with RecompileDetector(allowed=0) as det:
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(len(work))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    for i, (plen, mx, _) in enumerate(work):
        assert len(res[i][0]) == mx and res[i][1] == "length", \
            (i, res[i])
    assert det.count == 0, f"steady state compiled: {det.events}"
    assert xla_compile_count() == compiles0


# -------------------------------------------------------- int8 forward tier
def test_int8_forward_bounded_error():
    from deeplearning4j_tpu import MultiLayerNetwork, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.ops.kernels.quantized import int8_forward_fn
    from deeplearning4j_tpu.optimize.updaters import Sgd
    import jax

    conf = (NeuralNetConfiguration(seed=3, updater=Sgd(0.1),
                                   dtype="float32")
            .list(DenseLayer(n_in=32, n_out=64, activation="tanh"),
                  OutputLayer(n_out=8, activation="softmax",
                              loss="mcxent"))
            .build())
    net = MultiLayerNetwork(conf).init()
    x = jnp.asarray(R.standard_normal((16, 32)), jnp.float32)
    y32 = np.asarray(net.output(x))
    fwd = jax.jit(int8_forward_fn(net))
    y8 = np.asarray(fwd(net.params, net.state, x))
    rel = np.max(np.abs(y8 - y32)) / (np.max(np.abs(y32)) + 1e-12)
    assert rel < 0.05, rel
    # int8 tier quantizes FROM full precision only
    amp = (NeuralNetConfiguration(seed=3, updater=Sgd(0.1),
                                  dtype="float32",
                                  compute_dtype="bfloat16")
           .list(DenseLayer(n_in=32, n_out=64, activation="tanh"),
                 OutputLayer(n_out=8, activation="softmax", loss="mcxent"))
           .build())
    with pytest.raises(ValueError, match="full-precision"):
        int8_forward_fn(MultiLayerNetwork(amp).init())
