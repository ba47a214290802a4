"""The Laguna family (``model_type: laguna``: sliding-window attention
layers beside layers that keep the whole context, over one key-value row
layout) served through ``ComputationGraph``, ``GraphDecodeSpec`` and
``GenerationEngine`` (ISSUE 44), at a toy size in float32 on the CPU,
against the benchmark's plain reference
(``benchmarks/families/laguna/reference.py``); the kernels' windowed cases
against their plain twins; the rings; the refusals; the new readers; and
the programs the accepted cells run, held to what the parent commit
traced."""
import hashlib
import json
import os
import re
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.families.deepseek_v3 import build as ds_build  # noqa: E402
from benchmarks.families.deepseek_v3 import weights as ds_weights  # noqa: E402
from benchmarks.families.laguna import (build, flops, kernel_costs,  # noqa: E402
                                        reference, weights)
from benchmarks.families.lfm2_moe import build as lfm2_build  # noqa: E402
from benchmarks.families.lfm2_moe import weights as lfm2_weights  # noqa: E402
from deeplearning4j_tpu.models.decode import (  # noqa: E402
    GraphDecodeSpec, WindowDecodeUnsupportedError)
from deeplearning4j_tpu.models.zoo_extra import transformer_lm  # noqa: E402
from deeplearning4j_tpu.nn.layers import (GatedMLP,  # noqa: E402
                                          MixtureOfExpertsLayer,
                                          SelfAttentionLayer)
from deeplearning4j_tpu.ops import pallas_attention  # noqa: E402
from deeplearning4j_tpu.ops.pallas_paged_attention import (  # noqa: E402
    paged_attention_decode, paged_attention_reference)
from deeplearning4j_tpu.parallel.ring_attention import attention  # noqa: E402
from deeplearning4j_tpu.serving import GenerationEngine  # noqa: E402
from deeplearning4j_tpu.serving.generation.kvcache import (  # noqa: E402
    PagedStore, make_rings, ring_pages, ring_prefill_fill, ring_tables)
from deeplearning4j_tpu.serving.generation.programs import (  # noqa: E402
    GenerationConfig, GenerationProgramSet, pack_prefill)

with open(os.path.join(ROOT, "benchmarks", "configs", "laguna-xs.2",
                       "config.json")) as _f:
    PUBLISHED = json.load(_f)

WINDOW, BLK, CAP = 16, 8, 64
# the cell's configuration scaled down: every key the family reads is the
# published file's own, widths and counts cut (the window to 16 rows, so
# that a prompt of 29 and a context of 60 lie well past it)
TOY = dict(
    PUBLISHED, hidden_size=128, intermediate_size=256,
    moe_intermediate_size=64, shared_expert_intermediate_size=64,
    head_dim=32, num_key_value_heads=2,
    num_attention_heads_per_layer=[4, 8, 8, 8, 4], num_experts=16,
    num_experts_per_tok=2, vocab_size=256, sliding_window=WINDOW,
    served_context=CAP,
    precision={"serve": {"dtype": "float32", "compute_dtype": None},
               "train": {"dtype": "float32", "compute_dtype": None}})
NO_MARGIN = {"widest_logit_gap": 1e-3, "routing_margin": 0.0,
             "close_margin_share": 0.0}


@pytest.fixture(autouse=True)
def _full_matmuls():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def toy():
    net = build.build(TOY, TOY["hyperparameters"], "serve")
    w = weights.make(TOY, 7, "serve")
    build.install(net, w)
    return net, w


@pytest.fixture(scope="module")
def engine(toy):
    net, _ = toy
    with jax.default_matmul_precision("highest"):
        eng = GenerationEngine(net, model_name="lm", block_len=BLK,
                               max_seq_len=CAP, decode_slots=3,
                               prompt_rungs=(16, 32), prefill_batches=(1, 2))
    yield eng
    eng.stop()


def _prompts(seed, lengths):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, TOY["vocab_size"], n).astype(np.int32)
            for n in lengths]


def _layer_params(w, name):
    return {k.split("/")[1]: a for k, a in w.items()
            if k.startswith(name + "/")}


# ------------------------------------------------------------ the forward
def test_the_graph_is_the_reference_past_its_window(toy):
    """``net.output`` over a sequence of 40 (the window is 16) against the
    reference's full forward: logits through the softmax, every
    position."""
    net, w = toy
    ids = _prompts(0, [40])[0]
    probs = np.asarray(net.output(ids[None]))[0]
    want = np.asarray(jax.nn.softmax(reference.forward(w, TOY, ids), -1))
    np.testing.assert_allclose(probs, want, rtol=1e-4, atol=1e-7)


@pytest.mark.parametrize("name,i,gated", [
    ("l0_attn", 0, True),       # 4 heads, half the head rotated by YaRN
    ("l2_attn", 2, True),       # 8 heads over 2, the whole head, windowed
    ("l2_attn", 2, False)])     # and without the gate
def test_one_attention_layer_is_the_references_mixer(toy, name, i, gated):
    """One ``SelfAttentionLayer.apply`` against the reference's mixer of
    the same weights (its residual taken off, its input norm a gain of
    ones): heads of a size of their own, grouped key-value heads, the
    partial and the scaled rotation, the window, the per-head gate."""
    net, w = toy
    cfg = dict(TOY, gating=gated)
    layer = SelfAttentionLayer(n_in=128, **build.attention_fields(cfg, i))
    p = _layer_params(w, name)
    if not gated:
        p.pop("Wg")
    assert set(p) == set(layer.init(jax.random.PRNGKey(0), None,
                                    jnp.float32)[0])
    x = jax.random.normal(jax.random.PRNGKey(3), (256, 128), jnp.float32)
    eps = TOY["rms_norm_eps"]
    u = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    got, _ = layer.apply(p, {}, u[None])
    want = reference._attention(
        x, dict(w, **{f"l{i}_norm1/gain": jnp.ones((128,))}), f"l{i}_", cfg,
        i, None) - x
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               rtol=1e-4, atol=2e-6)


def test_the_yarn_frequencies_are_the_hand_computed_ones():
    """The published full-attention layer: 64 of 128 values rotated, theta
    500,000, factor 64 over an original length of 4,096, beta_fast 64 and
    beta_slow 1: pairs 0-5 keep their frequency, pairs 16-31 turn 64 times
    slower, a linear ramp between; cos and sin carry 0.1 ln 64 + 1."""
    layer = SelfAttentionLayer(n_in=2048, **build.attention_fields(PUBLISHED, 0))
    assert (layer.n_heads, layer.kv_heads, layer.head_dim) == (48, 8, 128)
    assert layer.rotary_dim == 64 and layer.window is None
    inv, factor = layer.rope_frequencies()
    f = 500000.0 ** (-np.arange(32) / 32.0)
    for beta, pair in ((64, 5), (1, 16)):
        exact = 64 * np.log(4096 / (beta * 2 * np.pi)) / (2 * np.log(500000.))
        assert (np.floor(exact) if beta == 64 else np.ceil(exact)) == pair
    assert factor == pytest.approx(1.41589, abs=5e-6)
    assert factor == pytest.approx(0.1 * np.log(64) + 1)
    np.testing.assert_allclose(inv[:6], f[:6], rtol=1e-6)
    np.testing.assert_allclose(inv[16:], f[16:] / 64, rtol=1e-6)
    r = (10 - 5) / 11.0                   # pair 10, on the ramp
    assert inv[10] == pytest.approx(r * f[10] / 64 + (1 - r) * f[10],
                                    rel=1e-6)
    ref_inv, ref_factor, R = reference.rope_table(
        PUBLISHED["rope_parameters"]["full_attention"], 128)
    assert R == 64 and ref_factor == pytest.approx(factor)
    np.testing.assert_allclose(ref_inv, inv, rtol=1e-6)
    sliding = SelfAttentionLayer(n_in=2048,
                                 **build.attention_fields(PUBLISHED, 1))
    assert (sliding.n_heads, sliding.window, sliding.rotary_dim,
            sliding.rope_scaling) == (64, 512, None, None)
    with pytest.raises(ValueError, match="yarn is"):
        SelfAttentionLayer(n_in=8, n_out=8, n_heads=1, rope_theta=1e4,
                           rope_scaling={"rope_type": "llama3"}
                           ).init(jax.random.PRNGKey(0), None, jnp.float32)


def test_the_specification_reads_both_kinds_of_layer(toy):
    net, _ = toy
    spec = GraphDecodeSpec(net)
    assert spec.attn_names == [f"l{i}_attn" for i in range(5)]
    assert spec.full_names == ["l0_attn", "l4_attn"]
    assert spec.window_names == ["l1_attn", "l2_attn", "l3_attn"]
    assert (spec.window, spec.n_blocks, spec.n_window_layers) == (WINDOW, 2, 3)
    assert (spec.kv_heads, spec.head_dim, spec.cache_kind) == (2, 32, "kv")
    assert spec.moe_names == [f"l{i}_ffn" for i in range(1, 5)]
    assert spec.supports_head_sharding(1)
    assert not spec.supports_head_sharding(2)
    full, win = spec.split_kinds(list("abcde"))
    assert (full, win) == (["a", "e"], ["b", "c", "d"])


def _graph_of(layers):
    from deeplearning4j_tpu.nn.conf.config import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.graph.graph import ComputationGraph
    from deeplearning4j_tpu.nn.inputs import InputType
    from deeplearning4j_tpu.nn.layers import (EmbeddingSequenceLayer,
                                              RnnOutputLayer)
    g = (NeuralNetConfiguration(seed=0, dtype="float32").graph_builder()
         .add_inputs("t")
         .add_layer("e", EmbeddingSequenceLayer(n_in=16, n_out=32), "t"))
    h = "e"
    for i, a in enumerate(layers):
        g = g.add_layer(f"a{i}", a, h)
        h = f"a{i}"
    g = (g.add_layer("head", RnnOutputLayer(n_out=16, activation="softmax",
                                            loss="sparse_mcxent"), h)
         .set_outputs("head").set_input_types(InputType.recurrent(1, 16)))
    return ComputationGraph(g.build()).init()


@pytest.mark.parametrize("layers,match", [
    ([dict(n_kv_heads=2), dict(n_kv_heads=4)], "share one cache row"),
    ([dict(window=4), dict(window=8), dict()], "share one window"),
    ([dict(window=4)], "every attention layer has a sliding window")])
def test_what_the_specification_asks_of_the_attention_layers(layers, match):
    mk = lambda kw: SelfAttentionLayer(n_out=32, n_heads=4, causal=True, **kw)
    with pytest.raises(ValueError, match=match):
        GraphDecodeSpec(_graph_of([mk(kw) for kw in layers]))
    # query heads may differ where the key-value row is one
    ok = GraphDecodeSpec(_graph_of([
        SelfAttentionLayer(n_out=32, n_heads=8, n_kv_heads=2, head_size=8,
                           causal=True, window=4),
        SelfAttentionLayer(n_out=32, n_heads=2, n_kv_heads=2, head_size=8,
                           causal=True)]))
    assert ok.window == 4 and ok.full_names == ["a1"]


# ---------------------------------------------- prefill, then the rings
def _warmed(net, **kw):
    cfg = GenerationConfig(block_len=BLK, max_seq_len=CAP, decode_slots=3,
                           prompt_rungs=(32,), prefill_batches=(1,), **kw)
    return cfg, GenerationProgramSet(net, config=cfg).warm()


@pytest.fixture(scope="module")
def warmed(toy):
    with jax.default_matmul_precision("highest"):
        return _warmed(toy[0])


def test_the_cache_keeps_a_window_and_a_page_for_the_window_layers(warmed):
    cfg, ps = warmed
    cache = ps.make_cache()
    rp = ring_pages(WINDOW, BLK)
    assert rp == 3 and rp * BLK <= WINDOW + BLK
    assert [a.shape for a in cache] == [
        (2, cfg.num_blocks, BLK, 64), (2, cfg.num_blocks, BLK, 64),
        (3, 3 + 1, rp, BLK, 64), (3, 3 + 1, rp, BLK, 64)]
    assert ps.windowed and not ps.prefix_enabled and ps.prefix_skipped_windowed
    assert ps.kv_bytes_per_token() == 2 * 2 * 64 * 4 == 2 * ps.cache_row_bytes()
    assert ps.window_cache_bytes_per_slot() == 2 * 3 * rp * BLK * 64 * 4
    assert ("cow",) not in ps._compiled
    # the published shape: 512 rows and one page of 64, 9 pages a ring
    assert ring_pages(512, 64) == 9 and ring_pages(500, 64) == 9


@pytest.mark.parametrize("n", [5, 13, 16, 29])
def test_prefill_then_decode_token_by_token_is_the_references_forward(
        toy, warmed, n):
    """A prompt of ``n`` (shorter than the window, not a multiple of the
    page, the window itself, longer than it) prefilled at rung 32, then the
    rest of a sequence of 60 fed ONE token a step through the pages of the
    full layers and the rings of the window layers (three pages of 8: the
    ring wraps twice), its LOGITS at every position against the
    reference's full forward."""
    net, w = toy
    cfg, ps = warmed
    spec = ps.spec
    ids = _prompts(20 + n, [60])[0]
    want = np.asarray(reference.forward(w, TOY, ids))
    mb, S = cfg.blocks_per_seq, 3
    tables = np.zeros((S, mb), np.int32)
    tables[1] = 1 + np.arange(mb)
    tokens = np.zeros((1, 32), np.int32)
    tokens[0, :n] = ids[:n]
    z = lambda k, dt=np.int32: np.zeros(k, dt)
    first, cache, _ = ps.run_prefill(
        ps.make_cache(),
        pack_prefill(tokens, np.asarray([n], np.int32), tables[1:2],
                     np.asarray([1], np.int32), z(1, np.float32), z(1)),
        ps.fresh_key())
    assert int(ps.split_stats(first)[0][0]) == int(np.argmax(want[n - 1]))
    active = jnp.asarray([False, True, False])

    @jax.jit
    def step(cache, tok, pos):
        store = PagedStore(cache[0], cache[1], jnp.asarray(tables), pos,
                           active, BLK, None, cache[-2:])
        logits = spec.decode_step(net.params, net.state, tok, pos, store)
        return logits, store.cache

    for t in range(n, 60):
        logits, cache = step(cache, jnp.asarray([0, ids[t], 0]),
                             jnp.asarray([0, t, 0]))
        np.testing.assert_allclose(np.asarray(logits[1]), want[t],
                                   rtol=1e-4, atol=3e-5)


def test_a_prefill_leaves_each_prompts_last_rows_in_its_ring():
    """``ring_prefill_fill`` against the rule spelled out: ring row r
    holds the newest position p < n with p mod rows == r, at the prompt's
    TRUE length, whatever the rung."""
    k_ring, _ = make_rings(2, 3, WINDOW, BLK, 1, 4, jnp.float32)
    rows = k_ring.shape[2] * BLK
    L = 40
    kv = [jnp.arange(2 * L, dtype=jnp.float32).reshape(2, L, 1, 1)
          * jnp.ones((1, 1, 1, 4), jnp.float32) + jnp.float32(1000 * layer)
          for layer in range(2)]
    lengths = jnp.asarray([7, 37])
    out = np.asarray(ring_prefill_fill(k_ring, kv, lengths,
                                       jnp.asarray([2, 0])))
    for b, (n, slot) in enumerate(((7, 2), (37, 0))):
        for layer in range(2):
            flat = out[layer, slot].reshape(rows, 4)[:, 0]
            for p in range(max(0, n - rows), n):
                assert flat[p % rows] == b * L + p + 1000 * layer
    assert not out[:, 1].any() and not out[:, 3].any()
    t = np.asarray(ring_tables(2, 3, 8))
    assert t.tolist() == [[0, 1, 2, 0, 1, 2, 0, 1], [3, 4, 5, 3, 4, 5, 3, 4]]


# ------------------------------------------------------------ the kernels
def _brute_force(T, BQ, BK, window):
    r = np.arange(T)[:, None]
    c = np.arange(T)[None, :]
    seen = (c <= r) & (c > r - window)
    visited = masked = 0
    for r0 in range(0, T, BQ):
        for c0 in range(0, T, BK):
            tile = seen[r0:r0 + BQ, c0:c0 + BK]
            visited += bool(tile.any())
            masked += bool(tile.any() and not tile.all())
    return visited, masked, (T // BQ) * (T // BK)


@pytest.mark.parametrize("T,window", [(1024, 512), (2048, 512), (2048, 300),
                                      (1024, 1), (1024, 256), (512, 4096)])
def test_the_tile_schedule_counts_what_a_brute_force_mask_needs(T, window):
    BQ, BK = pallas_attention._blocks(T, True)
    assert pallas_attention.tile_schedule(T, True, window) == \
        _brute_force(T, BQ, BK, window)
    # and without a window it is the causal count it was
    assert pallas_attention.tile_schedule(T, True) == \
        _brute_force(T, BQ, BK, T)


def test_a_windowed_head_visits_a_twentieth_of_the_causal_tiles():
    causal = pallas_attention.tile_schedule(16384, True)[0]
    windowed = pallas_attention.tile_schedule(16384, True, 512)[0]
    assert causal == 64 * 65 // 2 and windowed == 3 * 64 - 3
    assert windowed / causal < (512 + 256) / 8192


@pytest.mark.parametrize("T,window,Hkv,resident", [
    (256, 64, 2, 1024),        # one tile
    (512, 100, 1, 1024),       # a window that is no multiple of anything
    (1024, 512, 4, 1024),      # one resident block, the cell's window
    (2048, 512, 2, 1024),      # two: the window reaches one block back
    (1024, 512, 2, 128),       # blocks of one tile: two blocks back
    (512, 1000, 2, 1024)])     # a window wider than the sequence: causal
def test_the_windowed_flash_forward_is_its_plain_twin(monkeypatch, T, window,
                                                      Hkv, resident):
    monkeypatch.setenv("DL4J_TPU_FUSED_ATTN_INTERPRET", "1")
    monkeypatch.setattr(pallas_attention, "_RESIDENT_MAX", resident)
    ks = jax.random.split(jax.random.PRNGKey(T + window), 3)
    q = jax.random.normal(ks[0], (1, 4, T, 128), jnp.float32)
    k = jax.random.normal(ks[1], (1, Hkv, T, 128), jnp.float32)
    v = jax.random.normal(ks[2], (1, Hkv, T, 128), jnp.float32)
    text = str(jax.make_jaxpr(lambda q, k, v: pallas_attention.flash_attention(
        q, k, v, causal=True, window=window))(q, k, v))
    assert pallas_attention.WINDOW_FWD_NAME in text
    got = pallas_attention.flash_attention(q, k, v, causal=True,
                                           window=window)
    rep = lambda a: jnp.repeat(a, 4 // Hkv, axis=1)
    want = attention(q, rep(k), rep(v), causal=True, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4,
                               atol=1e-5)
    if window >= T:
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(attention(q, rep(k), rep(v),
                                                  causal=True)),
            rtol=1e-4, atol=1e-5)
    with pytest.raises(ValueError, match="causal and takes no key_mask"):
        pallas_attention.flash_attention(q, k, v, window=window)


def test_a_windowed_layer_serves_through_the_kernel_and_trains_without_it(
        monkeypatch):
    monkeypatch.setenv("DL4J_TPU_FUSED_ATTN_INTERPRET", "1")
    layer = SelfAttentionLayer(n_in=64, n_out=64, n_heads=4, n_kv_heads=2,
                               head_size=128, causal=True, window=96,
                               head_gate=True, rope_theta=1e4, bias=False)
    p, _ = layer.init(jax.random.PRNGKey(0), None, jnp.float32)
    assert p["Wq"].shape == (64, 512) and p["Wo"].shape == (512, 64)
    assert p["Wk"].shape == (64, 256) and p["Wg"].shape == (64, 4)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 256, 64), jnp.float32)
    served = str(jax.make_jaxpr(
        lambda x: layer.apply(p, {}, x, train=False)[0])(x))
    trained = str(jax.make_jaxpr(
        lambda x: layer.apply(p, {}, x, train=True)[0])(x))
    assert pallas_attention.WINDOW_FWD_NAME in served
    assert pallas_attention.WINDOW_FWD_NAME not in trained
    assert "flash_attention" not in trained
    np.testing.assert_allclose(
        np.asarray(layer.apply(p, {}, x, train=False)[0]),
        np.asarray(layer.apply(p, {}, x, train=True)[0]), rtol=1e-4,
        atol=1e-5)


@pytest.mark.parametrize("blk,window,lens", [
    (8, 32, [1, 9, 0, 40, 96]),       # 0: an idle slot; 96: the ring's end
    (8, 20, [3, 20, 21, 77, 64]),     # a window that is no whole page
    (16, 16, [16, 17, 31, 32, 33])])  # a window of one page
def test_the_windowed_paged_decode_is_the_masked_gather(blk, window, lens):
    """Five slots' rings viewed as one pool, the logical tables mapping
    page j to ring page j mod ring_pages, each slot read from its window's
    first key on: against the gather of every slot's whole table under the
    same two-sided mask; 8 query heads over 2 key-value heads."""
    S, Hq, H, Dh, mb, L = 5, 8, 2, 64, 96 // blk, 2
    rp = ring_pages(window, blk)
    ks = jax.random.split(jax.random.PRNGKey(blk + window), 3)
    kp = jax.random.normal(ks[0], (L, (S + 1) * rp, blk, H * Dh), jnp.float32)
    vp = jax.random.normal(ks[1], (L, (S + 1) * rp, blk, H * Dh), jnp.float32)
    q = jax.random.normal(ks[2], (S, Hq, 1, Dh), jnp.float32)
    tables = ring_tables(S, rp, mb)
    lens = jnp.asarray(lens, jnp.int32)
    starts = jnp.maximum(lens - window, 0)
    got = paged_attention_decode(q, kp, vp, 1, tables, lens, starts=starts)
    want = paged_attention_reference(q, kp, vp, 1, tables, lens,
                                     starts=starts)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4,
                               atol=1e-5)
    assert not np.asarray(got)[np.asarray(lens) == 0].any()
    text = str(jax.make_jaxpr(lambda q: paged_attention_decode(
        q, kp, vp, 1, tables, lens, starts=starts))(q))
    assert "paged_attention_window_decode" in text
    # a start of 0 everywhere is the plain kernel's result
    np.testing.assert_allclose(
        np.asarray(paged_attention_decode(q, kp, vp, 1, tables, lens,
                                          starts=jnp.zeros_like(lens))),
        np.asarray(paged_attention_decode(q, kp, vp, 1, tables, lens)),
        rtol=1e-5, atol=1e-6)


# ------------------------------------------------------------ the experts
def _expert_layer_and_weights(toy, score="sigmoid", held=None):
    _, w = toy
    moe = MixtureOfExpertsLayer(n_in=128, n_out=128, n_experts=16, top_k=2,
                                n_hidden=64, norm_eps=1e-20, held=held,
                                routed_scaling_factor=2.5, score=score)
    p = _layer_params(w, "l1_ffn")
    if held:
        first, count = held
        p = dict(p, **{k: p[k][first:first + count]
                       for k in ("W1", "W3", "W2")})
    return moe, p


@pytest.mark.parametrize("score", ["sigmoid", "softmax"])
def test_both_router_scores_and_the_shared_expert_are_the_references(
        toy, score):
    _, w = toy
    cfg = dict(TOY, assumed=dict(TOY["assumed"], router_score=score))
    x = jax.random.normal(jax.random.PRNGKey(4), (256, 128), jnp.float32)
    want, _ = reference._moe_ffn(
        x, dict(w, **{"l1_norm2/gain": jnp.ones((128,))}), "l1_", cfg, None)
    u = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6)
    moe, p = _expert_layer_and_weights(toy, score)
    shared = GatedMLP(n_in=128, n_out=128, n_hidden=64)
    got = x + moe.apply(p, {}, u)[0] \
        + shared.apply(_layer_params(w, "l1_shared"), {}, u)[0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4,
                               atol=1e-5)
    idx, wts = moe.route(p, u)
    np.testing.assert_allclose(np.asarray(wts.sum(-1)), 2.5, rtol=1e-5)
    other, _ = _expert_layer_and_weights(
        toy, "softmax" if score == "sigmoid" else "sigmoid")
    assert float(jnp.max(jnp.abs(other.route(p, u)[1] - wts))) > 1e-3
    with pytest.raises(ValueError, match="'sigmoid' or 'softmax'"):
        MixtureOfExpertsLayer(n_in=8, n_experts=2, top_k=1, n_hidden=8,
                              score="tanh").init(jax.random.PRNGKey(0), None,
                                                 jnp.float32)


def test_shares_of_disjoint_held_ranges_and_one_shared_expert_add_up(toy):
    """The cell's 256-expert layer scaled down to 16: four chips holding
    experts 0-3, 4-7, 8-11 and 12-15 each compute their share of the
    routed sum; the shared expert, which every chip would compute alike,
    is counted once: the whole layer beside it."""
    _, w = toy
    u = jax.random.normal(jax.random.PRNGKey(6), (64, 128), jnp.float32)
    shared = GatedMLP(n_in=128, n_out=128, n_hidden=64).apply(
        _layer_params(w, "l1_shared"), {}, u)[0]
    whole, p = _expert_layer_and_weights(toy)
    want = whole.apply(p, {}, u)[0] + shared
    parts = shared
    for first in (0, 4, 8, 12):
        part, ph = _expert_layer_and_weights(toy, held=(first, 4))
        parts = parts + part.apply(ph, {}, u)[0]
    np.testing.assert_allclose(np.asarray(parts), np.asarray(want),
                               rtol=1e-4, atol=1e-6)


# ------------------------------------------------------------- the engine
def test_the_engine_serves_the_references_tokens_and_skips_the_prefix_cache(
        engine, toy):
    """The same block-aligned prompt twice: a model with window layers
    never takes a prefix hit (a ring does not keep the window's rows at
    the matched boundary); each admission is counted, the tokens are the
    reference's both times."""
    _, w = toy
    prompt = _prompts(8, [24])[0]                   # three whole blocks
    before = engine.metrics()["lm"]["prefix"]
    one, _ = engine.generate(prompt, max_tokens=30)
    two, _ = engine.generate(prompt, max_tokens=30)
    after = engine.metrics()["lm"]["prefix"]
    assert one == two and len(one) == 30
    assert after["hits"] == before["hits"] == 0
    assert after["skipped_windowed"] == before["skipped_windowed"] + 2
    assert after["skipped_stateful"] == 0
    res = reference.token_gaps(w, TOY, [(prompt, one)], limits=NO_MARGIN)
    assert res["tokens"] == 30 and res["widest_gap"] <= 1e-3
    row = engine.models()["lm"]
    assert row["cache_kind"] == "kv" and not row["prefix_cache"]
    assert (row["window"], row["window_layers"]) == (WINDOW, 3)
    assert row["cache_bytes_per_token"] == 2 * 2 * 64 * 4
    assert row["window_cache_bytes_per_slot"] == 2 * 3 * 3 * BLK * 64 * 4
    from deeplearning4j_tpu import telemetry
    assert telemetry.get_registry().counter(
        "generation.lm.prefix_skipped_windowed").value >= 2


def test_the_spans_carry_the_window_counters(engine):
    from deeplearning4j_tpu import telemetry
    reg = telemetry.get_registry()
    seq = reg.last_seq
    engine.generate(_prompts(9, [21])[0], max_tokens=4)
    events = [e for e in reg.trace_events_since(seq) if e.get("ph") == "X"]
    pre = [e["args"] for e in events if e["name"] == "generation.prefill"]
    dec = [e["args"] for e in events if e["name"] == "generation.decode_step"]
    assert pre and pre[0]["attn_key_rows"] == 21 * 22 // 2
    assert pre[0]["attn_window_key_rows"] == 16 * 17 // 2 + 5 * 16
    assert dec and all(a["cache_row_bytes"] == 2 * 64 * 4 for a in dec)
    assert dec[0]["live_tokens"] == 22 and dec[0]["window_tokens"] == 16
    assert "moe_pairs" in dec[0]


@pytest.mark.parametrize("what,kw", [
    ("kv_cache_dtype='int8'", {"kv_cache_dtype": "int8"}),
    ("speculative decoding", {"draft": True}),
    ("model-sharded decode", {"mesh": True})])
def test_what_keeps_whole_contexts_refuses_window_layers_by_name(toy, what,
                                                                 kw):
    net, _ = toy
    cfg = GenerationConfig(block_len=BLK, max_seq_len=CAP, decode_slots=2,
                           kv_cache_dtype=kw.get("kv_cache_dtype"))
    mesh = None
    if kw.get("mesh"):
        from jax.sharding import Mesh
        mesh = Mesh(np.array(jax.devices()[:2]).reshape(1, 2),
                    ("data", "model"))
    with pytest.raises(WindowDecodeUnsupportedError, match="l1_attn") as e:
        GenerationProgramSet(net, config=cfg, mesh=mesh,
                             draft_net=net if kw.get("draft") else None)
    assert what in str(e.value)


def test_a_windowed_draft_and_a_decode_window_are_refused_too(toy):
    net, _ = toy
    target = transformer_lm(vocab_size=256, d_model=32, n_heads=2, n_blocks=1,
                            max_length=CAP, token_input=True).init()
    cfg = GenerationConfig(block_len=BLK, max_seq_len=CAP, decode_slots=2)
    with pytest.raises(WindowDecodeUnsupportedError, match="draft"):
        GenerationProgramSet(target, config=cfg, draft_net=net)
    spec = GraphDecodeSpec(net)

    class Whole:                      # a window store of whole contexts
        def attend(self, i, q, k, v):
            return q
    with pytest.raises(WindowDecodeUnsupportedError, match="l1_attn"):
        spec.decode_window(net.params, net.state,
                           jnp.zeros((1, 2), jnp.int32),
                           jnp.zeros((1,), jnp.int32), Whole())


# ------------------------------------------- the benchmark's own reckoning
def test_the_configuration_keeps_every_published_width():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the catalog of architectures is not on this machine")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Laguna-XS.2")
    reduced = {"num_hidden_layers", "layer_types", "mlp_layer_types",
               "num_attention_heads_per_layer"}
    assert set(PUBLISHED["reduced"]) == reduced
    assert PUBLISHED["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in reduced:
            continue
        assert PUBLISHED[key] == value, key
    n = PUBLISHED["num_hidden_layers"]
    assert n == 5
    for key in reduced - {"num_hidden_layers"}:
        assert PUBLISHED[key] == row["config"][key][:n]
    assert PUBLISHED["assumed"]["router_score"] == "sigmoid"
    assert set(PUBLISHED["published"]) == reduced
    sizes = weights.shapes(PUBLISHED)
    assert sum(int(np.prod(s)) for s in sizes.values()) == 3_869_959_168


def test_the_costs_count_what_the_result_requires_and_the_list_outlasts():
    cfg = PUBLISHED
    p = 10240
    per_token = flops.forward_flops_per_token(cfg, (p + 1) / 2.0) \
        - flops.head_flops_per_token(cfg)
    assert per_token == pytest.approx(0.977e9, rel=2e-3)
    assert flops.window_context(512, (p + 1) / 2.0) == \
        pytest.approx((512 * 513 / 2 + (p - 512) * 512) / p)
    assert flops.window_context(512, 100.0) == 100.0
    # benchmarks/tests/test_lib.py's reckoning for every closed-loop cell
    from benchmarks.lib import peaks, traffic
    with open(os.path.join(ROOT, "benchmarks", "traffic",
                           "serve-codebase.json")) as f:
        tr = json.load(f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        run_seconds = json.load(f)["run_seconds"]
    pairs = traffic.stratified_pairs(tr["lengths"], tr["block"])
    per_request = sum(
        n * (flops.forward_flops_per_token(cfg, (n + 1) / 2.0)
             - flops.head_flops_per_token(cfg)) for n, _ in pairs) / len(pairs)
    peak = max(v["bf16_flops"] for v in peaks.PEAKS.values())
    most = tr["callers"] + (tr["preroll_s"] + run_seconds) * peak / per_request
    assert tr["block"] * tr["blocks"] >= most
    assert tr["block"] * tr["blocks"] == 1984
    fl, by = kernel_costs.window_prefill_cost(cfg, 5000.0, 100.0)
    assert fl == 3 * 64 * 5000 * 2 * 256
    assert by == 100 * (2 * 192 + 2 * 3 * 8) * 128 * 2
    fl, by = kernel_costs.window_decode_cost(cfg, 1000.0, 4096)
    assert (fl, by) == (3 * 64 * 1000 * 2 * 256, 3 * 1000 * 4096)
    assert kernel_costs.expert_sets(cfg) == 1024
    assert kernel_costs.experts_cost(cfg, 10.0, 3.0) == (
        10 * 2.0 * 3 * 2048 * 512, 3 * 3.0 * 2048 * 512 * 2 + 10 * 2.0 * 2048 * 2)


def _obs(pre_args, dec_args, by_op_s):
    """A closed-loop window of 10 s holding one prefill span of 1 s and
    twelve decode steps of 0.1 s, of which 4 s were traced."""
    ev = [{"ph": "X", "name": "generation.prefill", "ts": 1e6, "dur": 1e6,
           "args": pre_args}]
    ev += [{"ph": "X", "name": "generation.decode_step",
            "ts": (3 + 0.2 * i) * 1e6, "dur": 1e5, "args": dec_args}
           for i in range(12)]
    return {"kind": "closed_loop", "config": PUBLISHED, "events": ev,
            "epoch_ns": 0, "window_perf": (0.0, 10.0),
            "device": {"kind": "TPU v5 lite"},
            "engine": {"num_blocks": 11, "block_len": 100,
                       "cache_kind": "kv"},
            "trace": {"by_op_s": by_op_s, "busy_s": 4.0, "window_s": 4.0}}


NEW_READERS = ("kernels.window_prefill_roofline_pct.tput",
               "kernels.window_decode_roofline_pct.tput",
               "attn.window_busy_pct.tput", "attn.full_busy_pct.tput",
               "kvcache.window_read_pct.tput")


def test_the_new_readers_read_the_spans_and_fall_silent_without_them():
    from benchmarks import run as harness
    read = lambda name, obs: harness.load_reader(name).read(obs)
    obs = _obs({"attn_window_key_rows": 5_000_000, "tokens": 10_000},
               {"live_tokens": 400_000, "window_tokens": 20_000,
                "cache_row_bytes": 4096},
               {"paged_attention_window_decode": 0.2,
                "flash_attention_window_fwd": 0.6,
                "flash_attention_fwd": 1.0, "paged_attention_decode": 0.4})
    # decode: memory binds; 12 steps over a 10 s window, 4 s of it traced
    least = 12 * 3 * 20_000 * 4096 / 819e9
    assert read(NEW_READERS[1], obs) == \
        pytest.approx(100 * least / 10.0 * 4.0 / 0.2)
    least = 3 * 64 * 5e6 * 2 * 256 / 197e12           # compute binds
    assert read(NEW_READERS[0], obs) == \
        pytest.approx(100 * least / 10.0 * 4.0 / 0.6)
    assert read(NEW_READERS[2], obs) == pytest.approx(20.0)
    assert read(NEW_READERS[3], obs) == pytest.approx(35.0)
    assert read(NEW_READERS[4], obs) == pytest.approx(5.0)
    # the parent's spans and another family's configuration: nothing
    bare = _obs({"tokens": 10_000}, {"live_tokens": 550}, {"fusion": 1.0})
    other = dict(obs, config={"family": "gpt2"})
    for name in NEW_READERS:
        assert read(name, bare) is None
        assert read(name, {"kind": "closed_loop", "config": {}}) is None
        if name != NEW_READERS[4]:
            assert read(name, other) is None
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_READERS:
        assert listed[name]["workloads"] == ["laguna-serve-codebase"]
    cell = [m["name"] for m in bench["per_layer"]
            if "laguna-serve-codebase" in m.get("workloads", ())]
    assert not any(n.startswith(("mla.", "kernels.mla_")) for n in cell)
    assert {"kvcache.pool_live_pct.tput", "moe.expert_load_max_over_mean.tput",
            "kernels.moe_experts_roofline_pct.tput",
            "programs.decode_step_p50_ms.tput"} <= set(cell)


def test_the_closed_loop_kind_runs_the_family_and_the_control_fails(toy):
    """The cell's CPU rehearsal: the toy configuration through the
    closed-loop kind's own ``run`` (engine, callers, window, sampling, the
    reference's check) with the family's modules, then the float8 control
    in the program's place, which must stand off from the reference where
    the float32 program sits on it. Counts only."""
    from benchmarks import run as harness
    from benchmarks.kinds import closed_loop
    from benchmarks.lib.correct import Checks
    fam = {k: __import__(f"benchmarks.families.laguna.{k}", fromlist=[k])
           for k in ("build", "weights", "reference", "flops")}
    traffic = {
        "kind": "closed_loop", "callers": 4, "preroll_s": 0.5,
        "timeout_s": 120.0, "block": 8, "blocks": 400,
        "lengths": {"prompt": {"kind": "uniform", "lo": 4, "hi": 30},
                    "output": {"kind": "uniform", "lo": 10, "hi": 30},
                    "max_total": CAP, "pairing_seed": 1},
        "engine": {"block_len": BLK, "max_seq_len": CAP, "decode_slots": 3,
                   "prompt_rungs": [16, 32], "prefill_batches": [1]},
        "check": {"min_tokens": 60, "max_requests": 12}}
    limits = harness.load_json(harness.HERE, "limits",
                               "laguna-serve-codebase.json")
    out = {}
    for control in (False, True):
        ctx = {"cell": {"name": "toy", "chips": 1}, "config": TOY,
               "traffic": traffic, "limits": limits, "seed": 2 ** 31 + 5,
               "seconds": 4.0, "trace": False, "rehearsal": True,
               "device": {"platform": "cpu", "kind": "cpu", "count": 1},
               "t_start": time.perf_counter(), "log": lambda m: None,
               "checks": Checks(), "control": control, "family": fam,
               "tracer": harness.Tracer(False, "unused"),
               "memory_peak_bytes": lambda: 0,
               "epoch_ns": time.time_ns() - time.perf_counter_ns()}
        res = closed_loop.run(ctx)
        out[control] = (ctx, res)
    ctx, res = out[False]
    assert res["failed"] == 0 and res["counts"]["completed"] >= 2
    assert res["counts"]["compiles_in_window"] == 0
    assert ctx["checks"].correct, ctx["checks"].rows
    assert res["obs"]["engine"]["window_cache_bytes_per_slot"] > 0
    steps = [e["args"] for e in res["obs"]["events"]
             if e.get("name") == "generation.decode_step"]
    assert steps and all(a["window_tokens"] <= a["live_tokens"]
                         for a in steps)
    c = out[True][0]["control_result"]
    assert c["control_widest_gap"] > 0.05 and c["kept_widest_gap"] < 1e-3


# ------------------------- the programs of the cells the benchmark had
LFM2 = {
    "family": "lfm2_moe", "conv_L_cache": 3, "hidden_size": 128,
    "intermediate_size": 256, "moe_intermediate_size": 128,
    "layer_types": ["conv", "full_attention", "conv", "full_attention"],
    "norm_eps": 1e-5, "norm_topk_prob": True, "num_attention_heads": 2,
    "num_key_value_heads": 1, "num_dense_layers": 1, "num_experts": 8,
    "num_experts_per_tok": 2, "rope_parameters": {"rope_theta": 1000000},
    "routed_scaling_factor": 1, "vocab_size": 256, "served_context": 256,
    "hyperparameters": TOY["hyperparameters"], "precision": TOY["precision"],
}
KANANA = {
    "family": "deepseek_v3", "hidden_size": 128, "intermediate_size": 256,
    "moe_intermediate_size": 64, "first_k_dense_replace": 1,
    "num_hidden_layers": 3, "num_attention_heads": 4, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "qk_head_dim": 192, "v_head_dim": 128,
    "kv_lora_rank": 64, "q_lora_rank": None, "rope_scaling": None,
    "rope_theta": 10000, "rms_norm_eps": 1e-6, "norm_topk_prob": True,
    "n_routed_experts": 8, "n_shared_experts": 2, "num_experts_per_tok": 2,
    "n_group": 1, "topk_group": 1, "routed_scaling_factor": 2.448,
    "vocab_size": 256, "served_context": 256,
    "limits_cell": "kanana2-serve-longdoc",
    "hyperparameters": TOY["hyperparameters"], "precision": TOY["precision"],
}
# sha256 (first 16 hex digits) of the jaxpr text of each program as traced
# by ``_program_text`` under this suite's conftest (x64 on) and this file's
# highest-precision fixture. Taken anew at PR 45 (parent 5fcaa60), which
# MEANT to change every serving program (one packed host array, unpacked
# as the program's first act: ``tests/test_mla_serving.py`` says what of
# the text differs from the parent's); gpt2's and lfm2's are the hashes
# that file holds them to. Before, they were PR 44's parent's (4ee199f):
# what PR 44 added to the kernels, the layers, the stores and the
# specification are new cases, and the calls these three families make
# trace to what they traced to before.
PARENT_PROGRAMS = {
    ("gpt2", "prefill"): "9136f6ee64a270a1",
    ("gpt2", "decode"): "10a41b56698b4941",
    ("lfm2", "prefill"): "d7e71823e122800d",
    ("lfm2", "decode"): "6692baeca2ac488b",
    ("kanana", "prefill"): "5dae05d5500ba61c",
    ("kanana", "decode"): "27df67f7cf202269",
}


def _program_text(net, which):
    cfg = GenerationConfig(block_len=16, max_seq_len=256, decode_slots=3,
                           prompt_rungs=(256,), prefill_batches=(2,))
    ps = GenerationProgramSet(net, config=cfg)
    cache = ps._cache_spec()
    if which == "prefill":
        jaxpr = jax.make_jaxpr(ps._prefill_fn())(
            ps.params, ps.state, cache, *ps._prefill_avals(2, 256))
    else:
        jaxpr = jax.make_jaxpr(ps._decode_fn())(
            ps.params, ps.state, cache, *ps._decode_avals())
    return re.sub(r"0x[0-9a-f]+", "0x", str(jaxpr))


@pytest.fixture(scope="module")
def accepted_nets():
    gpt = transformer_lm(vocab_size=256, d_model=128, n_heads=2, n_blocks=2,
                         max_length=256, token_input=True).init()
    lfm2 = lfm2_build.build(LFM2, LFM2["hyperparameters"], "serve")
    lfm2_build.install(lfm2, lfm2_weights.make(LFM2, 7, "serve"))
    kanana = ds_build.build(KANANA, KANANA["hyperparameters"], "serve")
    ds_build.install(kanana, ds_weights.make(KANANA, 7, "serve"))
    return {"gpt2": gpt, "lfm2": lfm2, "kanana": kanana}


@pytest.mark.parametrize("family,which", sorted(PARENT_PROGRAMS))
def test_the_accepted_families_programs_are_what_the_parent_traced(
        accepted_nets, monkeypatch, family, which):
    """gpt2-medium's, lfm2's and kanana's prefill and decode programs at a
    toy size (a rung of 256 and heads the flash kernel takes, so the
    prefill runs it and the decode step the paged kernel, as on the
    chip): no window anywhere in them, and the parent's text."""
    monkeypatch.setenv("DL4J_TPU_FUSED_ATTN_INTERPRET", "1")
    text = _program_text(accepted_nets[family], which)
    kernel = pallas_attention.FWD_NAME if which == "prefill" else (
        "paged_attention_latent_decode" if family == "kanana"
        else "paged_attention_decode")
    assert kernel in text
    assert "attention_window" not in text       # neither windowed kernel
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == \
        PARENT_PROGRAMS[(family, which)]


def test_laguna_programs_name_both_kinds_of_kernel(monkeypatch):
    """The cell's own programs at the hash tests' shapes: a prefill runs
    the causal AND the windowed flash forward, a decode step the plain AND
    the windowed paged kernel."""
    monkeypatch.setenv("DL4J_TPU_FUSED_ATTN_INTERPRET", "1")
    cfg = dict(TOY, head_dim=128, served_context=256, sliding_window=64)
    net = build.build(cfg, cfg["hyperparameters"], "serve")
    build.install(net, weights.make(cfg, 7, "serve"))
    pre, dec = _program_text(net, "prefill"), _program_text(net, "decode")
    assert pallas_attention.FWD_NAME in pre
    assert pallas_attention.WINDOW_FWD_NAME in pre
    assert "paged_attention_decode" in dec
    assert "paged_attention_window_decode" in dec
