"""The latent-attention / expert family (``model_type: deepseek_v3``) served
through ``ComputationGraph``, ``GraphDecodeSpec`` and ``GenerationEngine``
(ISSUE 40), at a toy size in float32 on the CPU, against the benchmark's
plain reference (``benchmarks/families/deepseek_v3/reference.py``, which
computes the expanded form only); the kernels' new cases against their
plain twins; and the programs the accepted cells run, held to what the
parent commit traced."""
import hashlib
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.families.deepseek_v3 import (build, kernel_costs,  # noqa: E402
                                             reference, weights)
from benchmarks.families.lfm2_moe import build as lfm2_build  # noqa: E402
from benchmarks.families.lfm2_moe import weights as lfm2_weights  # noqa: E402
from deeplearning4j_tpu.models.decode import (GraphDecodeSpec,  # noqa: E402
                                              LatentDecodeUnsupportedError)
from deeplearning4j_tpu.models.zoo_extra import transformer_lm  # noqa: E402
from deeplearning4j_tpu.nn.layers import (GatedMLP,  # noqa: E402
                                          LatentAttentionLayer,
                                          MixtureOfExpertsLayer)
from deeplearning4j_tpu.ops import pallas_attention  # noqa: E402
from deeplearning4j_tpu.ops.pallas_paged_attention import (  # noqa: E402
    paged_attention_decode, paged_attention_reference)
from deeplearning4j_tpu.parallel.ring_attention import attention  # noqa: E402
from deeplearning4j_tpu.serving import GenerationEngine  # noqa: E402
from deeplearning4j_tpu.serving.generation.kvcache import (  # noqa: E402
    PagedStore, make_pools)
from deeplearning4j_tpu.serving.generation.programs import (  # noqa: E402
    GenerationConfig, GenerationProgramSet, pack_decode, pack_prefill)

TOY = {
    "family": "deepseek_v3", "hidden_size": 128, "intermediate_size": 256,
    "moe_intermediate_size": 64, "first_k_dense_replace": 1,
    "num_hidden_layers": 3, "num_attention_heads": 4, "qk_nope_head_dim": 32,
    "qk_rope_head_dim": 16, "qk_head_dim": 48, "v_head_dim": 32,
    "kv_lora_rank": 64, "q_lora_rank": None, "rope_scaling": None,
    "rope_theta": 10000, "rms_norm_eps": 1e-6, "norm_topk_prob": True,
    "n_routed_experts": 8, "n_shared_experts": 2, "num_experts_per_tok": 2,
    "n_group": 1, "topk_group": 1, "routed_scaling_factor": 2.448,
    "vocab_size": 256, "served_context": 64,
    "limits_cell": "kanana2-serve-longdoc",
    "hyperparameters": {"learning_rate": 3e-4, "beta1": 0.9, "beta2": 0.999,
                        "epsilon": 1e-8},
    "precision": {"serve": {"dtype": "float32", "compute_dtype": None},
                  "train": {"dtype": "float32", "compute_dtype": None}},
}
CAP = 64
ROW = 128            # 64 latent + 16 rotary values, laid out on 128 lanes
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
        eng = GenerationEngine(net, model_name="lm", block_len=8,
                               max_seq_len=CAP, decode_slots=3,
                               prompt_rungs=(16, 32), prefill_batches=(1, 2))
    yield eng
    eng.stop()


def _prompts(seed, lengths):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, TOY["vocab_size"], n).astype(np.int32)
            for n in lengths]


# ------------------------------------------------------------ the forward
def test_the_graph_is_the_reference(toy):
    """``net.output`` over a whole sequence against the reference's full
    forward: logits through the softmax, every position."""
    net, w = toy
    ids = _prompts(0, [40])[0]
    probs = np.asarray(net.output(ids[None]))[0]
    want = np.asarray(jax.nn.softmax(reference.forward(w, TOY, ids), -1))
    np.testing.assert_allclose(probs, want, rtol=1e-4, atol=1e-7)


def test_the_layers_expanded_apply_is_the_references_attention(toy):
    """One ``LatentAttentionLayer.apply`` against the reference's mixer of
    the same weights (its residual taken off, its input norm a gain of
    ones): the rotation of interleaved pairs, the shared key part, the
    norm on the latent, the scale."""
    net, w = toy
    layer = net.vertices[net.vertex_names.index("l1_attn")].layer_conf
    p = {k.split("/")[1]: a for k, a in w.items() if k.startswith("l1_attn/")}
    x = jax.random.normal(jax.random.PRNGKey(3), (256, 128), jnp.float32)
    eps = TOY["rms_norm_eps"]
    u = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    got, _ = layer.apply(p, {}, u[None])
    want = reference._attn_mixer(
        x, jnp.ones((128,)), p["Wq"], p["Wkva"], p["kv_gain"], p["Wkvb"],
        p["Wo"], eps=eps, quant=None, n_head=4, dn=32, dr=16, dv=32,
        rank=64, theta=10000.0) - x
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               rtol=1e-4, atol=2e-6)


def test_the_specification_reads_the_latent_layers(toy):
    net, _ = toy
    spec = GraphDecodeSpec(net)
    assert spec.attn_names == ["l0_attn", "l1_attn", "l2_attn"]
    assert spec.moe_names == ["l1_ffn", "l2_ffn"]
    assert spec.latent and spec.cache_kind == "latent" and not spec.stateful
    assert (spec.n_blocks, spec.n_heads, spec.kv_heads, spec.head_dim) == \
        (3, 4, 1, ROW)
    assert spec.supports_head_sharding(1)
    assert not spec.supports_head_sharding(2)
    gpt = GraphDecodeSpec(transformer_lm(vocab_size=64, d_model=32, n_heads=2,
                                         n_blocks=1, max_length=16,
                                         token_input=True).init())
    assert gpt.cache_kind == "kv" and gpt.supports_head_sharding(2)


def test_absorbed_decode_token_by_token_is_the_references_forward(toy):
    """A prompt fed ONE token a step from position 0 through the absorbed
    form and the latent pages (block 8: the sequence crosses three pages),
    its logits at every position against the reference's expanded full
    forward."""
    net, w = toy
    spec = GraphDecodeSpec(net)
    ids = _prompts(2, [27])[0]
    want = np.asarray(reference.forward(w, TOY, ids))
    pool, = make_pools(spec.n_blocks, 9, 8, 1, ROW, jnp.float32, latent=True)
    tables = jnp.asarray([[1, 2, 3, 4, 5, 6, 7, 8]], jnp.int32)
    active = jnp.asarray([True])

    @jax.jit
    def step(pool, tok, pos):
        store = PagedStore(pool, None, tables, pos, active, 8)
        logits = spec.decode_step(net.params, net.state, tok, pos, store)
        return logits, store.pools[0]

    for t, tok in enumerate(ids):
        logits, pool = step(pool, jnp.asarray([tok]), jnp.asarray([t]))
        np.testing.assert_allclose(np.asarray(logits[0]), want[t],
                                   rtol=1e-4, atol=2e-5)


def test_prefill_at_a_padded_rung_then_twenty_decode_steps(toy):
    """Two prompts of different lengths in one prefill batch at rung 32
    (the expanded form, rows scattered into ONE pool), then 20 decode steps
    in the absorbed form over those pages: every token is the reference's
    argmax over the whole sequence."""
    net, w = toy
    cfg = GenerationConfig(block_len=8, max_seq_len=CAP, decode_slots=3,
                           prompt_rungs=(32,), prefill_batches=(2,))
    ps = GenerationProgramSet(net, config=cfg).warm()
    cache = ps.make_cache()
    assert [a.shape for a in cache] == [(3, cfg.num_blocks, 8, ROW)]
    assert ps.prefix_enabled and not ps.prefix_skipped_stateful
    assert ps.kv_bytes_per_token() == 3 * ROW * 4 == 3 * ps.cache_row_bytes()
    prompts = _prompts(1, [7, 29])
    S, mb = 3, cfg.blocks_per_seq
    tokens = np.zeros((2, 32), np.int32)
    tables = np.zeros((S, mb), np.int32)
    for i, p in enumerate(prompts):
        tokens[i, :len(p)] = p
        tables[i] = 1 + i * mb + np.arange(mb)
    z = lambda n, dt=np.int32: np.zeros(n, dt)
    first, cache, key = ps.run_prefill(
        cache, pack_prefill(tokens, np.asarray([7, 29], np.int32), tables[:2],
                            np.asarray([0, 1], np.int32), z(2, np.float32),
                            z(2)), ps.fresh_key())
    first, stats = ps.split_stats(first)
    assert stats is not None and 1 <= int(stats[1]) <= 2 * 8
    served = [[int(first[0])], [int(first[1])]]
    cur = np.zeros(S, np.int32)
    cur[:2] = first
    pos = np.asarray([7, 29, 0], np.int32)
    active = np.asarray([True, True, False])
    for _ in range(20):
        nxt, cache, key = ps.run_decode(cache, cur, pos, tables, active, key,
                                        z(S, np.float32), z(S))
        nxt, _ = ps.split_stats(nxt)
        for i in range(2):
            served[i].append(int(nxt[i]))
        cur[:2] = nxt[:2]
        pos[:2] += 1
    res = reference.token_gaps(w, TOY, list(zip(prompts, served)),
                               limits=NO_MARGIN)
    assert res["tokens"] == 42 and res["widest_gap"] <= 1e-3, res


def test_a_row_fed_from_the_device_gives_the_logits_of_the_host_fed_token(
        toy, monkeypatch):
    """The decode program takes a row's token from the step before, as it
    left the device, where the host does not know it (ISSUE 41): the
    step's LOGITS for such a row are those for the same token fed from
    the host, and the rows written to the latent pages are the same. The
    program as traced, with the sampler handing the logits through (and
    no counters behind them)."""
    from deeplearning4j_tpu.serving.generation import programs
    net, _ = toy
    cfg = GenerationConfig(block_len=8, max_seq_len=CAP, decode_slots=3,
                           prompt_rungs=(32,), prefill_batches=(2,))
    ps = GenerationProgramSet(net, config=cfg)
    ps.stats_len = 0
    monkeypatch.setattr(programs, "sample_tokens",
                        lambda logits, key, temp, topk: (logits, key))
    step = jax.jit(ps._decode_fn())
    S, mb = 3, cfg.blocks_per_seq
    tables = np.zeros((S, mb), np.int32)
    for i in range(S):
        tables[i] = 1 + i * mb + np.arange(mb)
    toks = np.asarray([17, 201, 5], np.int32)
    pos = np.asarray([0, 3, 9], np.int32)
    rest = (pos, tables, np.ones(S, np.bool_), np.zeros(S, np.float32),
            np.zeros(S, np.int32))
    host, (pool_h,), _ = step(
        ps.params, ps.state, ps.make_cache(),
        pack_decode(toks, np.ones(S, np.bool_), *rest),
        np.zeros(S, np.int32), ps.fresh_key())
    # rows 0 and 2 from the device (the host's copy of them is stale), row
    # 1 from the host (the device's is another token)
    prev = jnp.asarray([17, 99, 5], jnp.int32)
    dev, (pool_d,), _ = step(
        ps.params, ps.state, ps.make_cache(),
        pack_decode(np.asarray([3, 201, 250], np.int32),
                    np.asarray([False, True, False]), *rest),
        prev, ps.fresh_key())
    assert host.shape == (S, TOY["vocab_size"])
    assert jnp.array_equal(host, dev) and jnp.array_equal(pool_h, pool_d)
    # and the host's stale copy, had it been fed, gives other logits
    stale, _, _ = step(
        ps.params, ps.state, ps.make_cache(),
        pack_decode(np.asarray([3, 201, 250], np.int32),
                    np.ones(S, np.bool_), *rest), prev, ps.fresh_key())
    assert not jnp.array_equal(stale[0], host[0])
    assert jnp.array_equal(stale[1], host[1])


# ------------------------------------------------------------ the kernels
@pytest.mark.parametrize("W", [1, 3])
def test_the_latent_paged_kernel_is_the_dense_gather(W):
    """One pool of rows with no head axis, every query head reading the
    whole row as key and its first lanes as value, under a scale that is
    not 1 / sqrt(row): against the gather of every slot's whole table."""
    S, Hq, blk, mb, L, row, lanes = 4, 4, 8, 6, 2, 128, 64
    ks = jax.random.split(jax.random.PRNGKey(5), 2)
    pool = jax.random.normal(ks[0], (L, S * mb + 1, blk, row), jnp.float32)
    q = jax.random.normal(ks[1], (S, Hq, W, row), jnp.float32)
    tables = (1 + jnp.arange(S * mb, dtype=jnp.int32)).reshape(S, mb)
    lens = jnp.asarray([1, 9, 0, 40], jnp.int32)      # 0: an idle slot
    kw = dict(scale=48 ** -0.5, value_lanes=lanes)
    got = paged_attention_decode(q, pool, None, 1, tables, lens, **kw)
    want = paged_attention_reference(q, pool, None, 1, tables, lens, **kw)
    assert got.shape == (S, Hq, W, lanes)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4,
                               atol=1e-5)
    assert not np.asarray(got[2]).any()
    with pytest.raises(ValueError, match="scale and value_lanes"):
        paged_attention_decode(q, pool, None, 1, tables, lens)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_with_score_size_192_and_value_size_128(causal, monkeypatch):
    monkeypatch.setenv("DL4J_TPU_FUSED_ATTN_INTERPRET", "1")
    assert pallas_attention.fused_attention_applicable(
        1, 2, 512, 192, jnp.float32, 128)
    assert not pallas_attention.fused_attention_applicable(
        1, 2, 512, 192, jnp.float32, 80)
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    q = jax.random.normal(ks[0], (1, 2, 512, 192), jnp.float32)
    k = jax.random.normal(ks[1], (1, 2, 512, 192), jnp.float32)
    v = jax.random.normal(ks[2], (1, 2, 512, 128), jnp.float32)
    got = pallas_attention.flash_attention(q, k, v, causal=causal,
                                           scale=192 ** -0.5)
    want = attention(q, k, v, causal=causal, scale=192 ** -0.5)
    assert got.shape == (1, 2, 512, 128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4,
                               atol=1e-5)


def test_a_long_prefill_takes_the_flash_kernel_and_training_does_not(
        monkeypatch):
    monkeypatch.setenv("DL4J_TPU_FUSED_ATTN_INTERPRET", "1")
    layer = LatentAttentionLayer(n_in=64, n_out=64, n_heads=2,
                                 qk_nope_dim=128, qk_rope_dim=64, v_dim=128,
                                 kv_rank=32)
    p, _ = layer.init(jax.random.PRNGKey(0), None, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 256, 64), jnp.float32)
    served = str(jax.make_jaxpr(
        lambda x: layer.apply(p, {}, x, train=False)[0])(x))
    trained = str(jax.make_jaxpr(
        lambda x: layer.apply(p, {}, x, train=True)[0])(x))
    assert pallas_attention.FWD_NAME in served
    assert pallas_attention.FWD_NAME not in trained
    np.testing.assert_allclose(
        np.asarray(layer.apply(p, {}, x, train=False)[0]),
        np.asarray(layer.apply(p, {}, x, train=True)[0]), rtol=1e-4,
        atol=1e-5)


# ------------------------------------------------------------ the experts
def _expert_layer_and_weights(toy, held=None):
    _, w = toy
    moe = MixtureOfExpertsLayer(n_in=128, n_out=128, n_experts=8, top_k=2,
                                n_hidden=64, norm_eps=1e-20, held=held,
                                routed_scaling_factor=2.448)
    p = {k.split("/")[1]: a for k, a in w.items() if k.startswith("l1_ffn/")}
    if held:
        first, count = held
        p = dict(p, **{k: p[k][first:first + count]
                       for k in ("W1", "W3", "W2")})
    return moe, p


def test_routed_and_shared_experts_are_the_references(toy):
    _, w = toy
    x = jax.random.normal(jax.random.PRNGKey(4), (256, 128), jnp.float32)
    want, _ = reference._moe_ffn(x, dict(w, **{"l1_norm2/gain":
                                               jnp.ones((128,))}),
                                 "l1_", TOY, None)
    u = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6)
    moe, p = _expert_layer_and_weights(toy)
    shared = GatedMLP(n_in=128, n_out=128, n_hidden=128)
    ps = {k.split("/")[1]: a for k, a in w.items()
          if k.startswith("l1_shared/")}
    got = x + moe.apply(p, {}, u)[0] + shared.apply(ps, {}, u)[0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4,
                               atol=1e-5)


def test_shares_of_disjoint_held_ranges_and_one_shared_expert_add_up(toy):
    """Three chips holding experts 0-2, 3-5 and 6-7 each compute their
    share of the routed sum; the shared expert, which every chip would
    compute alike, is counted once: the whole layer."""
    _, w = toy
    u = jax.random.normal(jax.random.PRNGKey(6), (64, 128), jnp.float32)
    whole, p = _expert_layer_and_weights(toy)
    want = whole.apply(p, {}, u)[0]
    parts = jnp.zeros_like(want)
    for held in ((0, 3), (3, 3), (6, 2)):
        part, ph = _expert_layer_and_weights(toy, held)
        parts = parts + part.apply(ph, {}, u)[0]
    np.testing.assert_allclose(np.asarray(parts), np.asarray(want),
                               rtol=1e-4, atol=1e-6)
    eps6, _ = _expert_layer_and_weights(toy)
    eps6.norm_eps = 1e-6                    # the other family's: not equal
    assert float(jnp.max(jnp.abs(eps6.apply(p, {}, u)[0] - want))) > 0


# ------------------------------------------------------------- the engine
def test_a_prefix_hit_on_latent_pages_gives_the_tokens_of_a_miss(engine, toy):
    """The same block-aligned prompt twice: the second admission shares
    the first's latent pages (copy-on-write of the last) and replays one
    token; its tokens are the first's, and the reference's."""
    _, w = toy
    prompt = _prompts(8, [24])[0]                   # three whole blocks
    before = engine.metrics()["lm"]["prefix"]
    miss, _ = engine.generate(prompt, max_tokens=12)
    hit, _ = engine.generate(prompt, max_tokens=12)
    after = engine.metrics()["lm"]["prefix"]
    assert hit == miss
    assert after["hits"] == before["hits"] + 1
    assert after["skipped_stateful"] == 0
    res = reference.token_gaps(w, TOY, [(prompt, hit)], limits=NO_MARGIN)
    assert res["widest_gap"] <= 1e-3
    row = engine.models()["lm"]
    assert row["cache_kind"] == "latent" and row["prefix_cache"]
    assert row["cache_bytes_per_token"] == 3 * ROW * 4


def test_the_spans_carry_the_attention_counters(engine):
    from deeplearning4j_tpu import telemetry
    reg = telemetry.get_registry()
    seq = reg.last_seq
    prompt = _prompts(9, [13])[0]
    engine.generate(prompt, max_tokens=4)
    events = [e for e in reg.trace_events_since(seq) if e.get("ph") == "X"]
    pre = [e["args"] for e in events if e["name"] == "generation.prefill"]
    dec = [e["args"] for e in events if e["name"] == "generation.decode_step"]
    assert pre and pre[0]["attn_key_rows"] == 13 * 14 // 2
    assert dec and all(a["cache_row_bytes"] == ROW * 4 for a in dec)
    assert dec[0]["live_tokens"] == 14


@pytest.mark.parametrize("what,kw", [
    ("kv_cache_dtype='int8'", {"kv_cache_dtype": "int8"}),
    ("speculative decoding", {"draft": True})])
def test_the_int8_tier_and_speculation_refuse_a_latent_cache_by_name(
        toy, what, kw):
    net, _ = toy
    cfg = GenerationConfig(block_len=8, max_seq_len=CAP, decode_slots=2,
                           kv_cache_dtype=kw.get("kv_cache_dtype"))
    draft = net if kw.get("draft") else None
    with pytest.raises(LatentDecodeUnsupportedError, match="l0_attn") as e:
        GenerationProgramSet(net, config=cfg, draft_net=draft)
    assert what in str(e.value)


def test_a_latent_draft_is_refused_too(toy):
    net, _ = toy
    target = transformer_lm(vocab_size=256, d_model=32, n_heads=2, n_blocks=1,
                            max_length=CAP, token_input=True).init()
    cfg = GenerationConfig(block_len=8, max_seq_len=CAP, decode_slots=2)
    with pytest.raises(LatentDecodeUnsupportedError, match="draft"):
        GenerationProgramSet(target, config=cfg, draft_net=net)


# ------------------------------------------------- the costs of the kernels
def test_the_attention_costs_count_what_the_result_requires():
    cfg = dict(TOY, num_hidden_layers=6, num_attention_heads=32,
               kv_lora_rank=512, qk_rope_head_dim=64, qk_head_dim=192,
               v_head_dim=128)
    flops, nbytes = kernel_costs.mla_decode_cost(cfg, 1000.0, 1280)
    assert flops == 6 * 1000 * 32 * 2 * (576 + 512)
    assert nbytes == 6 * 1000 * 1280
    flops, nbytes = kernel_costs.mla_prefill_cost(cfg, 5000.0, 100.0)
    assert flops == 6 * 5000 * 32 * 2 * (192 + 128)
    assert nbytes == 6 * 100 * 32 * 2 * (192 + 128) * 2
    assert kernel_costs.expert_sets(dict(cfg, n_routed_experts=128,
                                         first_k_dense_replace=1)) == 640


def _obs(pre_args, dec_args, by_op_s):
    """A closed-loop window of 10 s holding one prefill span of 1 s and
    twelve decode steps of 0.1 s, of which 4 s were traced."""
    ev = [{"ph": "X", "name": "generation.prefill", "ts": 1e6, "dur": 1e6,
           "args": pre_args}]
    ev += [{"ph": "X", "name": "generation.decode_step",
            "ts": (3 + 0.2 * i) * 1e6, "dur": 1e5, "args": dec_args}
           for i in range(12)]
    cfg = dict(TOY, num_hidden_layers=6, num_attention_heads=32,
               kv_lora_rank=512, qk_rope_head_dim=64, qk_head_dim=192,
               v_head_dim=128)
    return {"kind": "closed_loop", "config": cfg, "events": ev,
            "epoch_ns": 0, "window_perf": (0.0, 10.0),
            "device": {"kind": "TPU v5 lite"},
            "engine": {"num_blocks": 11, "block_len": 100,
                       "cache_kind": "latent"},
            "trace": {"by_op_s": by_op_s, "busy_s": 3.0, "window_s": 4.0}}


def test_the_new_readers_read_the_spans_and_fall_silent_without_them():
    from benchmarks import run as harness
    read = lambda name, obs: harness.load_reader(name).read(obs)
    obs = _obs({"attn_key_rows": 50_000_000, "tokens": 10_000},
               {"live_tokens": 550, "cache_row_bytes": 1280},
               {"paged_attention_latent_decode": 0.5,
                "flash_attention_fwd": 1.0})
    # decode: memory binds; 12 steps over a 10 s window, 4 s of it traced
    least = 12 * 6 * 550 * 1280 / 819e9
    assert read("kernels.mla_decode_roofline_pct.tput", obs) == \
        pytest.approx(100 * least / 10.0 * 4.0 / 0.5)
    least = 6 * 50e6 * 32 * 2 * 320 / 197e12          # compute binds
    assert read("kernels.mla_prefill_roofline_pct.tput", obs) == \
        pytest.approx(100 * least / 10.0 * 4.0 / 1.0)
    assert read("mla.attention_busy_pct.tput", obs) == pytest.approx(50.0)
    assert read("kvcache.pool_live_pct.tput", obs) == pytest.approx(50.0)
    # the parent's spans and record carry none of it: nothing, no raise
    bare = _obs({"tokens": 10_000}, {"live_tokens": 550}, {"fusion": 1.0})
    del bare["engine"]["cache_kind"]
    for name in ("kernels.mla_decode_roofline_pct.tput",
                 "kernels.mla_prefill_roofline_pct.tput",
                 "mla.attention_busy_pct.tput", "kvcache.pool_live_pct.tput"):
        assert read(name, bare) is None
        assert read(name, {"kind": "closed_loop", "config": {}}) is None


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
# sha256 (first 16 hex digits) of the jaxpr text of each program as traced
# by this very function under this suite's conftest (x64 on). Taken anew at
# PR 45 (parent 5fcaa60), which MEANT to change every serving program: a
# program takes its per-row host arguments as ONE packed int32 array
# (``programs.pack_decode`` / ``pack_prefill``) and unpacks it as its first
# act. Beside the signature line the texts differ from the parent's by the
# unpacking alone (a slice and a squeeze a column, two ``ne 0`` for the
# decode step's masks, one ``bitcast_convert_type`` for the temperature:
# 19-21 lines a program, compared line by line with the variables' names
# taken out). History: the prefill hashes before were those of commit
# 7936418 (PR 40's parent), the decode hashes PR 41's (the step before's
# result and the mask of the rows the host knows).
PARENT_PROGRAMS = {
    ("gpt2", "prefill"): "9136f6ee64a270a1",
    ("gpt2", "decode"): "10a41b56698b4941",
    ("lfm2", "prefill"): "d7e71823e122800d",
    ("lfm2", "decode"): "6692baeca2ac488b",
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
    return {"gpt2": gpt, "lfm2": lfm2}


@pytest.mark.parametrize("family,which", sorted(PARENT_PROGRAMS))
def test_the_accepted_families_programs_are_what_the_parent_traced(
        accepted_nets, monkeypatch, family, which):
    """gpt2-medium's and lfm2's prefill and decode programs at a toy size
    (heads of 64 and a rung of 256, so the prefill takes the flash kernel
    and the decode step the paged one, as on the chip)."""
    monkeypatch.setenv("DL4J_TPU_FUSED_ATTN_INTERPRET", "1")
    text = _program_text(accepted_nets[family], which)
    kernel = pallas_attention.FWD_NAME if which == "prefill" \
        else "paged_attention_decode"
    assert kernel in text
    assert "latent" not in text
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == \
        PARENT_PROGRAMS[(family, which)]
