"""The hybrid convolution / attention / mixture-of-experts family served
through ``ComputationGraph``, ``GraphDecodeSpec`` and ``GenerationEngine``
(ISSUE 38), at a toy size in float32 on the CPU, against the benchmark's
plain reference (``benchmarks/families/lfm2_moe/reference.py``); and the
GPT-2 graph through the generalised specification against the arithmetic
the names-keyed specification had."""
import os
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.families.lfm2_moe import build, reference, weights  # noqa: E402
from deeplearning4j_tpu import telemetry  # noqa: E402
from deeplearning4j_tpu.models.decode import (GraphDecodeSpec,  # noqa: E402
                                              StatefulDecodeUnsupportedError,
                                              TransformerDecodeSpec,
                                              naive_generate)
from deeplearning4j_tpu.models.zoo_extra import transformer_lm  # noqa: E402
from deeplearning4j_tpu.serving import GenerationEngine  # noqa: E402
from deeplearning4j_tpu.serving.generation.programs import (  # noqa: E402
    GenerationConfig, GenerationProgramSet, pack_prefill)

TOY = {
    "family": "lfm2_moe", "conv_L_cache": 3, "hidden_size": 128,
    "intermediate_size": 256, "moe_intermediate_size": 128,
    "layer_types": ["conv", "full_attention", "conv", "full_attention"],
    "norm_eps": 1e-5, "norm_topk_prob": True, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_dense_layers": 1, "num_experts": 8,
    "num_experts_per_tok": 2, "rope_parameters": {"rope_theta": 1000000},
    "routed_scaling_factor": 1, "vocab_size": 256, "served_context": 64,
    "limits_cell": "lfm2moe-serve-extract",
    "hyperparameters": {"learning_rate": 3e-4, "beta1": 0.9, "beta2": 0.999,
                        "epsilon": 1e-8},
    "precision": {"serve": {"dtype": "float32", "compute_dtype": None},
                  "train": {"dtype": "float32", "compute_dtype": None}},
}
CAP = 64
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


def test_the_specification_reads_the_graph_by_layer_kinds(toy):
    net, _ = toy
    spec = GraphDecodeSpec(net)
    assert spec.attn_names == ["l1_mixer", "l3_mixer"]
    assert spec.recurrent_names == ["l0_mixer", "l2_mixer"]
    assert spec.moe_names == ["l1_ffn", "l2_ffn", "l3_ffn"]
    assert (spec.n_blocks, spec.n_heads, spec.kv_heads, spec.head_dim) == \
        (2, 4, 2, 32)
    assert spec.stateful and spec.pos_name is None and spec.token_input
    assert spec.recurrent_state_shape(4) == (2, 4, 2, 128)
    assert TransformerDecodeSpec is GraphDecodeSpec


def test_prefill_at_a_padded_rung_then_twenty_decode_steps(toy):
    """Two prompts of DIFFERENT lengths in one prefill batch at rung 32,
    then 20 decode steps through the paged cache and the convolution state:
    every token is the reference's argmax over the whole sequence (the
    convolution state taken at the true length, not at the rung, is what
    this catches: a state read at row 31 of a 7-token prompt is padding)."""
    net, w = toy
    cfg = GenerationConfig(block_len=8, max_seq_len=CAP, decode_slots=3,
                           prompt_rungs=(32,), prefill_batches=(2,))
    ps = GenerationProgramSet(net, config=cfg).warm()
    cache = ps.make_cache()
    assert [a.shape for a in cache] == [
        (2, cfg.num_blocks, 8, 2 * 32), (2, cfg.num_blocks, 8, 2 * 32),
        (2, 4, 2, 128)]                 # 2 kv heads; 2 conv layers, 3+1 slots
    prompts = _prompts(1, [7, 29])
    S, mb = 3, cfg.blocks_per_seq
    tokens = np.zeros((2, 32), np.int32)
    tables = np.zeros((S, mb), np.int32)
    for i, p in enumerate(prompts):
        tokens[i, :len(p)] = p
        tables[i] = 1 + i * mb + np.arange(mb)
    lengths = np.asarray([7, 29], np.int32)
    z = lambda n, dt=np.int32: np.zeros(n, dt)
    first, cache, key = ps.run_prefill(
        cache, pack_prefill(tokens, lengths, tables[:2],
                            np.asarray([0, 1], np.int32), z(2, np.float32),
                            z(2)), ps.fresh_key())
    first, stats = ps.split_stats(first)
    assert stats is not None and 1 <= int(stats[1]) <= 3 * 8
    served = [[int(first[0])], [int(first[1])]]
    cur = np.zeros(S, np.int32)
    cur[:2] = first
    pos = np.asarray([7, 29, 0], np.int32)
    active = np.asarray([True, True, False])
    for _ in range(20):
        nxt, cache, key = ps.run_decode(cache, cur, pos, tables, active, key,
                                        z(S, np.float32), z(S))
        nxt, stats = ps.split_stats(nxt)
        assert 3 <= int(stats[1]) <= 3 * 4      # 2 live slots x 2 experts
        for s in range(2):
            served[s].append(int(nxt[s]))
        cur[:2], pos = nxt[:2], pos + np.asarray([1, 1, 0], np.int32)
    res = reference.token_gaps(w, TOY, list(zip(prompts, served)),
                               limits=NO_MARGIN)
    assert res["tokens"] == 42 and res["positions_left_out"] == 0
    assert res["widest_gap"] < 1e-4, res["widest_gap"]
    # the idle slot's convolution state was left alone
    assert not np.any(np.asarray(cache[2][:, 2]))


def test_engine_serves_what_a_full_recompute_serves(toy, engine):
    """More requests than slots, differing lengths, through the scheduler:
    token for token what ``naive_generate`` (full recompute, no cache, no
    state) gives."""
    net, _ = toy
    spec = GraphDecodeSpec(net)
    prompts = _prompts(2, [5, 13, 30, 1, 17])
    got = {}

    def go(i, p):
        got[i] = engine.generate(p, max_tokens=12, stream=False, timeout=120)

    ths = [threading.Thread(target=go, args=(i, p))
           for i, p in enumerate(prompts)]
    [t.start() for t in ths]
    [t.join() for t in ths]
    for i, p in enumerate(prompts):
        assert got[i] == (naive_generate(net, p, 12, pad_to=CAP, spec=spec),
                          "length")


def test_a_slot_released_and_reused_starts_from_a_zero_state(toy, engine):
    """One slot at a time: a long request leaves its convolution state in
    the slot's row; the next request, a one-token prompt, must start from
    rows of zeros before its first position, as a fresh sequence does."""
    net, _ = toy
    spec = GraphDecodeSpec(net)
    long_p, short_p = _prompts(3, [30, 1])
    for p in (long_p, short_p, long_p[:2]):
        got, _ = engine.generate(p, max_tokens=8, stream=False, timeout=120)
        assert got == naive_generate(net, p, 8, pad_to=CAP, spec=spec)


def test_the_record_counts_what_this_model_holds(toy, engine):
    row = engine.models()["lm"]
    # 2 attention layers x (K + V) x 2 key-value heads x 32 x 4 bytes
    assert row["kv_bytes_per_token"] == 2 * 2 * 2 * 32 * 4
    # 2 convolution layers x (3 slots + the trash row) x 2 rows x 128 x 4
    assert row["conv_state_bytes"] == 2 * 4 * 2 * 128 * 4
    assert row["prefix_cache"] is False and row["adapter"] == "paged"


def test_spans_carry_the_expert_counters_and_nothing_compiles(toy, engine):
    """``generation.prefill`` and ``generation.decode_step`` carry what the
    expert layers routed, read back with the tokens: no compile in the
    window, as many dispatches as before."""
    reg = telemetry.get_registry()
    was = reg.enabled
    reg.enabled = True
    try:
        seq0 = reg.last_seq
        compiles0 = telemetry.xla_compile_count()
        p = _prompts(4, [11])[0]
        engine.generate(p, max_tokens=6, stream=False, timeout=120)
        events = reg.trace_events_since(seq0)
        assert telemetry.xla_compile_count() == compiles0
    finally:
        reg.enabled = was
    spans = lambda n: [e["args"] for e in events
                       if e.get("ph") == "X" and e["name"] == n]
    pre = spans("generation.prefill")
    assert len(pre) == 1
    # 3 expert layers x 2 experts a token
    assert pre[0]["moe_pairs"] == 11 * 6
    assert pre[0]["moe_pairs_padded"] == (16 - 11) * 6
    assert 11 * 2 / 8 <= pre[0]["moe_load_max"] <= 11
    assert 2 <= pre[0]["experts_touched"] <= 24
    steps = spans("generation.decode_step")
    assert len(steps) == 5
    for a in steps:
        assert a["moe_pairs"] == 6 and 3 <= a["experts_touched"] <= 6
    # one dispatch and one read-back a program call, as for any model
    assert len(spans("generation.dispatch")) == 6
    assert len(spans("generation.readback")) == 6


# --------------------------- what does not carry the state refuses by name
def test_int8_tier_refuses_a_model_with_state(toy):
    net, _ = toy
    with pytest.raises(StatefulDecodeUnsupportedError, match="int8"):
        GenerationProgramSet(net, config=GenerationConfig(
            block_len=8, max_seq_len=CAP, decode_slots=2,
            kv_cache_dtype="int8"))


def test_speculation_refuses_a_model_with_state(toy):
    net, _ = toy
    draft = transformer_lm(vocab_size=256, d_model=32, n_heads=2, n_blocks=1,
                           max_length=CAP, token_input=True).init()
    with pytest.raises(StatefulDecodeUnsupportedError, match="speculative"):
        GenerationProgramSet(net, config=GenerationConfig(
            block_len=8, max_seq_len=CAP, decode_slots=2), draft_net=draft)
    # and as a draft: the dense draft cache keeps K/V alone
    with pytest.raises(StatefulDecodeUnsupportedError, match="draft"):
        GenerationProgramSet(draft, config=GenerationConfig(
            block_len=8, max_seq_len=CAP, decode_slots=2), draft_net=net)


def test_a_decode_window_refuses_a_model_with_state(toy):
    net, _ = toy
    spec = GraphDecodeSpec(net)
    with pytest.raises(StatefulDecodeUnsupportedError, match="window"):
        spec.window_hidden(net.params, net.state, jnp.zeros((1, 3), jnp.int32),
                           jnp.zeros((1,), jnp.int32), None)


def test_the_prefix_cache_is_skipped_and_counted_never_wrongly_hit(toy):
    """Asked for by name, the prefix cache still does not serve a model
    whose sequences carry a convolution state: the same block-aligned
    prompt twice is two prefills, both right, and the skips are counted."""
    net, _ = toy
    spec = GraphDecodeSpec(net)
    eng = GenerationEngine(net, model_name="lm", block_len=8, max_seq_len=CAP,
                           decode_slots=2, prompt_rungs=(32,),
                           prefill_batches=(1,), prefix_cache=True)
    try:
        assert eng.models()["lm"]["prefix_cache"] is False
        p = _prompts(5, [24])[0]                    # three whole blocks
        want = naive_generate(net, p, 6, pad_to=CAP, spec=spec)
        for _ in range(2):
            got, _ = eng.generate(p, max_tokens=6, stream=False, timeout=120)
            assert got == want
        m = eng.metrics()["lm"]
        assert m["prefix"]["skipped_stateful"] == 2
        assert m["prefix"]["hits"] == 0 and m["prefills"] == 2
    finally:
        eng.stop()


# ------------------------------------ GPT-2 through the generalised spec
class _RecordingStore:
    """A dense one-sequence store: keeps what it is given, attends over
    what it holds (no paging), so that two specifications can be compared
    on the same protocol."""

    def __init__(self, n_layers, cap, H, Dh, pos):
        self.k = jnp.zeros((n_layers, 1, cap, H, Dh))
        self.v = jnp.zeros((n_layers, 1, cap, H, Dh))
        self.pos = pos

    def attend(self, i, q, k_tok, v_tok):
        from deeplearning4j_tpu.parallel.ring_attention import attention
        self.k = self.k.at[i, 0, self.pos].set(k_tok[0])
        self.v = self.v.at[i, 0, self.pos].set(v_tok[0])
        mask = (jnp.arange(self.k.shape[2]) <= self.pos)[None]
        return attention(q, self.k[i].transpose(0, 2, 1, 3),
                         self.v[i].transpose(0, 2, 1, 3), causal=False,
                         key_mask=mask)


def _gpt2_as_before(net, params, state, tokens, rows, store, pos):
    """The arithmetic ``TransformerDecodeSpec`` had when it walked the
    vertex NAMES of ``models.transformer_lm`` (the tree before ISSUE 38),
    written out: (prefill logits, ks, vs, one decode step's logits)."""
    idx = {n: i for i, n in enumerate(net.vertex_names)}
    v = dict(zip(net.vertex_names, net.vertices))
    p = lambda n: params[idx[n]]
    run = lambda n, x: v[n].apply(p(n), state[idx[n]], [x], train=False,
                                  rng=None)[0]
    H = v["b0_attn"].layer_conf.n_heads
    d = v["b0_attn"].layer_conf.n_out
    acts, _ = net.apply_fn(params, state, [tokens], train=False)
    y = jnp.take_along_axis(acts["ln_f"], rows[:, None, None], axis=1)
    logits = v["head"].layer_conf.pre_output(p("head"), y)[:, 0]
    ks, vs = [], []
    n_blocks = sum(1 for n in idx if n.endswith("_attn"))
    for i in range(n_blocks):
        a = acts[f"b{i}_ln1"]
        B, L, _ = a.shape
        ks.append((a @ p(f"b{i}_attn")["Wk"]).reshape(B, L, H, d // H))
        vs.append((a @ p(f"b{i}_attn")["Wv"]).reshape(B, L, H, d // H))
    tok = tokens[:, pos[0]]
    x = v["embed"].layer_conf.apply(p("embed"), {}, tok[:, None],
                                    train=False)[0]
    x = v["pos"].layer_conf.act(x + p("pos")["P"][pos][:, None, :])
    for i in range(n_blocks):
        ap, layer = p(f"b{i}_attn"), v[f"b{i}_attn"].layer_conf
        yy = run(f"b{i}_ln1", x)
        B = yy.shape[0]
        q = (yy @ ap["Wq"]).reshape(B, 1, H, d // H).transpose(0, 2, 1, 3)
        out = store.attend(i, q, (yy @ ap["Wk"]).reshape(B, H, d // H),
                           (yy @ ap["Wv"]).reshape(B, H, d // H))
        out = out.transpose(0, 2, 1, 3).reshape(B, 1, d)
        x = x + layer.act(out @ ap["Wo"] + ap["b"])
        x = x + run(f"b{i}_ff2", run(f"b{i}_ff1", run(f"b{i}_ln2", x)))
    step = v["head"].layer_conf.pre_output(p("head"), run("ln_f", x))[:, 0]
    return logits, ks, vs, step


def test_gpt2_prefill_and_decode_equal_to_the_bit_what_the_tree_gave():
    net = transformer_lm(vocab_size=64, d_model=32, n_heads=2, n_blocks=2,
                         max_length=16, token_input=True).init()
    spec = GraphDecodeSpec(net)
    assert spec.attn_names == ["b0_attn", "b1_attn"] and not spec.stateful
    assert spec.pos_name == "pos" and spec.n_moe == 0
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, 64, (1, 16)),
                         jnp.int32)
    rows = jnp.asarray([9], jnp.int32)
    pos = jnp.asarray([5], jnp.int32)

    def new(params, state):
        store = _RecordingStore(2, 16, 2, 16, 5)
        logits, ks, vs = spec.prefill_forward(params, state, tokens, rows)
        step = spec.decode_step(params, state, tokens[:, 5], pos, store)
        return logits, ks, vs, step

    def old(params, state):
        return _gpt2_as_before(net, params, state, tokens, rows,
                               _RecordingStore(2, 16, 2, 16, 5), pos)

    got = jax.jit(new)(net.params, net.state)
    want = jax.jit(old)(net.params, net.state)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.shape == b.shape and bool(jnp.array_equal(a, b))
    # and its cache and programs are what they were: two pools, no state,
    # no counters behind the tokens
    ps = GenerationProgramSet(net, config=GenerationConfig(
        block_len=8, max_seq_len=16, decode_slots=2))
    assert len(ps.make_cache()) == 2 and ps.stats_len == 0
    assert ps.recurrent_state_bytes() == 0 and ps.prefix_enabled


# ----------------------------------------- the cell's rehearsal on the CPU
def test_the_closed_loop_kind_runs_the_family_and_the_control_fails(
        toy, engine, monkeypatch):
    """``lfm2moe-serve-extract`` rehearsed at the toy size: the closed-loop
    kind's own ``run`` (engine, callers, window, sampling, the reference's
    check) with the family's modules, then the float8 control in the
    program's place, which must stand off from the reference where the
    float32 program sits on it. Nothing here hangs on the machine's speed
    or on the real cell's limits (the driver's run of PR 45's tree failed
    this test on a loaded CPU: a slow window completes one or two requests,
    and over their handful of tokens float8 may put the reference's own
    token first everywhere, a gap of 0.006 where 0.05 was asked): one
    completion is enough for the kind's run, the toy is held to limits of
    its own (nothing left out for its routing margin), and how far float8
    stands off is read over a FIXED sample of a hundred served tokens,
    handed to the comparison the kind's run makes (``check_outputs``: the
    sampling, the weights made anew, the control in the program's place,
    the checks), so the control fails THROUGH the harness."""
    from benchmarks import run as harness
    from benchmarks.kinds import _serve, closed_loop
    from benchmarks.lib.correct import Checks
    fam = {k: __import__(f"benchmarks.families.lfm2_moe.{k}",
                         fromlist=[k])
           for k in ("build", "weights", "reference", "flops")}
    traffic = {
        "kind": "closed_loop", "callers": 4, "preroll_s": 0.5,
        "timeout_s": 120.0, "block": 8, "blocks": 400,
        "lengths": {"prompt": {"kind": "uniform", "lo": 4, "hi": 30},
                    "output": {"kind": "uniform", "lo": 2, "hi": 6},
                    "max_total": CAP, "pairing_seed": 1},
        "engine": {"block_len": 8, "max_seq_len": CAP, "decode_slots": 3,
                   "prompt_rungs": [16, 32], "prefill_batches": [1, 2]},
        "check": {"min_tokens": 40, "max_requests": 12}}
    limits = {"widest_logit_gap": 1e-3, "routing_margin": 0.0,
              "close_margin_share": 0.0}
    monkeypatch.setattr(reference, "cell_limits", lambda cfg: limits)
    out = {}
    for control in (False, True):
        ctx = {"cell": {"name": "toy", "chips": 1}, "config": TOY,
               "traffic": traffic, "limits": limits, "seed": 2 ** 31 + 5,
               "seconds": 6.0, "trace": False, "rehearsal": True,
               "device": {"platform": "cpu", "kind": "cpu", "count": 1},
               "t_start": time.perf_counter(), "log": lambda m: None,
               "checks": Checks(), "control": control, "family": fam,
               "tracer": harness.Tracer(False, "unused"),
               "memory_peak_bytes": lambda: 0,
               "epoch_ns": time.time_ns() - time.perf_counter_ns()}
        res = closed_loop.run(ctx)
        out[control] = (ctx, res)
    ctx, res = out[False]
    assert res["failed"] == 0 and res["counts"]["completed"] >= 1
    assert res["counts"]["compiles_in_window"] == 0
    assert ctx["checks"].correct, ctx["checks"].rows
    assert res["obs"]["engine"]["conv_state_bytes"] > 0
    assert out[True][0]["control_result"]["tokens"] > 0
    # the toy's logits are not the cell's: what holds at any size is that
    # the float32 program sits on the reference and float8 does not
    done = [{"prompt": p,
             "tokens": engine.generate(p, max_tokens=25, stream=False)[0]}
            for p in _prompts(11, [12, 20, 27, 30])]
    fixed = dict(out[True][0], checks=Checks(), seed=7,   # the toy's weights
                 traffic=dict(traffic, check={"min_tokens": 100,
                                              "max_requests": 4}))
    _serve.check_outputs(fixed, done, 0)
    c = fixed["control_result"]
    assert fixed["checks"].correct, fixed["checks"].rows
    assert c["tokens"] == 100
    assert c["control_widest_gap"] > 0.05 and c["kept_widest_gap"] < 1e-3
