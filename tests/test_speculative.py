"""Speculative decoding (ISSUE 14 tentpole, draft/verify leg).

Pins:
  - exact-output: speculative greedy decode (draft propose + batched
    verify + longest-agreeing-prefix acceptance) is token-for-token
    identical to plain greedy decode, f32 AND bf16, truncated-transformer
    (dense cache) AND LSTM (state cache) drafts, sequential AND under
    concurrent continuous-batched admission, composed with prefix-cache
    hits/COW;
  - full-acceptance regression: a self-draft (draft == target) accepts
    every proposal — the draft cache can never carry an unwritten gap
    behind the next verify window;
  - stop tokens / max_tokens landing MID-window truncate exactly as plain
    decode; sampling requests and per-request opt-outs ride the plain
    path;
  - hot-swap cohort pinning: in-flight requests finish on the old params
    AND old draft; same-arch swaps reuse every compiled executable;
  - the acceptance yield (>= 2 tokens a target dispatch with a
    truncated draft). Zero steady-state recompiles with prefix cache +
    speculation both enabled is the ``speculative`` case of
    tests/test_generation.py::test_zero_recompiles_generation_after_warmup.
"""
import threading
import time

import numpy as np
import pytest

from deeplearning4j_tpu.models.decode import (TransformerDecodeSpec,
                                              naive_generate,
                                              truncated_draft)
from deeplearning4j_tpu.models.zoo_extra import (text_generation_lstm,
                                                 transformer_lm)
from deeplearning4j_tpu.serving import (GenerationEngine,
                                        xla_compile_count)
from deeplearning4j_tpu.serving.generation import accept_greedy

R = np.random.default_rng(4321)


def _lm(seed=7, vocab=53, d_model=32, n_heads=2, n_blocks=2, max_length=64,
        dtype="float32"):
    return transformer_lm(vocab_size=vocab, d_model=d_model,
                          n_heads=n_heads, n_blocks=n_blocks,
                          max_length=max_length, seed=seed, dtype=dtype,
                          token_input=True).init()


# ------------------------------------------------------------ rule + builder
def test_accept_greedy_rule():
    props = np.array([[5, 6, 7], [5, 6, 7], [5, 9, 7], [1, 2, 3]])
    targs = np.array([[5, 6, 7, 8], [5, 6, 9, 8], [5, 6, 7, 8],
                      [9, 2, 3, 4]])
    counts, emitted = accept_greedy(props, targs)
    assert counts.tolist() == [3, 2, 1, 0]
    assert emitted[0] == [5, 6, 7, 8]       # all accepted + bonus token
    assert emitted[1] == [5, 6, 9]          # correction replaces p_3
    assert emitted[2] == [5, 6]
    assert emitted[3] == [9]                # immediate correction


def test_truncated_draft_shares_target_weights():
    net = _lm()
    draft = truncated_draft(net, 1)
    src = dict(zip(net.vertex_names, net.params))
    dst = dict(zip(draft.vertex_names, draft.params))
    assert "b1_attn" not in dst and "b0_attn" in dst
    assert np.array_equal(np.asarray(dst["embed"]["W"]),
                          np.asarray(src["embed"]["W"]))
    assert np.array_equal(np.asarray(dst["b0_attn"]["Wq"]),
                          np.asarray(src["b0_attn"]["Wq"]))
    with pytest.raises(ValueError):
        truncated_draft(net, 3)             # only 2 blocks exist


def test_spec_config_validation():
    net = _lm(seed=11, vocab=37, d_model=16, n_blocks=1, max_length=32)
    lstm = text_generation_lstm(vocab_size=37, hidden=12,
                                max_length=32, seed=5).init()
    # LSTM target cannot speculate (no block tables to verify over)
    with pytest.raises(ValueError, match="paged"):
        GenerationEngine(lstm, model_name="x", block_len=8, max_seq_len=32,
                         decode_slots=1, prefill_batches=(1,),
                         prompt_rungs=(16,), draft=net, warm=False)
    # draft/target vocab mismatch
    bad = text_generation_lstm(vocab_size=29, hidden=12,
                               max_length=32, seed=5).init()
    with pytest.raises(ValueError, match="vocab"):
        GenerationEngine(net, model_name="x", block_len=8, max_seq_len=32,
                         decode_slots=1, prefill_batches=(1,),
                         prompt_rungs=(32,), draft=bad, warm=False)
    with pytest.raises(ValueError, match="spec_k"):
        GenerationEngine(net, model_name="x", block_len=8, max_seq_len=32,
                         spec_k=-1, warm=False)


# ------------------------------------------------- shared engine + the pins
@pytest.fixture(scope="module")
def spec_lm():
    """One warmed f32 engine with a truncated-transformer draft (dense
    adapter, k=3) AND the prefix cache on — the two tentpole features
    composed. Read-only for the tests below."""
    net = _lm()
    draft = truncated_draft(net, 1)
    eng = GenerationEngine(net, model_name="lm", block_len=8, max_seq_len=64,
                           decode_slots=4, prefill_batches=(1, 2),
                           prompt_rungs=(64,), draft=draft, spec_k=3)
    yield net, TransformerDecodeSpec(net), eng
    eng.stop()


def test_speculative_greedy_bit_identical_f32(spec_lm):
    """THE pin: speculative greedy output == naive full-recompute greedy,
    sequential AND 8 concurrent clients over 4 slots (verify windows
    interleaving with step-boundary admission), WITH prefix hits/COW from
    the repeated prompts."""
    net, spec, eng = spec_lm
    prompts = [R.integers(1, 53, size=n).tolist() for n in (5, 16, 9)]
    refs = [naive_generate(net, p, 12, pad_to=64, spec=spec)
            for p in prompts]
    for p, want in zip(prompts, refs):
        toks, reason = eng.generate(p, max_tokens=12)
        assert (toks, reason) == (want, "length")
    outs = {}

    def client(i):
        st = eng.generate(prompts[i % 3], max_tokens=12, stream=True)
        outs[i] = (list(st), st.finish_reason)

    threads = [threading.Thread(target=client, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for i in range(8):
        assert outs[i] == (refs[i % 3], "length"), f"client {i} diverged"
    snap = eng.metrics()["lm"]
    assert snap["speculative"]["verify_steps"] > 0
    assert snap["speculative"]["emitted"] > 0
    assert snap["prefix"]["hits"] >= 3          # repeats hit the cache


def test_stop_token_and_length_mid_window(spec_lm):
    """A stop token (or the max_tokens budget) landing in the MIDDLE of a
    verify window truncates exactly where plain greedy decode stops."""
    net, spec, eng = spec_lm
    p = [3, 9, 4]
    greedy = naive_generate(net, p, 9, pad_to=64, spec=spec)
    stop = greedy[4]                             # mid-window position
    toks, reason = eng.generate(p, max_tokens=9, stop=[stop])
    assert reason == "stop"
    assert toks == greedy[:greedy.index(stop)]
    # odd max_tokens not divisible by the k+1 window
    toks, reason = eng.generate(p, max_tokens=7)
    assert (toks, reason) == (greedy[:7], "length")


def test_sampling_and_opt_out_ride_plain_path(spec_lm):
    net, spec, eng = spec_lm
    p = [5, 7, 11]
    v0 = eng.metrics()["lm"]["speculative"]["verify_steps"]
    # per-request opt-out: exact greedy, no verify windows
    want = naive_generate(net, p, 6, pad_to=64, spec=spec)
    toks, _ = eng.generate(p, max_tokens=6, speculative=False)
    assert toks == want
    assert eng.metrics()["lm"]["speculative"]["verify_steps"] == v0
    # sampling opts out automatically (exactness is greedy-only)
    toks, reason = eng.generate(p, max_tokens=8, temperature=1.0, top_k=5)
    assert reason == "length" and len(toks) == 8
    assert all(0 <= t < 53 for t in toks)
    assert eng.metrics()["lm"]["speculative"]["verify_steps"] == v0


def test_self_draft_accepts_every_proposal():
    """Regression for the draft-cache gap bug: with draft == target every
    proposal must agree (the draft writes K/V for ALL fed positions,
    including p_k's, so no window ever reads an unwritten position)."""
    net = _lm(seed=31, vocab=41, d_model=16, n_blocks=1, max_length=64)
    eng = GenerationEngine(net, model_name="lm", block_len=8, max_seq_len=64,
                           decode_slots=2, prefill_batches=(1,),
                           prompt_rungs=(64,), draft=net, spec_k=3)
    try:
        spec = TransformerDecodeSpec(net)
        p = R.integers(1, 41, size=6).tolist()
        want = naive_generate(net, p, 13, pad_to=64, spec=spec)
        toks, _ = eng.generate(p, max_tokens=13)
        assert toks == want
        s = eng.metrics()["lm"]["speculative"]
        assert s["accepted"] == s["proposed"], \
            f"self-draft disagreed with itself: {s}"
    finally:
        eng.stop()


@pytest.mark.slow
def test_speculative_lstm_draft_bit_identical():
    """The state-adapter draft: an LSTM proposes, the stacked-state rewind
    rolls its recurrent state back to exactly what verify accepted —
    output stays plain-greedy-identical even at near-zero acceptance.
    Slow lane (ISSUE 19 tier-1 budget reclaim): the transformer-draft
    bit-identity + acceptance pins in this file keep the speculative
    greedy-identity contract tier-1."""
    net = _lm(seed=11, vocab=37, d_model=16, n_blocks=1, max_length=32)
    lstm = text_generation_lstm(vocab_size=37, hidden=12, max_length=32,
                                seed=5).init()
    eng = GenerationEngine(net, model_name="lm", block_len=8, max_seq_len=32,
                           decode_slots=2, prefill_batches=(1, 2),
                           prompt_rungs=(32,), draft=lstm, spec_k=3)
    try:
        assert eng.models()["lm"]["speculative"]["draft_adapter"] == "state"
        spec = TransformerDecodeSpec(net)
        prompts = [R.integers(1, 37, size=n).tolist() for n in (4, 8, 7)]
        refs = [naive_generate(net, p, 10, pad_to=32, spec=spec)
                for p in prompts]
        outs = {}

        def client(i):
            outs[i] = eng.generate(prompts[i % 3], max_tokens=10)[0]

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for i in range(6):
            assert outs[i] == refs[i % 3], f"client {i} diverged"
        assert eng.metrics()["lm"]["speculative"]["verify_steps"] > 0
    finally:
        eng.stop()


@pytest.mark.slow   # bf16 variant; tier-1 keeps the f32 pin
# (test_speculative_greedy_bit_identical_f32) and the core bf16 decode
# pin (test_generation.py::test_paged_greedy_bit_identical_dtypes_and_embeds)
def test_speculative_bf16_bit_identical():
    net = _lm(seed=13, vocab=37, d_model=16, n_blocks=2, max_length=32,
              dtype="bfloat16")
    draft = truncated_draft(net, 1)
    eng = GenerationEngine(net, model_name="lm", block_len=8, max_seq_len=32,
                           decode_slots=2, prefill_batches=(1,),
                           prompt_rungs=(32,), draft=draft, spec_k=3)
    try:
        spec = TransformerDecodeSpec(net)
        for n in (4, 8):
            p = R.integers(1, 37, size=n).tolist()
            want = naive_generate(net, p, 10, pad_to=32, spec=spec)
            assert eng.generate(p, max_tokens=10)[0] == want
    finally:
        eng.stop()


# ----------------------------------------------------------------- hot-swap
def test_hot_swap_spec_cohort_pinning():
    """In-flight speculative generations finish on the OLD params + OLD
    draft; post-swap admissions run the new params. Same-arch swap reuses
    every compiled executable (draft/verify included): zero new traces."""
    net_a = _lm(seed=7)
    net_b = _lm(seed=8)
    spec_a, spec_b = TransformerDecodeSpec(net_a), TransformerDecodeSpec(net_b)
    draft = truncated_draft(net_a, 1)
    prompt = R.integers(1, 53, size=6).tolist()
    want_a = naive_generate(net_a, prompt, 40, pad_to=64, spec=spec_a)
    want_b = naive_generate(net_b, prompt, 40, pad_to=64, spec=spec_b)
    assert want_a != want_b
    eng = GenerationEngine(net_a, model_name="lm", block_len=8,
                           max_seq_len=64, decode_slots=2,
                           prefill_batches=(1,), prompt_rungs=(64,),
                           draft=draft, spec_k=3)
    try:
        traces0 = eng.trace_count
        compiles0 = xla_compile_count()
        st_a = eng.generate(prompt, max_tokens=40, stream=True)
        deadline = time.monotonic() + 5.0
        while eng.metrics()["lm"]["prefills"] < 1:
            assert time.monotonic() < deadline
            time.sleep(0.002)
        assert eng.hot_swap("lm", net_b) == 2
        st_b = eng.generate(prompt, max_tokens=40, stream=True)
        assert st_a.result() == (want_a, "length"), \
            "in-flight speculative generation must finish on OLD params"
        assert st_b.result() == (want_b, "length")
        assert eng.trace_count == traces0
        assert xla_compile_count() == compiles0
    finally:
        eng.stop()


# ------------------------------------------------------- acceptance yield
def test_truncated_draft_yields_two_tokens_a_verify():
    """ISSUE 14 acceptance, which is a count and no timing: a draft that
    is the target's first block, against a target whose second block
    adds a quarter of its usual residual (the agreement a distilled draft
    has), lands >= 2 tokens per target dispatch, the correction token
    included; plain decode is 1.0 by definition."""
    net = _lm(seed=123, vocab=128, d_model=64)
    params = list(net.params)
    for i, name in enumerate(net.vertex_names):
        if name == "b1_attn":
            params[i] = dict(params[i], Wo=params[i]["Wo"] * 0.25,
                             b=params[i]["b"] * 0.25)
        elif name == "b1_ff2":
            params[i] = {k: v * 0.25 for k, v in params[i].items()}
    net.params = tuple(params)
    eng = GenerationEngine(net, model_name="lm", block_len=16,
                           max_seq_len=64, decode_slots=2,
                           prefill_batches=(1,), prompt_rungs=(64,),
                           draft=truncated_draft(net, 1), spec_k=4)
    try:
        spec = TransformerDecodeSpec(net)
        rng = np.random.default_rng(5)
        for _ in range(4):
            p = rng.integers(1, 128, size=8).tolist()
            assert eng.generate(p, max_tokens=24)[0] == \
                naive_generate(net, p, 24, pad_to=64, spec=spec)
        s = eng.metrics()["lm"]["speculative"]
        assert s["verify_steps"] > 0
        assert s["accepted_tokens_per_verify"] >= 2.0, s
        # fewer target dispatches than tokens: the point of the scheme
        assert s["verify_steps"] * 2 <= s["emitted"], s
    finally:
        eng.stop()


# ----------------------------------------------------------------- HTTP opt-in
def test_http_speculative_surface():
    """/generate honors "speculative": false; /models and /metrics expose
    the per-model opt-in state and the new economics sections."""
    import json
    import urllib.request
    from deeplearning4j_tpu.serving import ServingHTTPServer
    net = _lm(seed=67, vocab=29, d_model=16, n_blocks=1, max_length=32)
    eng = GenerationEngine(net, model_name="lm", block_len=8, max_seq_len=32,
                           decode_slots=2, prefill_batches=(1,),
                           prompt_rungs=(32,), draft=net, spec_k=2)
    srv = ServingHTTPServer(generation=eng)
    base = f"http://127.0.0.1:{srv.start()}"
    try:
        spec = TransformerDecodeSpec(net)
        p = [3, 5, 7]
        want = naive_generate(net, p, 6, pad_to=32, spec=spec)

        def post(body):
            req = urllib.request.Request(
                base + "/generate", json.dumps(body).encode(),
                {"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=30) as r:
                return json.loads(r.read())

        out = post({"prompt": p, "max_tokens": 6, "stream": False})
        assert out["tokens"] == want
        v1 = eng.metrics()["lm"]["speculative"]["verify_steps"]
        assert v1 > 0
        out = post({"prompt": p, "max_tokens": 6, "stream": False,
                    "speculative": False})
        assert out["tokens"] == want
        assert eng.metrics()["lm"]["speculative"]["verify_steps"] == v1
        with urllib.request.urlopen(base + "/models", timeout=10) as r:
            models = json.loads(r.read())["generation"]["lm"]
        assert models["speculative"] == {"enabled": True, "k": 2,
                                         "draft_adapter": "dense"}
        assert models["prefix_cache"] is True
        with urllib.request.urlopen(base + "/metrics", timeout=10) as r:
            metrics = json.loads(r.read())["generation"]["lm"]
        assert "prefix" in metrics and "speculative" in metrics
        assert "accepted_tokens_per_verify" in metrics["speculative"]
    finally:
        srv.stop()
