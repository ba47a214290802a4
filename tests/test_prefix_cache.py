"""Copy-on-write prefix-cache sharing (ISSUE 14 tentpole, cache leg).

Pins:
  - bit-exactness: greedy decode through a CACHED prefix (block-aligned
    full match -> COW + one-step replay; partial match -> suffix replay)
    matches cache-free naive decode token-for-token, f32 AND bf16,
    including divergence on the first token after a shared prefix and COW
    under concurrent continuous-batched admission;
  - allocator hardening: freeing an unallocated block, double-freeing, or
    freeing a block with a live refcount raises; the scheduler's quiesce
    invariant (allocated == cached) catches leaks;
  - LRU eviction under pool pressure runs BEFORE BlockPoolExhaustedError;
  - cohort pinning: a hot-swap never serves old-params cached K/V to
    new-params admissions;
  - tracing: a cached-prefix request's timeline shows generation.prefix_hit
    and NO prefill span (the satellite's trace2timeline fixture).
"""
import threading
import time

import numpy as np
import pytest

from deeplearning4j_tpu.models.decode import (TransformerDecodeSpec,
                                              naive_generate)
from deeplearning4j_tpu.models.zoo_extra import transformer_lm
from deeplearning4j_tpu.serving import GenerationEngine
from deeplearning4j_tpu.serving.generation import BlockAllocator, PrefixCache
from deeplearning4j_tpu.serving.generation.prefix import _block_hashes

R = np.random.default_rng(1234)


def _lm(seed=7, vocab=53, d_model=32, n_heads=2, n_blocks=2, max_length=64,
        dtype="float32"):
    return transformer_lm(vocab_size=vocab, d_model=d_model,
                          n_heads=n_heads, n_blocks=n_blocks,
                          max_length=max_length, seed=seed, dtype=dtype,
                          token_input=True).init()


# ------------------------------------------------------- allocator hardening
def test_block_allocator_refcounts_and_hardening():
    a = BlockAllocator(6)                      # ids 1..5 usable
    got = a.alloc(3)
    assert a.allocated == frozenset(got)
    with pytest.raises(ValueError):
        a.free([got[0], got[0]])               # double free in one call
    # refcounted blocks refuse free until released
    a.incref(got[1])
    a.incref(got[1])
    with pytest.raises(ValueError):
        a.free([got[1]])
    assert a.decref(got[1]) == 1
    with pytest.raises(ValueError):
        a.free([got[1]])                       # still one ref
    assert a.decref(got[1]) == 0
    a.free([got[1]])
    # freeing an id this allocator never handed out
    free_id = next(b for b in range(1, 6) if b not in a.allocated)
    with pytest.raises(ValueError):
        a.free([free_id])
    with pytest.raises(ValueError):
        a.incref(free_id)                      # incref needs allocation
    with pytest.raises(ValueError):
        a.decref(got[2])                       # decref below zero
    with pytest.raises(ValueError):
        a.free([0])                            # trash block


def test_block_hash_chain_properties():
    p = np.arange(20, dtype=np.int32)
    h8 = _block_hashes(p, 8)
    assert len(h8) == 2                        # only FULL blocks hash
    assert _block_hashes(p[:7], 8) == []
    # chain property: same first block -> same h0; any earlier token
    # change reaches every later hash
    q = p.copy()
    q[3] = 99
    hq = _block_hashes(q, 8)
    assert hq[0] != h8[0] and hq[1] != h8[1]
    r = p.copy()
    r[12] = 99
    hr = _block_hashes(r, 8)
    assert hr[0] == h8[0] and hr[1] != h8[1]


def test_prefix_cache_unit_match_register_release_evict():
    a = BlockAllocator(12)
    pc = PrefixCache(a, 4)
    prompt = np.arange(12, dtype=np.int32)     # 3 full blocks
    blocks = a.alloc(4)                        # 3 prompt + 1 decode block
    managed = pc.register(prompt, np.array(blocks, np.int32), blocks)
    assert managed == blocks[:3]               # full blocks only
    assert all(a.refcount(b) == 1 for b in managed)
    assert pc.shared_blocks == 3 and pc.lru_blocks == 0
    # owner releases -> blocks park in LRU, still allocated
    pc.release(managed)
    assert pc.lru_blocks == 3
    assert a.refcount(managed[0]) == 0
    assert set(managed) <= set(a.allocated)
    # a shorter prompt with the same prefix matches 1 block and revives it
    shared, matched = pc.match(np.arange(6, dtype=np.int32))
    assert (shared, matched) == ([managed[0]], 4)
    assert pc.lru_blocks == 2 and a.refcount(managed[0]) == 1
    # evictable_for excludes blocks THIS prompt would revive
    assert pc.evictable_for(prompt) == 0       # both LRU blocks match
    assert pc.evictable_for(np.full(12, 7, np.int32)) == 2
    pc.release(shared)
    # eviction is oldest-first, children follow their parent: evicting the
    # chain head frees ALL three (descendants can't outlive the parent)
    freed0 = a.free_blocks
    n = pc.ensure_free(freed0 + 3)
    assert n == 3 and pc.cached_blocks == 0
    assert a.free_blocks == freed0 + 3
    assert pc.evictions == 3
    # the same prompt now misses
    assert pc.probe(prompt) == 0


# ------------------------------------------- shared engine + exactness pins
@pytest.fixture(scope="module")
def cache_lm():
    """One warmed f32 engine (block 8, slots 4, prefix cache ON by
    default) shared by the read-only pins below."""
    net = _lm()
    eng = GenerationEngine(net, model_name="lm", block_len=8, max_seq_len=64,
                           decode_slots=4, prefill_batches=(1, 2),
                           prompt_rungs=(64,))
    yield net, TransformerDecodeSpec(net), eng
    eng.stop()


def test_cached_prefix_bit_identical_f32(cache_lm):
    """THE pin: repeated prompts hit the cache (block-aligned -> COW +
    single-step replay; partial -> suffix replay) and stay token-for-token
    identical to cache-free naive decode."""
    net, spec, eng = cache_lm
    p16 = R.integers(1, 53, size=16).tolist()      # aligned: COW on repeat
    p13 = R.integers(1, 53, size=13).tolist()      # partial match on repeat
    want16 = naive_generate(net, p16, 10, pad_to=64, spec=spec)
    want13 = naive_generate(net, p13, 10, pad_to=64, spec=spec)
    m0 = eng.metrics()["lm"]["prefix"]
    for _ in range(3):
        assert eng.generate(p16, max_tokens=10)[0] == want16
        assert eng.generate(p13, max_tokens=10)[0] == want13
    m1 = eng.metrics()["lm"]["prefix"]
    assert m1["hits"] - m0["hits"] >= 4            # repeats all hit
    assert m1["cow_copies"] - m0["cow_copies"] >= 2
    assert m1["tokens_saved"] > m0["tokens_saved"]
    # cached TTFT is recorded for hit admissions
    assert m1["ttft_cached_ms"]["p50"] > 0


def test_divergent_continuation_after_shared_prefix(cache_lm):
    """Acceptance pin: two prompts sharing a block-aligned prefix but
    diverging right after it produce EXACTLY their own naive decodes —
    the shared blocks feed both, the divergent suffix replays privately."""
    net, spec, eng = cache_lm
    common = R.integers(1, 53, size=16).tolist()
    a = common + R.integers(1, 53, size=3).tolist()
    b = common + R.integers(1, 53, size=5).tolist()
    assert a[16:] != b[16:19]
    want_a = naive_generate(net, a, 8, pad_to=64, spec=spec)
    want_b = naive_generate(net, b, 8, pad_to=64, spec=spec)
    eng.generate(common, max_tokens=4)              # seed the cache
    got_a, _ = eng.generate(a, max_tokens=8)
    got_b, _ = eng.generate(b, max_tokens=8)
    assert got_a == want_a
    assert got_b == want_b


def test_cow_under_concurrent_admission(cache_lm):
    """Acceptance pin: block-aligned full-match admissions (each COWs the
    final shared block) landing WHILE other slots decode perturb nothing."""
    net, spec, eng = cache_lm
    p16 = R.integers(1, 53, size=16).tolist()
    p9 = R.integers(1, 53, size=9).tolist()
    want16 = naive_generate(net, p16, 8, pad_to=64, spec=spec)
    want9 = naive_generate(net, p9, 8, pad_to=64, spec=spec)
    eng.generate(p16, max_tokens=2)                 # cache both prefixes
    eng.generate(p9, max_tokens=2)
    cow0 = eng.metrics()["lm"]["prefix"]["cow_copies"]
    outs = {}

    def client(i):
        p, want = (p16, want16) if i % 2 == 0 else (p9, want9)
        st = eng.generate(p, max_tokens=8, stream=True)
        outs[i] = (list(st), want)

    threads = [threading.Thread(target=client, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for i in range(8):
        got, want = outs[i]
        assert got == want, f"client {i} diverged under concurrent COW"
    assert eng.metrics()["lm"]["prefix"]["cow_copies"] - cow0 >= 4


def test_short_match_on_long_prompt_admits_as_miss(cache_lm):
    """Replay-budget guard: a cached match whose unmatched suffix exceeds
    ``prefix_max_replay`` (default 2 blocks) admits as a plain MISS —
    teacher-forcing a long suffix one token per decode dispatch would
    cost far more than the batched prefill it 'saves'. Output stays
    exact either way; the pin is that it took the prefill path."""
    net, spec, eng = cache_lm
    seed_p = R.integers(1, 53, size=8).tolist()        # caches one block
    eng.generate(seed_p, max_tokens=2)
    long_p = seed_p + R.integers(1, 53, size=32).tolist()   # suffix 32 > 16
    m0 = eng.metrics()["lm"]["prefix"]
    want = naive_generate(net, long_p, 6, pad_to=64, spec=spec)
    assert eng.generate(long_p, max_tokens=6)[0] == want
    m1 = eng.metrics()["lm"]["prefix"]
    assert m1["hits"] == m0["hits"], \
        "a 1-block match on a 40-token prompt must not replay 32 tokens"
    assert m1["misses"] == m0["misses"] + 1
    # within-budget suffix still hits: 8 shared + 8 new tokens (suffix 8)
    mid_p = seed_p + R.integers(1, 53, size=8).tolist()
    want = naive_generate(net, mid_p, 6, pad_to=64, spec=spec)
    assert eng.generate(mid_p, max_tokens=6)[0] == want
    assert eng.metrics()["lm"]["prefix"]["hits"] == m1["hits"] + 1


def test_quiesce_invariant_catches_leak(cache_lm):
    """The scheduler's quiesce assertion: allocated == cached when no
    requests are live; a leaked block (allocated outside any table or the
    cache) raises. Regression for silent pool leaks."""
    _, _, eng = cache_lm
    rt = eng._get("lm")
    # self-sufficient under any test order (reversed-order harness runs
    # this before the traffic-generating pins): ensure a cohort exists
    eng.generate([2, 4, 6], max_tokens=2)
    deadline = time.monotonic() + 10.0
    while rt.in_flight or rt.queue_depth:
        assert time.monotonic() < deadline
        time.sleep(0.005)
    rt._check_quiesce()                             # clean after traffic
    coh = rt._cohorts[-1]
    leak = coh.allocator.alloc(1)
    with pytest.raises(RuntimeError, match="leaked"):
        rt._check_quiesce()
    coh.allocator.free(leak)
    rt._check_quiesce()


@pytest.mark.slow   # bf16 variant; tier-1 keeps the f32 pin
# (test_cached_prefix_bit_identical_f32) and the core bf16 decode pin
# (test_generation.py::test_paged_greedy_bit_identical_dtypes_and_embeds)
def test_cached_prefix_bit_identical_bf16():
    """Same exactness pin in bf16 (COW + partial-match replay)."""
    net = _lm(seed=11, vocab=37, d_model=16, n_blocks=1, max_length=32,
              dtype="bfloat16")
    spec = TransformerDecodeSpec(net)
    eng = GenerationEngine(net, model_name="lm", block_len=8, max_seq_len=32,
                           decode_slots=2, prefill_batches=(1,),
                           prompt_rungs=(32,))
    try:
        p8 = R.integers(1, 37, size=8).tolist()
        p11 = R.integers(1, 37, size=11).tolist()
        want8 = naive_generate(net, p8, 8, pad_to=32, spec=spec)
        want11 = naive_generate(net, p11, 8, pad_to=32, spec=spec)
        for _ in range(2):
            assert eng.generate(p8, max_tokens=8)[0] == want8
            assert eng.generate(p11, max_tokens=8)[0] == want11
        snap = eng.metrics()["lm"]["prefix"]
        assert snap["hits"] >= 2 and snap["cow_copies"] >= 1
    finally:
        eng.stop()


def test_eviction_under_pool_pressure_before_exhaustion():
    """A pool too small for live blocks + cached LRU evicts refcount-0
    cached blocks instead of raising BlockPoolExhaustedError; the evicted
    prefix then misses again."""
    net = _lm(seed=41, vocab=29, d_model=16, n_blocks=1, max_length=32)
    spec = TransformerDecodeSpec(net)
    # 5 usable blocks; each 8-token prompt + 8 new = 2 blocks (+1 COW on
    # a repeat). Two distinct cached prompts fill 2 LRU blocks.
    eng = GenerationEngine(net, model_name="lm", block_len=8, max_seq_len=32,
                           decode_slots=1, prefill_batches=(1,),
                           prompt_rungs=(32,), num_blocks=6)
    try:
        pa = R.integers(1, 29, size=8).tolist()
        pb = R.integers(1, 29, size=8).tolist()
        pc_ = R.integers(1, 29, size=8).tolist()
        for p in (pa, pb):
            want = naive_generate(net, p, 8, pad_to=32, spec=spec)
            assert eng.generate(p, max_tokens=8)[0] == want
        m = eng.metrics()["lm"]["prefix"]
        assert m["cached_lru_blocks"] >= 2
        # a third distinct prompt needs 4 blocks (8+24 -> 4) with only 3
        # free: the LRU must yield a block instead of a 429
        want = naive_generate(net, pc_, 24, pad_to=32, spec=spec)
        assert eng.generate(pc_, max_tokens=24)[0] == want
        m = eng.metrics()["lm"]["prefix"]
        assert m["evictions"] >= 1
        rt = eng._get("lm")
        deadline = time.monotonic() + 10.0
        while rt.in_flight or rt.queue_depth:
            assert time.monotonic() < deadline
            time.sleep(0.005)
        rt._check_quiesce()
    finally:
        eng.stop()


def test_prefix_cache_opt_out():
    net = _lm(seed=53, vocab=29, d_model=16, n_blocks=1, max_length=32)
    eng = GenerationEngine(net, model_name="lm", block_len=8, max_seq_len=32,
                           decode_slots=2, prefill_batches=(1,),
                           prompt_rungs=(32,), prefix_cache=False)
    try:
        assert eng.models()["lm"]["prefix_cache"] is False
        p = R.integers(1, 29, size=8).tolist()
        a, _ = eng.generate(p, max_tokens=4)
        b, _ = eng.generate(p, max_tokens=4)
        assert a == b
        snap = eng.metrics()["lm"]["prefix"]
        assert snap["hits"] == 0 and snap["misses"] == 0
    finally:
        eng.stop()


def test_hot_swap_does_not_share_prefix_across_cohorts():
    """Cohort pinning: cached blocks hold OLD-params K/V; after hot_swap
    the same prompt must MISS in the new cohort and produce new-params
    tokens (a cross-cohort hit would emit a params mixture)."""
    net_a = _lm(seed=7)
    net_b = _lm(seed=8)
    spec_a, spec_b = TransformerDecodeSpec(net_a), TransformerDecodeSpec(net_b)
    p = R.integers(1, 53, size=16).tolist()
    want_a = naive_generate(net_a, p, 8, pad_to=64, spec=spec_a)
    want_b = naive_generate(net_b, p, 8, pad_to=64, spec=spec_b)
    assert want_a != want_b
    eng = GenerationEngine(net_a, model_name="lm", block_len=8,
                           max_seq_len=64, decode_slots=2,
                           prefill_batches=(1,), prompt_rungs=(64,))
    try:
        assert eng.generate(p, max_tokens=8)[0] == want_a    # cached (old)
        assert eng.generate(p, max_tokens=8)[0] == want_a    # hit (old)
        hits_before = eng.metrics()["lm"]["prefix"]["hits"]
        assert hits_before >= 1
        eng.hot_swap("lm", net_b)
        assert eng.generate(p, max_tokens=8)[0] == want_b, \
            "post-swap admission must not reuse old-cohort cached K/V"
    finally:
        eng.stop()


# ------------------------------------------------------------------ tracing
def test_prefix_hit_trace_timeline(tmp_path):
    """Satellite pin: a cached-prefix request's trace shows
    generation.prefix_hit stamped with the trace id and NO prefill span —
    trace2timeline reconstructs the request visibly skipping prefill."""
    import json
    import os
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from tools.trace2summary import load_events
    from tools.trace2timeline import timeline
    from deeplearning4j_tpu.telemetry import get_registry
    from deeplearning4j_tpu.telemetry.tracecontext import (new_trace_context,
                                                           use_trace_context)
    net = _lm(seed=67, vocab=29, d_model=16, n_blocks=1, max_length=32)
    eng = GenerationEngine(net, model_name="lm", block_len=8, max_seq_len=32,
                           decode_slots=2, prefill_batches=(1,),
                           prompt_rungs=(32,))
    try:
        p = R.integers(1, 29, size=16).tolist()
        eng.generate(p, max_tokens=4)                   # seed (miss)
        ctx = new_trace_context()
        with use_trace_context(ctx):
            toks, _ = eng.generate(p, max_tokens=4)     # hit
        assert len(toks) == 4
        path = get_registry().write_trace_jsonl(
            str(tmp_path / "t.jsonl"), trace_id=ctx.trace_id)
        names = [json.loads(ln)["name"] for ln in open(path)]
        assert "generation.prefix_hit" in names
        assert "generation.prefill" not in names, \
            "a cached-prefix request must SKIP prefill"
        assert names.count("generation.decode_step") >= 4
        rows = timeline(load_events(path), ctx.trace_id)
        order = [r["name"] for r in rows]
        assert order.index("generation.submit") \
            < order.index("generation.prefix_hit") \
            < order.index("generation.decode_step") \
            < order.index("generation.finish")
        hit = next(r for r in rows if r["name"] == "generation.prefix_hit")
        assert "matched_tokens=16" in hit["detail"]
        assert "cow=1" in hit["detail"]
    finally:
        eng.stop()
