"""serving/generation: paged KV-cache decode with continuous batching.

Pins (ISSUE 9):
  - bit-exactness: greedy decode through the paged-cache path matches
    naive full-recompute decode token-for-token (f32 AND bf16, token-id
    and one-hot embed inputs) — same pinning pattern as
    tests/test_overlap_sync.py;
  - zero recompiles: after warm-up, a mixed stream of prompt lengths and
    generation lengths triggers ZERO backend compiles (asserted via the
    telemetry RecompileDetector, as test_zero_recompiles_after_warmup
    does for forward serving);
  - continuous batching: requests admitted into an in-flight decode batch
    at step boundaries produce the same tokens as isolated decodes;
  - admission-control/deadline/drain semantics carried over from
    serving/engine.py, plus the block-pool exhaustion taxonomy;
  - hot-swap cutover rule: in-flight generations finish on old params,
    new admissions run the new model.

Heavy soak variants are marked ``slow``; tier-1 keeps the same assertions
at a handful-of-requests scale.
"""
import re
import threading
import time

import numpy as np
import pytest

from deeplearning4j_tpu.models.decode import (LSTMDecodeSpec,
                                              TransformerDecodeSpec,
                                              naive_generate,
                                              naive_generate_lstm,
                                              truncated_draft)
from deeplearning4j_tpu.models.zoo_extra import (text_generation_lstm,
                                                 transformer_lm)
from deeplearning4j_tpu.serving import (BlockPoolExhaustedError,
                                        DrainingError, GenerationConfig,
                                        GenerationEngine, QueueFullError,
                                        ShapeMismatchError,
                                        xla_compile_count)
from deeplearning4j_tpu.serving.generation import BlockAllocator
from deeplearning4j_tpu.serving.generation.programs import (
    ADMIT_LOOKAHEAD, pack_decode, pack_prefill, padding_prefill, unpack_decode,
    unpack_prefill)
from deeplearning4j_tpu.telemetry import RecompileDetector, get_registry

R = np.random.default_rng(99)


def _lm(seed=7, vocab=53, d_model=32, n_heads=2, n_blocks=2, max_length=64,
        dtype="float32", token_input=True):
    return transformer_lm(vocab_size=vocab, d_model=d_model,
                          n_heads=n_heads, n_blocks=n_blocks,
                          max_length=max_length, seed=seed, dtype=dtype,
                          token_input=token_input).init()


def _prompts(vocab, sizes, seed=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, size=n).tolist() for n in sizes]


# ------------------------------------------------------- pool + config units
def test_block_allocator():
    a = BlockAllocator(5)              # ids 1..4 usable, 0 is trash
    assert a.total_usable == 4 and a.free_blocks == 4
    got = a.alloc(3)
    assert len(got) == 3 and 0 not in got
    assert a.free_blocks == 1 and a.used_blocks == 3
    with pytest.raises(BlockPoolExhaustedError):
        a.alloc(2)
    a.free(got[:2])
    assert a.free_blocks == 3
    with pytest.raises(ValueError):
        a.free([got[0]])               # double free
    with pytest.raises(ValueError):
        a.free([0])                    # trash block is not freeable
    with pytest.raises(ValueError):
        BlockAllocator(1)


def test_generation_config_plan():
    cfg = GenerationConfig(block_len=16, max_seq_len=100, decode_slots=4,
                           prompt_rungs=(20, 50), prefill_batches=(4, 1, 1))
    assert cfg.capacity == 112                 # rounded up to block_len
    assert cfg.blocks_per_seq == 7
    # rungs round up to block multiples and always include the capacity
    assert cfg.prompt_rungs == (32, 64, 112)
    assert cfg.prefill_batches == (1, 4)
    assert cfg.blocks_needed(10, 6) == 1
    assert cfg.blocks_needed(10, 7) == 2
    assert cfg.prompt_rung(33) == 64
    assert cfg.prefill_rung(3) == 4
    assert cfg.num_blocks == 4 * 7 + 1
    with pytest.raises(ValueError):
        cfg.prompt_rung(113)


# ------------------------------------------- shared read-only engine + pins
@pytest.fixture(scope="module")
def shared_lm():
    """One warmed f32 engine shared by the read-only tests below (every
    AOT warm-up is seconds of tier-1 budget). Tests using it must leave it
    healthy: no stop(), no monkeypatching, no pool reconfiguration."""
    net = _lm(dtype="float32")
    eng = GenerationEngine(net, model_name="lm", block_len=8, max_seq_len=64,
                           decode_slots=4, prefill_batches=(1, 2),
                           prompt_rungs=(64,))
    yield net, TransformerDecodeSpec(net), eng
    eng.stop()


def test_generation_programs_registered_in_cost_index(shared_lm):
    """ISSUE 15: warm-up registers every generation executable's XLA cost
    analysis in the process cost index (decode step paired with the
    decode_step_ms histogram the scheduler observes; prefill rungs
    cost-only) — read-only against the shared engine."""
    from deeplearning4j_tpu.telemetry.perf import get_cost_index
    idx = get_cost_index()
    e = idx.get("generation.lm.decode_step")
    assert e is not None and e.source == "compiled"
    assert e.flops_per_step and e.flops_per_step > 0
    assert e.timing_metric == "generation.lm.decode_step_ms"
    assert any(p.startswith("generation.lm.prefill.")
               for p in idx.paths())


def test_paged_greedy_bit_identical_to_naive_f32(shared_lm):
    """THE pin: greedy decode through the paged KV cache — sequential AND
    continuous-batched concurrent — matches cache-free full-recompute
    decode token-for-token."""
    net, spec, eng = shared_lm
    prompts = _prompts(53, (5, 9, 13))
    refs = [naive_generate(net, p, 10, pad_to=64, spec=spec)
            for p in prompts]
    req0 = eng.metrics()["lm"]["requests"]
    for p, want in zip(prompts, refs):
        toks, reason = eng.generate(p, max_tokens=10)
        assert reason == "length"
        assert toks == want
    # continuous batching: 6 concurrent clients share 4 decode slots —
    # step-boundary admission + slot backfill must not perturb numerics
    outs = {}

    def client(i):
        st = eng.generate(prompts[i % 3], max_tokens=10, stream=True)
        outs[i] = (list(st), st.finish_reason)

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for i in range(6):
        assert outs[i][0] == refs[i % 3], f"client {i} diverged"
        assert outs[i][1] == "length"
    snap = eng.metrics()["lm"]
    assert snap["requests"] == req0 + 9
    assert snap["finished"].get("length", 0) >= 9


@pytest.mark.parametrize("dtype,token_input", [("bfloat16", True),
                                               ("float32", False)])
def test_paged_greedy_bit_identical_dtypes_and_embeds(dtype, token_input):
    """Same pin in bf16 and through the legacy one-hot embed input."""
    net = _lm(seed=11, vocab=37, d_model=16, n_blocks=1, max_length=32,
              dtype=dtype, token_input=token_input)
    spec = TransformerDecodeSpec(net)
    prompts = _prompts(37, (4, 7), seed=5)
    refs = [naive_generate(net, p, 8, pad_to=32, spec=spec)
            for p in prompts]
    eng = GenerationEngine(net, model_name="lm", block_len=8, max_seq_len=32,
                           decode_slots=2, prefill_batches=(1,),
                           prompt_rungs=(32,))
    try:
        for p, want in zip(prompts, refs):
            toks, _ = eng.generate(p, max_tokens=8)
            assert toks == want
    finally:
        eng.stop()


def test_lstm_generation_matches_rnn_time_step():
    """The recurrent leg: engine decode (fixed-shape state cache) matches
    the public rnn_time_step greedy loop token-for-token."""
    net = text_generation_lstm(vocab_size=31, hidden=24, max_length=32,
                               seed=5).init()
    assert LSTMDecodeSpec(net).vocab == 31
    prompts = _prompts(31, (3, 7), seed=11)
    refs = [naive_generate_lstm(net, p, 8) for p in prompts]
    eng = GenerationEngine(net, model_name="charlm", block_len=8,
                           max_seq_len=32, decode_slots=2,
                           prefill_batches=(1, 2), prompt_rungs=(16,))
    try:
        assert eng.models()["charlm"]["adapter"] == "state"
        outs = {}

        def client(i):
            outs[i] = eng.generate(prompts[i % 2], max_tokens=8)[0]

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for i in range(4):
            assert outs[i] == refs[i % 2]
    finally:
        eng.stop()


# -------------------------------------------------------- zero recompiles
# engine modes: the net each serves is d=16, one block, float32 unless it
# says otherwise; the engine has four slots and prefill batches 1 and 2
def _steady_lm(**kw):
    return _lm(**{**dict(seed=21, vocab=41, d_model=16, n_blocks=1,
                         max_length=64), **kw})


def _steady_char_lstm():
    return text_generation_lstm(vocab_size=41, hidden=24, max_length=64,
                                seed=5).init()


def _no_prefix_hits(snap, row):
    return snap["prefix"]["hits"] == 0


def _two_way_model_mesh():
    import jax
    from deeplearning4j_tpu.parallel.mesh import make_mesh
    return make_mesh((1, 2), ("data", "model"), jax.devices()[:2])


# mode: (net, net -> what it changes of the engine, what must show)
_STEADY_MODES = {
    "continuous": (
        _steady_lm, lambda net: dict(prefix_cache=False), _no_prefix_hits),
    "one_at_a_time": (
        _steady_lm,
        lambda net: dict(decode_slots=1, prefill_batches=(1,),
                         prefix_cache=False),
        _no_prefix_hits),
    "prefix": (
        _steady_lm, lambda net: dict(prefix_cache=True),
        lambda snap, row: (snap["prefix"]["hits"] >= 2
                           and snap["prefix"]["cow_copies"] >= 1)),
    "speculative": (
        lambda: _steady_lm(n_blocks=2),
        lambda net: dict(spec_k=3, draft=truncated_draft(net, 1)),
        lambda snap, row: (snap["speculative"]["verify_steps"] > 0
                           and snap["prefix"]["hits"] >= 2)),
    "int8_pool": (
        _steady_lm, lambda net: dict(kv_cache_dtype="int8"),
        lambda snap, row: row["kv_cache_dtype"] == "int8"),
    "bf16_pool": (
        lambda: _steady_lm(dtype="bfloat16"), lambda net: {},
        lambda snap, row: row["kv_cache_dtype"] is None),
    "lstm_state": (
        _steady_char_lstm, lambda net: {},
        lambda snap, row: row["adapter"] == "state"),
    "model_sharded": (
        _steady_lm, lambda net: dict(mesh=_two_way_model_mesh()),
        lambda snap, row: row["model_shards"] == 2),
}


@pytest.mark.parametrize("mode", sorted(_STEADY_MODES))
def test_zero_recompiles_generation_after_warmup(mode):
    """Tier-1 guard (ISSUE acceptance): after warm-up, a mixed stream of
    prompt lengths (two rungs), generation lengths, sampling settings,
    repeated prompts and concurrent admissions triggers ZERO backend
    compiles in every mode the engine runs in — asserted via the
    telemetry RecompileDetector AND the process-wide compile counter AND
    the engine's own trace hook."""
    build_net, engine_changes, mode_shows = _STEADY_MODES[mode]
    net = build_net()
    eng = GenerationEngine(net, model_name="lm", block_len=8, max_seq_len=64,
                           prompt_rungs=(16, 64), seed=3,
                           **{**dict(decode_slots=4, prefill_batches=(1, 2)),
                              **engine_changes(net)})
    try:
        traces0 = eng.trace_count
        compiles0 = xla_compile_count()
        tokens0 = get_registry().counter("generation.lm.tokens_out").value
        # (prompt length, max_tokens, temperature, top_k); the 8s and 16s
        # repeat one block-aligned prompt each
        work = [(8, 6, 0.0, 0), (8, 6, 0.0, 0), (16, 5, 0.0, 0),
                (16, 5, 0.0, 0), (3, 8, 0.7, 5), (30, 4, 0.0, 2),
                (8, 6, 0.0, 0), (13, 9, 1.2, 0), (40, 3, 0.0, 0),
                (2, 17, 0.3, 3)]
        results = {}

        def client(i):
            plen, mx, temp, topk = work[i]
            p = [(j * 7 + 1) % 40 + 1 for j in range(plen)]
            st = eng.generate(p, max_tokens=mx, temperature=temp,
                              top_k=topk, stream=True)
            results[i] = (list(st), st.finish_reason)

        with RecompileDetector(allowed=0) as det:
            client(0)                  # the repeats below find it cached
            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(1, len(work))]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        for i, (plen, mx, _, _) in enumerate(work):
            assert len(results[i][0]) == mx
            assert results[i][1] == "length"
            assert all(0 <= t < 41 for t in results[i][0])
        assert det.count == 0, \
            f"steady-state decode compiled: {det.events}"
        assert xla_compile_count() == compiles0
        assert eng.trace_count == traces0, "generation re-traced a program"
        assert mode_shows(eng.metrics()["lm"], eng.models()["lm"])
        # telemetry mirror: the decode loop published its gauges/counters
        reg = get_registry()
        assert reg.counter("generation.lm.tokens_out").value - tokens0 == \
            sum(w[1] for w in work)
        assert "generation.lm.slot_occupancy" in reg.snapshot()["gauges"]
    finally:
        eng.stop()


def test_continuous_and_one_at_a_time_emit_same_greedy_tokens(shared_lm):
    """Submission mode is not a numerics choice: six concurrent clients
    over four slots and one caller at a time on a one-slot engine emit
    the same greedy tokens for the same prompts."""
    net, _, eng = shared_lm
    prompts = _prompts(53, (4, 11, 17, 6, 23, 9), seed=41)
    outs = {}

    def client(i):
        outs[i] = eng.generate(prompts[i], max_tokens=9)

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    serial = GenerationEngine(net, model_name="lm", block_len=8,
                              max_seq_len=64, decode_slots=1,
                              prefill_batches=(1,), prompt_rungs=(64,))
    try:
        for i, p in enumerate(prompts):
            assert serial.generate(p, max_tokens=9) == outs[i], \
                f"prompt {i} diverged between submission modes"
    finally:
        serial.stop()


def test_greedy_tokens_do_not_depend_on_a_sampling_neighbour(shared_lm):
    """The sampler draws only when a row of its batch has a temperature,
    and then for that row: a greedy request emits the same tokens alone,
    beside greedy neighbours and beside a neighbour that samples (whose
    batch takes the sampler's other branch at prefill and every step)."""
    net, spec, eng = shared_lm
    rt = eng._get("lm")
    prompts = _prompts(53, (7, 12, 5), seed=39)
    want = [naive_generate(net, p, 9, pad_to=64, spec=spec) for p in prompts]
    for temps in ((0.0, 0.0, 0.0), (0.0, 1.0, 0.0)):
        with rt._cond:                    # one admission pass, one batch
            streams = [eng.generate(p, max_tokens=9, stream=True,
                                    temperature=t)
                       for p, t in zip(prompts, temps)]
        outs = [s.result()[0] for s in streams]
        for i, t in enumerate(temps):
            if t == 0.0:
                assert outs[i] == want[i], (temps, i)
            else:
                assert len(outs[i]) == 9
                assert all(0 <= tok < 53 for tok in outs[i])


# ------------------------------------------------------------- sampling
def test_sampling_modes_and_stop_tokens(shared_lm):
    net, spec, eng = shared_lm
    prompt = [3, 9, 4]
    greedy = naive_generate(net, prompt, 6, pad_to=64, spec=spec)
    # top_k=1 collapses sampling to greedy at ANY temperature
    toks, _ = eng.generate(prompt, max_tokens=6, temperature=5.0,
                           top_k=1)
    assert toks == greedy
    # temperature sampling emits valid ids and the full budget
    toks, reason = eng.generate(prompt, max_tokens=12, temperature=1.0,
                                top_k=4)
    assert reason == "length" and len(toks) == 12
    assert all(0 <= t < 53 for t in toks)
    # stop tokens terminate with reason "stop" and are NOT emitted
    stop = greedy[3]
    toks, reason = eng.generate(prompt, max_tokens=6, stop=[stop])
    assert reason == "stop"
    assert toks == greedy[:greedy.index(stop)]
    assert eng.metrics()["lm"]["finished"].get("stop", 0) >= 1


# ---------------------------------------------- admission control + errors
def test_block_pool_exhaustion_and_queue_taxonomy():
    """Tiny pool: one request's blocks occupy it entirely. The queue
    head-of-line waits for blocks; an over-limit submit while the pool is
    dry raises BlockPoolExhaustedError (429 + retry hint), and a request
    that can NEVER fit fails immediately."""
    net = _lm(seed=41, vocab=29, d_model=16, n_blocks=1, max_length=32)
    eng = GenerationEngine(net, model_name="lm", block_len=8, max_seq_len=32,
                           decode_slots=2, prefill_batches=(1,),
                           prompt_rungs=(32,), num_blocks=3, queue_limit=1)
    try:
        # within capacity but needs more blocks than the pool HAS: a retry
        # can never help -> immediate 429-with-hint
        with pytest.raises(BlockPoolExhaustedError) as ei:
            eng.generate([1, 2], max_tokens=28)     # 4 blocks, pool has 2
        assert "retry" in str(ei.value)
        # slow decode down so r1 deterministically holds its blocks for
        # the whole submit sequence below (un-slowed it finishes in ms)
        rt = eng._get("lm")
        orig_decode = rt.active_ps.launch_decode    # the loop's launch

        def slow_decode(*a, **k):
            time.sleep(0.01)
            return orig_decode(*a, **k)

        rt.active_ps.launch_decode = slow_decode
        # r1 takes both usable blocks (plen 2 + 14 new = 16 = 2 blocks)
        s1 = eng.generate([1, 2], max_tokens=14, stream=True)
        # wait until r1 is admitted (blocks held) before probing the queue
        deadline = time.monotonic() + 5.0
        while eng.metrics()["lm"]["prefills"] < 1:
            assert time.monotonic() < deadline
            time.sleep(0.002)
        s2 = eng.generate([3, 4], max_tokens=14, stream=True)   # queued
        with pytest.raises(QueueFullError):          # queue_limit=1, dry pool
            eng.generate([5, 6], max_tokens=14)
        assert eng.metrics()["lm"]["rejected"]["exhausted"] >= 1
        # head-of-line admission once r1's blocks free: both complete
        t1, r1 = s1.result()
        t2, r2 = s2.result()
        assert (len(t1), r1) == (14, "length")
        assert (len(t2), r2) == (14, "length")
    finally:
        eng.stop()


def test_shape_validation(shared_lm):
    _, _, eng = shared_lm                    # capacity 64, prompt rung 64
    with pytest.raises(ShapeMismatchError):
        eng.generate([], max_tokens=4)                  # empty prompt
    with pytest.raises(ShapeMismatchError):
        eng.generate([1] * 65, max_tokens=4)    # > largest prompt rung
    with pytest.raises(ShapeMismatchError):
        eng.generate([1, 2], max_tokens=63)             # > capacity
    with pytest.raises(ShapeMismatchError):
        eng.generate([1, 2], max_tokens=0)


def test_deadline_mid_stream_terminates_cleanly(shared_lm):
    """A deadline expiring mid-generation closes the stream with reason
    'deadline' — the consumer's iteration ENDS (no hang), partial tokens
    stand, and the slot/blocks are released for the next request."""
    net, spec, eng = shared_lm
    # 8ms: long enough to clear admission + one warmed prefill, short
    # enough that no rig decodes all 60 tokens first (each step syncs a
    # token readback) — the deadline must win, whatever the machine speed
    st = eng.generate([1, 2, 3], max_tokens=60, timeout=0.008,
                      stream=True)
    toks = list(st)                      # must terminate on its own
    assert st.finish_reason == "deadline"
    assert len(toks) < 60
    assert st.emitted == len(toks)
    # the slot is free again: a normal request completes afterwards
    toks2, reason = eng.generate([4, 5], max_tokens=3)
    assert (len(toks2), reason) == (3, "length")
    # mid-generation expiry counts as finished; a (rare, loaded-rig)
    # expiry while still queued counts as rejected — either terminates
    m = eng.metrics()["lm"]
    assert (m["finished"].get("deadline", 0)
            + m["rejected"].get("deadline", 0)) >= 1


def test_drain_and_stop_semantics():
    """drain=True completes in-flight + queued work then refuses new
    submissions (503); drain=False terminates everything NOW — either way
    every stream finishes and no caller hangs."""
    net = _lm(seed=53, vocab=29, d_model=16, n_blocks=1, max_length=256)
    eng = GenerationEngine(net, model_name="lm", block_len=8, max_seq_len=64,
                           decode_slots=1, prefill_batches=(1,),
                           prompt_rungs=(64,))
    st = eng.generate([1, 2], max_tokens=20, stream=True)
    eng.stop(drain=True, timeout=30.0)
    toks, reason = st.result()
    assert (len(toks), reason) == (20, "length")    # drained to completion
    with pytest.raises(DrainingError):
        eng.generate([1], max_tokens=1)

    # 250 tokens of runway: no rig finishes them inside the 10ms window,
    # so stop(drain=False) always lands mid-flight
    eng2 = GenerationEngine(net, model_name="lm", block_len=8,
                            max_seq_len=256, decode_slots=1,
                            prefill_batches=(1,), prompt_rungs=(64,))
    st2 = eng2.generate([1, 2], max_tokens=250, stream=True)
    time.sleep(0.01)                       # let it get in flight
    eng2.stop(drain=False, timeout=5.0)
    toks2 = list(st2)                      # terminates, partial or empty
    assert st2.finish_reason == "shutdown"
    assert len(toks2) < 250


def test_prefill_failure_fails_caller_and_engine_recovers():
    """A device-side program failure must resolve EVERY caller (no hung
    streams), release the failed requests' slots and blocks, and drop the
    cohort (its donated cache may be invalid) so the next admission runs
    on a fresh pool — regression for the admitted-but-not-yet-in-cohort
    window where a prefill exception previously leaked the slot and left
    the stream waiting forever."""
    net = _lm(seed=67, vocab=29, d_model=16, n_blocks=1, max_length=32)
    spec = TransformerDecodeSpec(net)
    want = naive_generate(net, [1, 2, 3], 4, pad_to=32, spec=spec)
    eng = GenerationEngine(net, model_name="lm", block_len=8, max_seq_len=32,
                           decode_slots=2, prefill_batches=(1,),
                           prompt_rungs=(32,))
    try:
        rt = eng._get("lm")
        orig = rt.active_ps.run_prefill
        calls = {"n": 0}

        def boom(*a, **k):
            calls["n"] += 1
            if calls["n"] == 1:
                raise RuntimeError("injected device failure")
            return orig(*a, **k)

        rt.active_ps.run_prefill = boom
        st = eng.generate([1, 2, 3], max_tokens=4, stream=True)
        toks, reason = st.result(raise_on_error=False)   # must NOT hang
        assert reason == "error"
        assert isinstance(st.error, RuntimeError)
        assert toks == []
        # slot + blocks released, cohort rebuilt: next request is correct
        toks2, r2 = eng.generate([1, 2, 3], max_tokens=4)
        assert (toks2, r2) == (want, "length")
        assert eng.models()["lm"]["in_flight"] == 0
        snap = eng.metrics()["lm"]
        assert snap["rejected"]["error"] >= 1
        assert snap["finished"].get("error") == 1
    finally:
        eng.stop()


# ----------------------------------------------------------------- hot-swap
def test_hot_swap_cutover_in_flight_on_old_params():
    """The cutover rule: a generation in flight at swap time finishes on
    the OLD params; the next admission runs the new ones. Same-arch swap
    reuses compiled executables (no new traces/compiles)."""
    net_a = _lm(seed=7)
    net_b = _lm(seed=8)            # same arch, different params
    spec_a, spec_b = TransformerDecodeSpec(net_a), TransformerDecodeSpec(net_b)
    prompt = _prompts(53, (6,), seed=9)[0]
    want_a = naive_generate(net_a, prompt, 40, pad_to=64, spec=spec_a)
    want_b = naive_generate(net_b, prompt, 40, pad_to=64, spec=spec_b)
    assert want_a != want_b        # the pin below must be discriminating
    eng = GenerationEngine(net_a, model_name="lm", block_len=8,
                           max_seq_len=64, decode_slots=2,
                           prefill_batches=(1,), prompt_rungs=(64,))
    try:
        traces0 = eng.trace_count
        compiles0 = xla_compile_count()
        st_a = eng.generate(prompt, max_tokens=40, stream=True)
        deadline = time.monotonic() + 5.0        # wait for admission
        while eng.metrics()["lm"]["prefills"] < 1:
            assert time.monotonic() < deadline
            time.sleep(0.002)
        version = eng.hot_swap("lm", net_b)
        assert version == 2
        st_b = eng.generate(prompt, max_tokens=40, stream=True)
        toks_a, reason_a = st_a.result()
        toks_b, reason_b = st_b.result()
        assert (toks_a, reason_a) == (want_a, "length"), \
            "in-flight generation must finish on the OLD params"
        assert (toks_b, reason_b) == (want_b, "length"), \
            "post-swap admission must run the NEW params"
        assert eng.trace_count == traces0          # executables reused
        assert xla_compile_count() == compiles0
        assert eng.metrics()["lm"]["hot_swaps"] == 1
    finally:
        eng.stop()


def _swap_soak(n_swaps: int, clients: int, max_new: int):
    net_a = _lm(seed=7)
    net_b = _lm(seed=8)
    spec_a, spec_b = TransformerDecodeSpec(net_a), TransformerDecodeSpec(net_b)
    prompts = _prompts(53, (5, 9), seed=13)
    want = {}
    for i, p in enumerate(prompts):
        want[i] = (naive_generate(net_a, p, max_new, pad_to=64, spec=spec_a),
                   naive_generate(net_b, p, max_new, pad_to=64, spec=spec_b))
    eng = GenerationEngine(net_a, model_name="lm", block_len=8,
                           max_seq_len=64, decode_slots=4,
                           prefill_batches=(1, 2), prompt_rungs=(64,))
    errors = []
    stop_flag = threading.Event()

    def client(tid):
        k = tid
        while not stop_flag.is_set():
            i = k % 2
            toks, reason = eng.generate(prompts[i], max_tokens=max_new)
            if reason != "length" or \
                    (toks != want[i][0] and toks != want[i][1]):
                errors.append((tid, k, reason, toks))
                return
            k += 1

    try:
        threads = [threading.Thread(target=client, args=(t,))
                   for t in range(clients)]
        for t in threads:
            t.start()
        nets = [net_b, net_a]
        for s in range(n_swaps):
            time.sleep(0.05)
            eng.hot_swap("lm", nets[s % 2])
        stop_flag.set()
        for t in threads:
            t.join(timeout=30.0)
        assert not errors, f"hot-swap soak diverged: {errors[:3]}"
        assert eng.metrics()["lm"]["hot_swaps"] == n_swaps
    finally:
        stop_flag.set()
        eng.stop()


@pytest.mark.slow   # tier-1 keeps the hot-swap contract via
# test_hot_swap_cutover_in_flight_on_old_params; the 20-swap soak below
# covers the under-load interleaving
def test_hot_swap_under_decode_soak_fast():
    """Fast variant of the hot-swap-under-decode soak: swaps land
    while clients stream; every result must match ONE of the two param
    sets exactly — never a mixture."""
    _swap_soak(n_swaps=3, clients=3, max_new=12)


@pytest.mark.slow
def test_hot_swap_under_decode_soak():
    _swap_soak(n_swaps=20, clients=6, max_new=24)


@pytest.mark.slow
def test_generation_hammer_soak():
    """Sustained mixed traffic: many clients, mixed prompt rungs and
    sampling settings, full-length streams — result integrity + zero
    recompiles over thousands of tokens."""
    net = _lm(seed=61, vocab=41, d_model=16, n_blocks=1, max_length=64)
    eng = GenerationEngine(net, model_name="lm", block_len=8, max_seq_len=64,
                           decode_slots=8, prefill_batches=(1, 2, 4),
                           prompt_rungs=(16, 64), queue_limit=4096)
    try:
        compiles0 = xla_compile_count()
        stop_at = time.monotonic() + 8.0
        errors = []

        def client(tid):
            rng = np.random.default_rng(tid)
            while time.monotonic() < stop_at:
                plen = int(rng.integers(1, 40))
                mx = int(rng.integers(1, 20))
                temp = float(rng.choice([0.0, 0.8]))
                toks, reason = eng.generate(
                    rng.integers(1, 41, size=plen).tolist(),
                    max_tokens=mx, temperature=temp, timeout=60.0)
                if reason != "length" or len(toks) != mx or \
                        not all(0 <= t < 41 for t in toks):
                    errors.append((tid, plen, mx, reason, len(toks)))
                    return

        threads = [threading.Thread(target=client, args=(t,))
                   for t in range(12)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors, errors[:3]
        assert xla_compile_count() == compiles0
        assert eng.metrics()["lm"]["tokens_out"] > 500
    finally:
        eng.stop()


# ------------------------------------- what the loop says about itself
# (ISSUE 24: the program measures each of its layers where the work
# happens, under the names the benchmark's readers key on)
def _pair_of_requests(eng, temperatures=(0.0, 0.0), seed=2424):
    """The trace events of one hand-made pair of requests, prompts of 5
    and 11 tokens and 3 tokens out each, sent WITHOUT a trace context and
    admitted in one pass (the loop cannot take its lock between the two
    submissions): one prefill at rows 2 x rung 64, then two decode steps.
    Another ``seed`` gives other prompts, which miss the prefix cache."""
    rt = eng._get("lm")
    rng = np.random.default_rng(seed)
    a, b = (rng.integers(1, 53, size=n).tolist() for n in (5, 11))
    reg = get_registry()
    seq0 = reg.last_seq
    with rt._cond:
        streams = [eng.generate(p, max_tokens=3, stream=True, temperature=t)
                   for p, t in zip((a, b), temperatures)]
    assert [len(s.result()[0]) for s in streams] == [3, 3]
    while rt.in_flight:                   # the emit after the last step
        time.sleep(0.005)
    time.sleep(0.05)
    events = reg.trace_events_since(seq0)
    return [e for e in events if e["name"].startswith("generation.")]


@pytest.fixture(scope="module")
def pair_events(shared_lm):
    """A greedy pair. The engine has sat idle first, so the pass ends an
    idle period."""
    time.sleep(0.1)                       # five idle wake-ups of the loop
    return _pair_of_requests(shared_lm[2])


@pytest.fixture(scope="module")
def sampling_pair_events(shared_lm):
    """The same pair, the second request at a temperature of 1."""
    return _pair_of_requests(shared_lm[2], (0.0, 1.0), seed=3939)


@pytest.fixture(scope="module")
def greedy_pair_after_sampling_events(shared_lm, sampling_pair_events):
    """A greedy pair in slots of which one has just sampled."""
    return _pair_of_requests(shared_lm[2], seed=3940)


def _named(events, name, **match):
    return [e for e in events if e["name"] == name
            and all(e.get(k) == v for k, v in match.items())]


@pytest.mark.parametrize("parent,program,calls", [
    ("generation.prefill", "prefill", 1),
    ("generation.decode_step", "decode", 2)])
def test_program_spans_split_into_dispatch_and_readback(pair_events, parent,
                                                        program, calls):
    outer = _named(pair_events, parent, ph="X", cat="span")
    assert len(outer) == calls
    for child in ("generation.dispatch", "generation.readback"):
        inner = [e for e in _named(pair_events, child, ph="X", cat="span")
                 if e["args"]["program"] == program]
        assert len(inner) == calls
        for i in inner:
            # nested in a parent: by path, and in time (a decode span
            # holds the launch of one step and the read of the step
            # before, so a launch need not sit in its own step's span)
            assert i["args"]["path"] == parent + "/" + child
            assert [o for o in outer if o["ts"] <= i["ts"] and
                    i["ts"] + i["dur"] <= o["ts"] + o["dur"] + 1]
    # launch first, then the blocking read
    d, r = (_named(pair_events, n)[0] for n in
            ("generation.dispatch", "generation.readback"))
    assert d["ts"] + d["dur"] <= r["ts"] + 1


@pytest.mark.parametrize("phase,at_least", [
    ("generation.admit_batch", 1), ("generation.emit", 3),
    ("generation.idle_wait", 1)])
def test_loop_host_phases_are_phase_events(pair_events, phase, at_least):
    """Complete events of category ``phase``, never ``span``: the
    benchmark hands every ``span`` to a clock calibration that needs spans
    which block on the device."""
    found = _named(pair_events, phase)
    assert len(found) >= at_least
    assert all(e["ph"] == "X" and e["cat"] == "phase" and e["dur"] >= 0
               and e["args"]["model"] == "lm" for e in found)
    if phase == "generation.idle_wait":
        # one event for the whole idle period, not one per 20 ms wake-up
        assert len(found) == 1 and found[0]["dur"] >= 90_000
    else:
        # a phase never overlaps a program call
        for p in found:
            for s in _named(pair_events, "generation.dispatch") + \
                    _named(pair_events, "generation.readback"):
                assert p["ts"] + p["dur"] <= s["ts"] + 1 \
                    or s["ts"] + s["dur"] <= p["ts"] + 1


def test_admit_event_for_a_request_without_trace_context(pair_events):
    admits = _named(pair_events, "generation.admit", ph="i")
    assert sorted(e["args"]["prompt_len"] for e in admits) == [5, 11]
    for e in admits:
        assert e["args"]["queue_ms"] >= 0.0
        assert "trace_id" not in e["args"]
        assert e["args"]["slot"] in range(4)
    # the per-token heartbeat stays with traced requests only
    assert not _named(pair_events, "generation.decode_step", ph="i")


@pytest.mark.parametrize("which,sampled", [
    ("pair_events", 0), ("sampling_pair_events", 1),
    ("greedy_pair_after_sampling_events", 0)])
def test_step_and_prefill_spans_count_their_tokens(request, which, sampled):
    events = request.getfixturevalue(which)
    (fill,) = _named(events, "generation.prefill", ph="X")
    a = fill["args"]
    assert (a["rows"], a["tokens"], a["padded_tokens"]) == (2, 16, 2 * 64)
    # the head ran on one row a prompt, not on the 2 x 64 padded positions
    # (counted from the prefill program's own matmuls, programs._head_rows)
    assert a["head_rows"] == 2
    assert (a["batch"], a["rung"]) == (2, 64)         # as before
    steps = _named(events, "generation.decode_step", ph="X")
    # positions valid in the cache, this step's included: (5+1)+(11+1),
    # then one more each; the attention kernel reads the whole pages of 8
    # that hold them, 8 + 16 a layer (the gather read 4 slots x capacity
    # 64 = 256, whatever was live)
    assert [s["args"]["live_tokens"] for s in steps] == [18, 20]
    assert [s["args"]["gathered_tokens"] for s in steps] == [24, 24]
    assert all(s["args"]["slots"] == 2 for s in steps)
    # live rows with a temperature: a call with none took the sampler's
    # greedy branch. A slot that sampled last time and is greedy or empty
    # now does not count (and is not handed to the program as sampling)
    assert a["sampled"] == sampled
    assert [s["args"]["sampled"] for s in steps] == [sampled, sampled]


def test_metrics_show_queue_wait_and_host_phases(shared_lm, pair_events):
    _, _, eng = shared_lm
    snap = eng.metrics()["lm"]
    for key in ("queue_wait_ms", "admit_ms", "emit_ms"):
        assert set(snap[key]) == {"p50", "p99"} and snap[key]["p99"] >= 0.0
    hists = get_registry().snapshot()["histograms"]
    for key in ("queue_wait_ms", "admit_ms", "emit_ms", "decode_step_ms"):
        assert hists[f"generation.lm.{key}"]["count"] >= 2
    # the step histogram is fed from the span's own interval
    assert hists["span.generation.decode_step_ms"]["count"] >= 2


# ---------------- prefill applies the head to the rows it reads (ISSUE 33)
# rungs and batches of the program set below; 24 and 64 are no other
# dimension of the model (d 32, heads 2 x 16, ff 128, vocab 53)
_HEAD_RUNGS, _HEAD_BATCHES = (24, 64), (2, 4)


@pytest.fixture(scope="module")
def head_rows_set():
    from deeplearning4j_tpu.serving.generation.programs import \
        GenerationProgramSet
    net = _lm(dtype="float32")
    cfg = GenerationConfig(block_len=8, max_seq_len=64, decode_slots=4,
                           prefill_batches=_HEAD_BATCHES,
                           prompt_rungs=_HEAD_RUNGS)
    return net, GenerationProgramSet(net, config=cfg).warm()


def _hlo_shapes(text):
    """Every array shape an HLO module's text names, as tuples of ints."""
    return {tuple(int(d) for d in m.split(","))
            for m in re.findall(r"\b[a-z]+\d*\[([\d,]+)\]", text)}


@pytest.mark.parametrize("P", _HEAD_BATCHES)
@pytest.mark.parametrize("L", _HEAD_RUNGS)
def test_prefill_head_runs_on_the_rows_it_reads(head_rows_set, L, P):
    """A batch of unequal prompts (one token, one that fills the rung, one
    in between when there is room) and, at batch 4, a padding row."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.serving.generation.kvcache import prefill_scatter
    net, ps = head_rows_set
    spec, cfg = ps.spec, ps.config
    V, S, mb = spec.vocab, cfg.decode_slots, cfg.blocks_per_seq
    lens = [1, L] if P == 2 else [1, L, L // 2 + 1]
    rng = np.random.default_rng(3300 + L + P)
    tokens = np.zeros((P, L), np.int32)
    lengths = np.ones(P, np.int32)
    tables = np.zeros((P, mb), np.int32)
    slots = np.full(P, S, np.int32)
    for i, n in enumerate(lens):
        tokens[i, :n] = rng.integers(1, V, size=n)
        lengths[i] = n
        tables[i] = 1 + i * mb + np.arange(mb)
        slots[i] = i
    zf, zi = np.zeros(P, np.float32), np.zeros(P, np.int32)
    first, (k_pool, v_pool), _ = ps.run_prefill(
        ps.make_cache(), pack_prefill(tokens, lengths, tables, slots, zf, zi),
        ps.fresh_key())

    # (a) the first token, and the logits it is sampled from, are
    # net.output's row lengths - 1 of every live row
    probs = np.asarray(net.output(tokens))               # [P, L, V] softmax
    logits, _, _ = jax.jit(spec.prefill_forward)(
        ps.params, ps.state, tokens, lengths - 1)
    assert logits.shape == (P, V)
    for i, n in enumerate(lens):
        assert int(first[i]) == int(probs[i, n - 1].argmax())
        np.testing.assert_allclose(jax.nn.softmax(logits[i]),
                                   probs[i, n - 1], rtol=1e-5, atol=1e-7)

    # (b) the executable the engine runs names no array with both the
    # rung and the vocabulary in its shape: [P, L, V] cannot come back
    shapes = _hlo_shapes(ps._compiled[("prefill", P, L)].as_text())
    assert (P, V) in shapes and (P, L, spec.d_model) in shapes
    assert not [s for s in shapes if L in s and V in s]
    # and what the ``generation.prefill`` span reports as ``head_rows`` is
    # read off the program: one row a prompt, padding rows included
    assert ps.head_rows[(P, L)] == P

    # (c) the K/V in the pool are bit for bit what the graph's forward
    # gives: ln1's output times Wk / Wv, scattered by the block table
    def reference(params, state, pools):
        acts, _ = net.apply_fn(params, state, [tokens], train=False)
        kv = {"Wk": [], "Wv": []}
        for i in range(spec.n_blocks):
            for w in kv:
                kv[w].append((acts[f"b{i}_ln1"] @ params[spec.vi(
                    f"b{i}_attn")][w]).reshape(P, L, spec.n_heads,
                                               spec.head_dim))
        return (prefill_scatter(pools[0], kv["Wk"], tables),
                prefill_scatter(pools[1], kv["Wv"], tables))
    want_k, want_v = jax.jit(reference)(ps.params, ps.state, ps.make_cache())
    # block 0 is the trash block the padding row's writes collide in
    assert jnp.array_equal(k_pool[:, 1:], want_k[:, 1:])
    assert jnp.array_equal(v_pool[:, 1:], want_v[:, 1:])
    assert bool(jnp.any(k_pool[:, 1:] != 0))


def test_head_rows_is_read_off_the_program_not_assumed():
    """The LSTM adapter's prefill is a scan that applies the head at every
    step and keeps one row (no cell runs it; ISSUE 33 left it): its
    programs must say so, P x L rows, where the transformer's say P."""
    from deeplearning4j_tpu.serving.generation.programs import \
        GenerationProgramSet
    cfg = GenerationConfig(block_len=8, max_seq_len=64, decode_slots=2,
                           prefill_batches=(1, 2), prompt_rungs=(16,))
    ps = GenerationProgramSet(_steady_char_lstm(), config=cfg).warm()
    assert ps.head_rows == {(1, 16): 16, (2, 16): 32,
                            (1, 64): 64, (2, 64): 128}


# ------------- the sampler does the work its batch asks for (ISSUE 39)
def _primitives(jaxpr):
    """Names of every primitive in a jaxpr, sub-jaxprs included."""
    import jax
    names = set()
    for eqn in jaxpr.eqns:
        names.add(eqn.primitive.name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            names |= _primitives(sub)
    return names


@pytest.mark.parametrize("program", [("decode",)] + [
    ("prefill", P, L) for P in _HEAD_BATCHES for L in _HEAD_RUNGS],
    ids=lambda k: "-".join(str(p) for p in k))
def test_serving_programs_sort_nothing_and_branch_on_sampling(head_rows_set,
                                                              program):
    """The executables the engine runs: no ``sort`` (the top-k threshold
    is selected, not sorted for) and a ``conditional`` (a prefill has the
    sampler's alone; off the TPU the decode program's attention kernel
    runs in the Pallas interpreter, which brings its own)."""
    _, ps = head_rows_set
    text = ps._compiled[program].as_text()
    assert not re.findall(r"\bsort\(", text)
    found = len(re.findall(r"\bconditional\(", text))
    assert found == 1 or (program[0] == "decode" and found > 1)


@pytest.mark.parametrize("which", ["prefill", "decode"])
def test_greedy_branch_draws_no_random_bits(head_rows_set, which):
    """In the program as traced: the key's split sits outside the
    ``cond``; of its two branches one draws (random bits, the selection's
    loop) and the other holds no equation that makes or uses randomness."""
    import jax
    _, ps = head_rows_set
    P, L = _HEAD_BATCHES[0], _HEAD_RUNGS[0]
    cfg = ps.config
    S, mb = cfg.decode_slots, cfg.blocks_per_seq
    key = ps.fresh_key()
    if which == "prefill":
        args, fn = ps._prefill_avals(P, L), ps._prefill_fn()
    else:
        args, fn = ps._decode_avals(), ps._decode_fn()
    jaxpr = jax.make_jaxpr(fn)(ps.params, ps.state, ps.make_cache(),
                               *args).jaxpr
    conds = [e for e in jaxpr.eqns if e.primitive.name == "cond"]
    assert len(conds) == 1
    greedy, draw = sorted((_primitives(b.jaxpr)
                           for b in conds[0].params["branches"]), key=len)
    assert not [p for p in greedy if "random" in p or "threefry" in p
                or p in ("scan", "while", "sort")], greedy
    # the other draws, and selects its threshold in a loop of passes
    assert {"random_bits", "scan"} <= draw and "sort" not in draw
    # the split that carries the key on is outside the branch
    assert "random_split" in {e.primitive.name for e in jaxpr.eqns}


# ------------------- the decode pipeline, one step deep (ISSUE 41): the
# loop launches step k+1 before it reads step k, k+1 takes its tokens
# from k's result on the device, and a slot whose last token by count is
# in flight goes back at that launch
def _pipe_lm():
    return _lm(seed=41, vocab=47, d_model=16, n_blocks=1, max_length=64)


@pytest.fixture(scope="module")
def pipe_lm():
    """One engine for the pipeline's read-only tests: 3 slots, a pool that
    holds two whole sequences beside the prefix cache's blocks."""
    net = _pipe_lm()
    eng = GenerationEngine(net, model_name="lm", block_len=8, max_seq_len=64,
                           decode_slots=3, prefill_batches=(1, 2),
                           prompt_rungs=(64,))
    yield net, TransformerDecodeSpec(net), eng
    eng.stop()


def _steps_since(seq0):
    return [e for e in get_registry().trace_events_since(seq0)
            if e["name"] == "generation.decode_step" and e["ph"] == "X"]


def _settle(eng):
    rt = eng._get("lm")
    deadline = time.monotonic() + 10.0
    while rt._work_left():
        assert time.monotonic() < deadline
        time.sleep(0.002)
    time.sleep(0.02)


@pytest.mark.parametrize("replay", ["miss", "partial_hit", "aligned_hit"])
def test_pipelined_greedy_tokens_are_the_full_recomputes(pipe_lm, replay):
    """More requests than slots, output lengths 1-17 mixed, so that steps
    hold slots at their first, middle and last token at once, admissions
    land behind an unread step and slots are reused at once: every token
    is the cache-free recompute's (what the synchronous loop served).
    With a hit, prompts replay their suffix through host-known rows."""
    net, spec, eng = pipe_lm
    seed = {"miss": 411, "partial_hit": 412, "aligned_hit": 413}[replay]
    base = _prompts(47, (16,), seed=seed)[0]
    if replay == "miss":
        prompts = _prompts(47, (3, 5, 9, 12, 17, 20, 6), seed=seed)
    else:
        # the first request leaves two full blocks behind; the others
        # match them and replay 1-5 prompt tokens (aligned: the whole
        # prompt is cached, its last block is copied on write)
        tails = (0, 0, 0) if replay == "aligned_hit" else (1, 3, 5)
        assert eng.generate(base, max_tokens=2)[0] == \
            naive_generate(net, base, 2, pad_to=64, spec=spec)
        prompts = [base + _prompts(47, (n,), seed=seed + n)[0]
                   if n else list(base) for n in tails] * 2
    lengths = [1, 2, 17, 3, 9, 1, 5][:len(prompts)]
    refs = [naive_generate(net, p, n, pad_to=64, spec=spec)
            for p, n in zip(prompts, lengths)]
    hits0 = eng.metrics()["lm"]["prefix"]["hits"]
    streams = [eng.generate(p, max_tokens=n, stream=True)
               for p, n in zip(prompts, lengths)]
    for st, want in zip(streams, refs):
        assert st.result() == (want, "length")
    _settle(eng)
    snap = eng.metrics()["lm"]
    assert (snap["prefix"]["hits"] - hits0 > 0) == (replay != "miss")
    assert eng.models()["lm"]["in_flight"] == 0
    eng._get("lm")._check_quiesce()


def test_overlapped_is_0_on_the_first_step_after_idle_and_1_after(pipe_lm):
    """One request, five tokens: four decode steps, the first launched
    with nothing unread, each later one behind its predecessor."""
    _, _, eng = pipe_lm
    _settle(eng)
    names = ("generation.lm.decode_steps_overlapped",
             "generation.lm.overrun_tokens_dropped")
    counted = lambda: [get_registry().snapshot()["counters"][n]
                       for n in names]
    seq0 = get_registry().last_seq
    before, counted0 = eng.metrics()["lm"], counted()
    toks, _ = eng.generate(_prompts(47, (7,), seed=415)[0], max_tokens=5)
    _settle(eng)
    steps = _steps_since(seq0)
    assert [s["args"]["overlapped"] for s in steps] == [0, 1, 1, 1]
    assert all(s["args"]["slots"] == 1 for s in steps)
    after = eng.metrics()["lm"]
    assert after["decode_steps"] - before["decode_steps"] == 4
    assert after["decode_steps_overlapped"] \
        - before["decode_steps_overlapped"] == 3
    # the registry's counters move with them (every engine of this
    # process called "lm" counts there: deltas)
    assert [b - a for a, b in zip(counted0, counted())] == [3, 0]


@pytest.mark.parametrize("budget,overrun", [(8, 1), (4, 0)])
def test_a_stop_token_is_seen_one_step_late_and_the_overrun_dropped(
        pipe_lm, budget, overrun):
    """The fourth token is a stop token. With budget left the slot was in
    the next launch when the host saw it: neither the stop token nor the
    token the slot ran over is emitted, and the overrun is counted. Where
    the stop token is the slot's last by count, nothing ran over."""
    net, spec, eng = pipe_lm
    for seed in range(4160, 4260):       # a prompt whose fourth token is
        prompt = _prompts(47, (6,), seed=seed)[0]       # new to its output
        greedy = naive_generate(net, prompt, 8, pad_to=64, spec=spec)
        if greedy[3] not in greedy[:3]:
            break
    stop = greedy[3]
    _settle(eng)
    before = eng.metrics()["lm"]["overrun_tokens_dropped"]
    toks, reason = eng.generate(prompt, max_tokens=budget, stop=[stop])
    assert (toks, reason) == (greedy[:3], "stop")
    _settle(eng)
    assert eng.metrics()["lm"]["overrun_tokens_dropped"] - before == overrun
    # the slot and its pages are back, and serve the next request rightly
    assert eng.generate(prompt, max_tokens=8)[0] == greedy
    eng._get("lm")._check_quiesce()


@pytest.fixture(scope="module")
def last_step_watch():
    """One slot, pages for one sequence: A (4 tokens: a prefill and three
    decode steps) and B are submitted together, so B can only run in A's
    slot and pages. Every read of a decode step notes, on the loop's own
    thread, what the host held at that moment."""
    net = _pipe_lm()
    spec = TransformerDecodeSpec(net)
    eng = GenerationEngine(net, model_name="lm", block_len=8, max_seq_len=32,
                           decode_slots=1, prefill_batches=(1,),
                           prompt_rungs=(32,), num_blocks=3,
                           prefix_cache=False)
    rt = eng._get("lm")
    ps = rt.active_ps
    seen, checks = [], []
    orig, orig_check = ps.read_decode, rt._check_quiesce

    def watching(first, *step):
        coh = rt._cohorts[-1]
        seen.append({"slot_req": len(rt._slot_req), "early": len(rt._early),
                     "free_blocks": coh.allocator.free_blocks,
                     "slots_free": len(rt._slots_free),
                     "in_flight": rt.in_flight,
                     "prefills": rt.metrics.prefills})
        return orig(first, *step)

    def checking():
        # the loop's own call, at the end of a pass that left no request
        # in a slot
        checks.append({"early": len(rt._early),
                       "unread": [c.unread is not None
                                  for c in rt._cohorts],
                       "work_left": rt._work_left()})
        orig_check()

    ps.read_decode, rt._check_quiesce = watching, checking
    a, b = _prompts(47, (5, 7), seed=418)
    want = [naive_generate(net, p, n, pad_to=32, spec=spec)
            for p, n in ((a, 4), (b, 6))]
    with rt._cond:
        streams = [eng.generate(a, max_tokens=4, stream=True),
                   eng.generate(b, max_tokens=6, stream=True)]
    got = [st.result() for st in streams]
    _settle(eng)
    yield seen, got, want, checks
    eng.stop()


def test_a_length_finish_frees_slot_and_pages_at_its_last_launch(
        last_step_watch):
    seen, _, _, _ = last_step_watch
    # read 1 (step 1; steps 1 and 2 are launched): A holds slot and pages
    assert (seen[0]["slot_req"], seen[0]["early"]) == (1, 0)
    assert seen[0]["free_blocks"] == 0 and seen[0]["slots_free"] == 0
    # read 2 (step 2): step 3, A's last by count, is launched, and slot
    # and pages went back at that launch, before any token of it is read
    assert (seen[1]["slot_req"], seen[1]["early"]) == (0, 1)
    assert seen[1]["free_blocks"] == 2 and seen[1]["slots_free"] == 1
    # ... but A still counts as in flight until its last token is read
    assert seen[1]["in_flight"] == 1


def test_the_next_request_decodes_rightly_in_the_freed_slot_and_pages(
        last_step_watch):
    seen, got, want, _ = last_step_watch
    assert got == [(want[0], "length"), (want[1], "length")]
    # B's prefill ran BEFORE A's last token was read: read 3 is step 3's,
    # and B already holds the slot and both pages
    assert seen[2]["prefills"] == 2
    assert (seen[2]["slot_req"], seen[2]["early"]) == (1, 1)
    assert seen[2]["free_blocks"] == 0
    assert len(seen) == 3 + 5


def test_quiesce_holds_with_a_last_token_still_unread(last_step_watch):
    """The pass that launched A's last step ends with no request in a
    slot (B is still queued) and A's last token unread: the loop's
    accounting check runs and passes (an unread step counts as in flight)
    and the loop does not go idle. The last check finds nothing left."""
    _, _, _, checks = last_step_watch
    assert checks[0] == {"early": 1, "unread": [True], "work_left": True}
    assert checks[-1] == {"early": 0, "unread": [False], "work_left": False}


def _engine_with_an_unread_last_token(on_read):
    """Two slots; A's last token by count (of 3) and B's 3rd of 30 are in
    one unread step when ``on_read`` runs in place of its read, on the
    loop's thread: A's slot is back already, B's is held."""
    net = _pipe_lm()
    eng = GenerationEngine(net, model_name="lm", block_len=8, max_seq_len=64,
                           decode_slots=2, prefill_batches=(2,),
                           prompt_rungs=(64,))
    rt = eng._get("lm")
    ps = rt.active_ps
    orig = ps.read_decode
    reads = {"n": 0}

    def reading(first, *step):
        reads["n"] += 1
        if reads["n"] == 2:                 # step 2: A's last
            assert len(rt._early) == 1 and len(rt._slot_req) == 1
            on_read()
        return orig(first, *step)

    ps.read_decode = reading
    a, b = _prompts(47, (5, 7), seed=419)
    with rt._cond:
        streams = [eng.generate(a, max_tokens=3, stream=True),
                   eng.generate(b, max_tokens=30, stream=True)]
    return net, eng, streams


def test_a_failing_step_fails_the_callers_of_the_unread_step_too():
    """The device's error surfaces at a read with the next step launched:
    the request whose slot went back at its last launch, the one that
    holds a slot, and the step behind all fail; the engine recovers."""
    def boom():
        raise RuntimeError("injected device failure")

    net, eng, streams = _engine_with_an_unread_last_token(boom)
    try:
        for st in streams:
            toks, reason = st.result(raise_on_error=False)   # must NOT hang
            assert reason == "error" and isinstance(st.error, RuntimeError)
            assert 1 <= len(toks) <= 2
        _settle(eng)
        rt = eng._get("lm")
        assert rt.in_flight == 0 and not rt._early
        assert len(rt._slots_free) == 2
        p = _prompts(47, (6,), seed=420)[0]
        assert eng.generate(p, max_tokens=5)[0] == naive_generate(
            net, p, 5, pad_to=64, spec=TransformerDecodeSpec(net))
        assert eng.metrics()["lm"]["finished"].get("error") == 2
    finally:
        eng.stop()


def test_stop_without_drain_resolves_the_callers_of_the_unread_step():
    """``stop(drain=False)`` arrives while the loop is in the read of a
    step that holds A's last token: both streams end as ``shutdown``,
    A's too, whose slot had gone back."""
    at_read, go = threading.Event(), threading.Event()

    def wait():
        at_read.set()
        assert go.wait(10.0)

    _, eng, streams = _engine_with_an_unread_last_token(wait)
    assert at_read.wait(10.0)
    stopper = threading.Thread(
        target=lambda: eng.stop(drain=False, timeout=10.0))
    stopper.start()
    rt = eng._get("lm")
    deadline = time.monotonic() + 10.0
    while not rt.draining:
        assert time.monotonic() < deadline
        time.sleep(0.002)
    time.sleep(0.02)                 # stop() has marked what is in flight
    go.set()
    stopper.join(20.0)
    assert not stopper.is_alive()
    for st in streams:
        toks, reason = st.result(raise_on_error=False)
        assert reason == "shutdown" and len(toks) <= 2
    assert rt.in_flight == 0


def test_a_speculating_cohort_keeps_the_synchronous_order():
    """A program set that speculates reads the host's tokens in
    ``_spec_step``: its plain steps (a request that opts out, a sampling
    one) launch nothing behind the step they read."""
    net = _lm(seed=43)
    spec = TransformerDecodeSpec(net)
    eng = GenerationEngine(net, model_name="lm", block_len=8, max_seq_len=64,
                           decode_slots=2, prefill_batches=(1,),
                           prompt_rungs=(64,), draft=truncated_draft(net, 1),
                           spec_k=3)
    try:
        rt = eng._get("lm")
        assert rt.active_ps.spec_k == 3
        p, q = _prompts(53, (6, 9), seed=421)
        seq0 = get_registry().last_seq
        streams = [eng.generate(p, max_tokens=9, stream=True,
                                speculative=False),
                   eng.generate(q, max_tokens=9, stream=True)]
        assert [st.result()[0] for st in streams] == [
            naive_generate(net, x, 9, pad_to=64, spec=spec) for x in (p, q)]
        _settle(eng)
        steps = _steps_since(seq0)
        assert len(steps) == 8              # the opted-out request's
        assert all(s["args"]["overlapped"] == 0 for s in steps)
        # nothing is launched behind the step a pass reads, and the steps
        # are numbered all the same
        assert all(s["args"]["launched"] == -1 for s in steps)
        numbers = [s["args"]["step"] for s in steps]
        assert numbers == list(range(numbers[0], numbers[0] + 8))
        assert all("cpu_ms" not in s["args"] for s in steps)
        verifies = [e for e in get_registry().trace_events_since(seq0)
                    if e["name"] == "generation.verify" and e["ph"] == "X"]
        assert verifies and all(v["args"]["cpu_ms"] >= 0.0
                                and v["args"]["read_wait_ms"] >= 0.0
                                for v in verifies)
        assert all(c.unread is None for c in rt._cohorts)
        snap = eng.metrics()["lm"]
        assert snap["decode_steps_overlapped"] == 0
        assert snap["speculative"]["verify_steps"] >= 1
    finally:
        eng.stop()


# ------------- the loop accounts for itself (ISSUE 42): every decode pass
# says which step it launched and read and what it waited for, every
# request leaves one record, a pass far out of line leaves one stall event
def _generation_events(seq0):
    return [e for e in get_registry().trace_events_since(seq0)
            if e["name"].startswith("generation.")]


STEPS = 19


@pytest.fixture(scope="module")
def numbered_events(pipe_lm):
    """One request of twenty tokens on an idle engine: a prefill, then
    nineteen decode steps in eighteen overlapped passes and a last one
    that launches nothing."""
    _, _, eng = pipe_lm
    _settle(eng)
    seq0 = get_registry().last_seq
    prompt = _prompts(47, (6,), seed=4242)[0]
    assert len(eng.generate(prompt, max_tokens=STEPS + 1)[0]) == STEPS + 1
    _settle(eng)
    return _generation_events(seq0)


def _decode_spans(events, name):
    return [e for e in _named(events, name, ph="X", cat="span")
            if e["args"].get("program", "decode") == "decode"]


@pytest.mark.parametrize("name", [
    "generation.decode_step", "generation.dispatch", "generation.readback"])
def test_step_numbers_count_up_without_a_hole(numbered_events, name):
    numbers = [e["args"]["step"] for e in _decode_spans(numbered_events, name)]
    assert len(numbers) == STEPS
    assert numbers == list(range(numbers[0], numbers[0] + STEPS))
    # the other programs' launches and reads belong to no step
    assert all("step" not in e["args"] for e in numbered_events
               if e["args"].get("program") == "prefill")


def test_a_steps_launch_ends_before_its_read_starts(numbered_events):
    launch = {e["args"]["step"]: e
              for e in _decode_spans(numbered_events, "generation.dispatch")}
    reads = _decode_spans(numbered_events, "generation.readback")
    assert len(reads) == STEPS
    for r in reads:
        d = launch[r["args"]["step"]]
        assert d["ts"] + d["dur"] <= r["ts"] + 1


def test_launched_is_the_step_behind_the_one_a_pass_reads(numbered_events):
    passes = _decode_spans(numbered_events, "generation.decode_step")
    for p in passes[:-1]:
        assert p["args"]["launched"] == p["args"]["step"] + 1
    # the first pass after idle holds two launches, overlapped or not
    assert [p["args"]["overlapped"] for p in passes] == [0] + [1] * (STEPS - 1)
    assert passes[-1]["args"]["launched"] == -1


@pytest.mark.parametrize("name,steps", [
    ("generation.decode_step", STEPS), ("generation.prefill", 1)])
def test_a_pass_says_what_it_waited_for(numbered_events, name, steps):
    found = _named(numbered_events, name, ph="X", cat="span")
    assert len(found) == steps
    for e in found:
        a = e["args"]
        # the children's own stopwatches, rounded to the microsecond
        assert 0.0 <= a["launch_ms"] and 0.0 <= a["read_wait_ms"]
        assert a["launch_ms"] + a["read_wait_ms"] <= e["dur"] / 1e3 + 0.003
    if name == "generation.prefill":
        # a rare and long pass takes its own CPU time: there, and not
        # negative (no tier-1 test holds a CPU time to a size)
        assert found[0]["args"]["cpu_ms"] >= 0.0
        return
    # a pass with no launch has waited for none
    assert found[-1]["args"]["launch_ms"] == 0.0
    # a decode pass makes no system call of its own, but one in sixteen
    # samples the loop thread's usage so far
    assert all("cpu_ms" not in e["args"] for e in found)
    sampled = [e["args"] for e in found if "loop_cpu_ms" in e["args"]]
    assert [a["step"] for a in sampled] == [
        e["args"]["step"] for e in found if e["args"]["step"] % 16 == 0]
    assert 1 <= len(sampled) <= 2
    assert all(a["loop_cpu_ms"] >= 0.0 and a["nivcsw"] >= 0
               for a in sampled)


@pytest.fixture(scope="module")
def record_lm():
    """An engine of the record tests' own: they patch its program set."""
    net = _pipe_lm()
    eng = GenerationEngine(net, model_name="lm", block_len=8, max_seq_len=64,
                           decode_slots=2, prefill_batches=(1, 2),
                           prompt_rungs=(64,), prefix_cache=False)
    yield net, TransformerDecodeSpec(net), eng
    eng.stop()


def _end_as(reason, net, spec, eng):
    """Drive one request of the engine to end as ``reason``; returns its
    stream and the tokens it gave."""
    rt = eng._get("lm")
    ps = rt.active_ps
    launch, read = ps.launch_decode, ps.read_decode
    prompt = _prompts(47, (6,), seed=4300)[0]
    try:
        if reason == "length":
            st = eng.generate(prompt, max_tokens=5, stream=True)
            return st, list(st)
        if reason == "stop":
            greedy = naive_generate(net, prompt, 8, pad_to=64, spec=spec)
            stop = next(t for t in greedy[1:] if t != greedy[0])
            st = eng.generate(prompt, max_tokens=8, stop=[stop], stream=True)
            return st, list(st)

        def slow(*a, **k):
            time.sleep(0.02)
            return launch(*a, **k)

        def boom(first, *step):
            raise RuntimeError("injected device failure")

        if reason == "error":
            ps.read_decode = boom
        else:
            ps.launch_decode = slow
        st = eng.generate(prompt, max_tokens=50, stream=True,
                          timeout=0.3 if reason == "deadline" else 30.0)
        toks = []
        for tok in st:
            toks.append(tok)
            if reason == "cancelled" and len(toks) == 2:
                st.cancel()
        return st, toks
    finally:
        _settle(eng)
        ps.launch_decode, ps.read_decode = launch, read


@pytest.mark.parametrize("reason", ["length", "stop", "cancelled",
                                    "deadline", "error"])
def test_every_end_leaves_exactly_one_request_record(record_lm, reason):
    net, spec, eng = record_lm
    _settle(eng)
    seq0 = get_registry().last_seq
    st, toks = _end_as(reason, net, spec, eng)
    assert st.finish_reason == reason
    events = _generation_events(seq0)
    (rec,) = _named(events, "generation.request")
    a = rec["args"]
    # a complete event from the submission on, and never a ``span``: the
    # benchmark fits the device's clock on every span
    assert (rec["ph"], rec["cat"]) == ("X", "request")
    assert a["request"] == st.request_id and a["reason"] == reason
    assert a["tokens"] == len(toks) == st.emitted
    assert (a["prompt_len"], a["matched_tokens"], a["model"]) == (6, 0, "lm")
    assert (a["rung"], a["batch"]) == (64, 1) and a["slot"] in (0, 1)
    (admit,) = [e for e in _named(events, "generation.admit", ph="i")
                if e["args"]["request"] == st.request_id]
    assert a["queue_ms"] == admit["args"]["queue_ms"]
    assert toks and a["ttft_ms"] >= a["queue_ms"]
    assert rec["ts"] <= a["first_token_us"] <= a["last_token_us"] \
        <= rec["ts"] + rec["dur"] + 1
    # the passes it rode: one a token after the first, and the steps that
    # were in flight when the host learned of its end
    assert a["steps"] >= len(toks) - 1
    if reason == "length":
        assert a["steps"] == 4


def test_a_request_that_ends_in_the_queue_leaves_its_record_too(record_lm):
    net, spec, eng = record_lm
    rt = eng._get("lm")
    _settle(eng)
    seq0 = get_registry().last_seq
    with rt._cond:                # the loop cannot admit before the cancel
        st = eng.generate([1, 2, 3], max_tokens=4, stream=True)
        st.cancel()
    assert st.result() == ([], "cancelled")
    _settle(eng)
    (rec,) = _named(_generation_events(seq0), "generation.request")
    a = rec["args"]
    assert (a["request"], a["reason"], a["tokens"], a["slot"]) == (
        st.request_id, "cancelled", 0, -1)
    assert not {"queue_ms", "ttft_ms", "first_token_us"} & set(a)


def test_a_slow_read_leaves_one_stall_event_a_second():
    """Two reads of one second sleep 400 ms each, far over 20 times the
    median pass and over the floor of 50 ms: the first leaves the event,
    with the sleep in ``read_wait_ms`` and the thread's usage since its
    last sample; the second, inside the same second, none. A collection the read runs shows as the pass's
    ``gc_ms``, and the collector's hook goes with the engine."""
    import gc
    hooks = len(gc.callbacks)
    net = _pipe_lm()
    eng = GenerationEngine(net, model_name="lm", block_len=8, max_seq_len=64,
                           decode_slots=1, prefill_batches=(1,),
                           prompt_rungs=(64,), prefix_cache=False)
    try:
        assert len(gc.callbacks) == hooks + 1
        rt = eng._get("lm")
        ps = rt.active_ps
        prompt = _prompts(47, (6,), seed=4400)[0]
        eng.generate(prompt, max_tokens=12)      # the median's history
        _settle(eng)
        orig, reads = ps.read_decode, {"n": 0, "slept": []}

        class Slow:
            """A result whose copy to the host takes 400 ms more: the
            wait lies inside the read's own span."""

            def __init__(self, first):
                self.first = first

            def __array__(self, dtype=None, copy=None):
                time.sleep(0.4)
                return np.asarray(self.first)

        def reading(first, *step):
            reads["n"] += 1
            if reads["n"] in (3, 5):
                reads["slept"].append(step[0])
                first = Slow(first)
            if reads["n"] == 7:
                gc.collect(1)
            return orig(first, *step)

        ps.read_decode = reading
        seq0 = get_registry().last_seq
        assert len(eng.generate(prompt, max_tokens=10)[0]) == 10
        _settle(eng)
        events = _generation_events(seq0)
        (stall,) = _named(events, "generation.stall", ph="i")
        a = stall["args"]
        assert (a["span"], a["step"], a["model"]) == (
            "generation.decode_step", reads["slept"][0], "lm")
        assert 400.0 <= a["read_wait_ms"] <= a["wall_ms"]
        assert a["launch_ms"] >= 0.0 and a["compiles"] == 0
        # what the loop's thread did since its last usage sample, at most
        # sixteen passes back: the sleep is in the wall, not in the CPU
        assert a["since_sample_ms"] >= a["wall_ms"]
        assert 0.0 <= a["cpu_since_sample_ms"] and \
            a["nivcsw_since_sample"] >= 0
        # both slow passes are on their spans all the same
        passes = {p["args"]["step"]: p["args"] for p in
                  _named(events, "generation.decode_step", ph="X")}
        assert all(passes[n]["read_wait_ms"] >= 400.0
                   for n in reads["slept"])
        collected = [n for n, p in passes.items() if "gc_ms" in p]
        assert reads["slept"][0] + 4 in collected
        assert all(passes[n]["gc_ms"] > 0.0 for n in collected)
    finally:
        eng.stop()
    assert len(gc.callbacks) == hooks


# ------------- admission chooses its batch (ISSUE 43): when more requests
# wait than a pass can admit, the pass takes the head of the queue and the
# next waiting requests of the head's prompt rung, cut back to a batch rung
# they fill; when all that waits fits into the pass, it is arrival order
@pytest.mark.parametrize("rungs,free,batches,want", [
    # all that waits fits into the pass: all of it, in arrival order,
    # mixed rungs and a batch rung it does not fill
    ([16, 64, 16], 4, (1, 2, 4), [0, 1, 2]),
    ([64, 16], 2, (1, 2), [0, 1]),
    ([64], 1, (1, 2, 4), [0]),
    # more wait than the pass takes: the head, then its rung's next
    ([64, 16, 64, 16, 64, 64], 4, (1, 2, 4), [0, 2, 4, 5]),
    ([16, 64, 64, 16, 64], 4, (1, 2, 4), [0, 3]),
    # three of a rung: cut back to two, not three and an empty row
    ([16, 64, 16, 64, 16, 64], 4, (1, 2, 4), [0, 2]),
    # nobody of the head's rung waits: alone, never another rung's row
    ([16, 64, 64, 64, 64], 4, (1, 2, 4), [0]),
    # the free slots bound the batch, and three slots make a batch of two
    ([64, 64, 64], 2, (1, 2, 4), [0, 1]),
    ([64, 64, 64, 64, 64], 3, (1, 2, 4), [0, 1]),
    # no batch rung of one: two fill the smallest, a lone head still goes
    ([16, 16, 16], 2, (2, 4), [0, 1]),
    ([16, 64, 64], 2, (2, 4), [0]),
    # partners are looked for inside the lookahead only
    ([64] + [16] * (ADMIT_LOOKAHEAD - 2) + [64, 64], 4, (1, 2, 4),
     [0, ADMIT_LOOKAHEAD - 1]),
    ([64] + [16] * (ADMIT_LOOKAHEAD - 1) + [64, 64], 4, (1, 2, 4), [0]),
    # no free slot, nobody waiting
    ([16, 16, 16], 0, (1, 2), []),
    ([], 4, (1, 2), [])])
def test_admission_choice(rungs, free, batches, want):
    cfg = GenerationConfig(block_len=8, max_seq_len=64,
                           prefill_batches=batches, prompt_rungs=(16, 64))
    assert cfg.admission_choice(rungs, len(rungs), free) == want
    # the scheduler hands over the first rungs only and says how many wait
    assert cfg.admission_choice(rungs[:ADMIT_LOOKAHEAD], len(rungs),
                                free) == want


@pytest.mark.parametrize("seed,batches", [(1, (1, 2, 4)), (2, (1, 2, 4)),
                                          (3, (1, 2)), (4, (2, 4)),
                                          (5, (1, 4, 8))])
def test_a_deep_mixed_queue_leaves_in_whole_batches_of_one_rung(seed,
                                                                batches):
    """The rule replayed without an engine: sixty requests of three rungs
    arriving while the first forty leave, 1-9 slots free a pass. Every
    pass of a deep queue shares one rung and fills a batch rung; a queue
    that fits into the pass goes whole, in arrival order; the head goes at
    every pass, so a request leaves within as many admitting passes as its
    place in the queue when it arrived."""
    cfg = GenerationConfig(block_len=8, max_seq_len=64,
                           prefill_batches=batches, prompt_rungs=(16, 32, 64))
    rng = np.random.default_rng(seed)
    queue, due, passes = [], {}, 0

    def arrive(n):
        for _ in range(n):
            rid = len(due)
            queue.append((rid, int(rng.choice(cfg.prompt_rungs))))
            due[rid] = passes + len(queue)       # its place, in passes
    arrive(24)
    while queue:
        free = int(rng.integers(1, 10))
        room = min(free, batches[-1])
        took = cfg.admission_choice([r for _, r in queue], len(queue), free)
        passes += 1
        assert took and took[0] == 0 and took == sorted(set(took))
        if len(queue) > room:
            assert len({queue[i][1] for i in took}) == 1
            assert len(took) <= room
            assert len(took) in batches or len(took) < batches[0]
        else:
            assert took == list(range(len(queue)))
        for i in took:
            assert passes <= due[queue[i][0]]
        for i in reversed(took):
            del queue[i]
        if len(due) < 60:
            arrive(int(rng.integers(0, 5)))


_ADMIT_PLAN = dict(block_len=8, max_seq_len=64, decode_slots=4,
                   prefill_batches=(1, 2, 4), prompt_rungs=(16, 64))


@pytest.fixture(scope="module")
def admit_lm():
    """One engine for the admission tests: two prompt rungs, batches of
    one, two and four, four slots, no prefix cache (every request
    prefills)."""
    net = _pipe_lm()
    eng = GenerationEngine(net, model_name="lm", prefix_cache=False,
                           **_ADMIT_PLAN)
    yield net, TransformerDecodeSpec(net), eng
    eng.stop()


class _Prefills:
    """Every prefill an engine launches while the block runs, recorded on
    the loop's thread: its (P, L), the prompt lengths and slots of its
    live rows, and what waited and what was free BEFORE its pass (nobody
    submits meanwhile). ``draft`` holds the draft's prefills."""

    def __init__(self, eng):
        self.rt = eng._get("lm")
        self.ps = self.rt.active_ps
        self.seen, self.draft = [], []

    def __enter__(self):
        rt, ps, S = self.rt, self.ps, self.rt.config.decode_slots
        self.orig = ps.run_prefill, ps.run_draft_prefill

        def live(lengths, slots):
            return ([int(n) for n, s in zip(lengths, slots) if s != S],
                    [int(s) for s in slots if s != S])

        def run(cache, packed, key):
            tokens, lengths, _, slots, _, _ = unpack_prefill(
                packed, rt.config.blocks_per_seq)
            lens, rows = live(lengths, slots)
            self.seen.append({
                "P": tokens.shape[0], "L": tokens.shape[1], "lens": lens,
                "slots": rows, "waiting": len(rt._queue) + len(lens),
                "free": len(rt._slots_free) + len(lens)})
            return self.orig[0](cache, packed, key)

        def draft(cache, tokens, lengths, slots):
            lens, rows = live(lengths, slots)
            self.draft.append({"P": tokens.shape[0], "L": tokens.shape[1],
                               "lens": lens, "slots": rows})
            return self.orig[1](cache, tokens, lengths, slots)

        ps.run_prefill, ps.run_draft_prefill = run, draft
        return self

    def __exit__(self, *exc):
        self.ps.run_prefill, self.ps.run_draft_prefill = self.orig


def _serve_at_once(eng, prompts, lengths):
    """Submit everything before the loop can admit any of it; returns the
    tokens of each and the generation events of the run."""
    rt = eng._get("lm")
    _settle(eng)
    seq0 = get_registry().last_seq
    with rt._cond:
        streams = [eng.generate(p, max_tokens=n, stream=True)
                   for p, n in zip(prompts, lengths)]
    outs = [st.result() for st in streams]
    _settle(eng)
    assert all(reason == "length" for _, reason in outs)
    return [toks for toks, _ in outs], _generation_events(seq0)


# twelve prompts of distinct lengths (a launch names its rows by them):
# six pad to rung 16, six to rung 64, mixed in arrival order
_MIXED = (5, 30, 9, 41, 12, 22, 7, 50, 3, 33, 14, 19)
_MIXED_OUT = (3, 1, 6, 2, 5, 4, 1, 3, 2, 6, 4, 5)


def test_a_deep_mixed_queue_prefills_whole_batches_of_one_rung(admit_lm):
    """Twelve requests of two rungs wait for four slots. While more wait
    than a pass can take, every prefill holds prompts of ONE rung in a
    batch rung they fill; the passes at the end, which take all that
    waits, are arrival order. Every request is admitted within as many
    passes as its place at arrival, its ``jumped`` counts the earlier
    arrivals it went ahead of, and every token is the full recompute's."""
    net, spec, eng = admit_lm
    cfg = eng._get("lm").config
    prompts = _prompts(47, _MIXED, seed=4343)
    refs = [naive_generate(net, p, n, pad_to=64, spec=spec)
            for p, n in zip(prompts, _MIXED_OUT)]
    jumped0 = eng.metrics()["lm"]["admits_jumped"]
    with _Prefills(eng) as rec:
        outs, events = _serve_at_once(eng, prompts, _MIXED_OUT)
    assert outs == refs
    waiting = list(_MIXED)
    deep = 0
    for launch in rec.seen:
        lens = launch["lens"]
        assert launch["waiting"] == len(waiting)
        room = min(launch["free"], cfg.prefill_batches[-1])
        if len(waiting) > room:
            deep += 1
            assert lens[0] == waiting[0]                 # the head, first
            assert {cfg.prompt_rung(n) for n in lens} == {launch["L"]}
            assert len(lens) == launch["P"]              # no empty row
        else:
            assert lens == waiting
            assert launch["L"] == cfg.prompt_rung(max(lens))
        waiting = [n for n in waiting if n not in lens]
    assert not waiting and deep >= 2
    # the first pass had four slots and four waiting prompts of the head's
    # rung: one (4, 16) program
    assert (rec.seen[0]["P"], rec.seen[0]["L"]) == (4, 16)
    assert rec.seen[0]["lens"] == [5, 9, 12, 7]
    went = {n: k for k, launch in enumerate(rec.seen)
            for n in launch["lens"]}
    admits = {e["args"]["prompt_len"]: e["args"]["jumped"]
              for e in _named(events, "generation.admit", ph="i")}
    for place, n in enumerate(_MIXED):
        assert went[n] <= place
        assert admits[n] == sum(went[m] > went[n] for m in _MIXED[:place])
    assert sum(v > 0 for v in admits.values()) >= 3
    assert eng.metrics()["lm"]["admits_jumped"] - jumped0 == \
        sum(v > 0 for v in admits.values())
    assert get_registry().snapshot()["counters"][
        "generation.lm.admits_jumped"] >= sum(v > 0 for v in admits.values())


@pytest.mark.parametrize("sizes,program", [
    ((5, 30, 9), (4, 64)),        # an empty row, two prompts in a wide one
    ((30, 5), (2, 64)),
    ((7,), (1, 16)),
    ((9, 5, 30, 12), (4, 64))])
def test_a_queue_that_fits_into_one_pass_is_admitted_as_it_arrived(
        admit_lm, sizes, program):
    """The pass of an engine with time to spare: all that waits, in
    arrival order, padded to the longest prompt's rung and the next batch
    rung, and nobody went ahead of anybody."""
    net, spec, eng = admit_lm
    prompts = _prompts(47, sizes, seed=4344)
    refs = [naive_generate(net, p, 3, pad_to=64, spec=spec) for p in prompts]
    with _Prefills(eng) as rec:
        outs, events = _serve_at_once(eng, prompts, [3] * len(sizes))
    assert outs == refs
    (launch,) = rec.seen
    assert (launch["P"], launch["L"]) == program
    assert launch["lens"] == list(sizes)
    admits = _named(events, "generation.admit", ph="i")
    assert [e["args"]["prompt_len"] for e in admits] == list(sizes)
    assert all(e["args"]["jumped"] == 0 for e in admits)
    (sp,) = _named(events, "generation.prefill", ph="X", cat="span")
    assert (sp["args"]["batch"], sp["args"]["rows"], sp["args"]["rung"]) == (
        len(sizes), program[0], program[1])


def test_grouped_admission_emits_the_one_at_a_time_greedy_tokens(admit_lm):
    """Beside ``test_continuous_and_one_at_a_time_emit_same_greedy_tokens``:
    the order in which a deep queue is admitted is not a numerics choice
    either. Twelve clients at once over four slots and two rungs, and one
    caller at a time on a one-slot engine, emit the same tokens."""
    net, _, eng = admit_lm
    prompts = _prompts(47, _MIXED, seed=4345)
    outs, events = _serve_at_once(eng, prompts, [7] * len(prompts))
    assert any(e["args"]["jumped"] for e in
               _named(events, "generation.admit", ph="i"))
    serial = GenerationEngine(net, model_name="lm", block_len=8,
                              max_seq_len=64, decode_slots=1,
                              prefill_batches=(1,), prompt_rungs=(16, 64),
                              prefix_cache=False)
    try:
        for i, p in enumerate(prompts):
            assert serial.generate(p, max_tokens=7)[0] == outs[i], \
                f"prompt {i} diverged under the grouped order"
    finally:
        serial.stop()


@pytest.fixture(scope="module", params=[False, True],
                ids=["no_prefix_cache", "prefix_cache"])
def tight_lm(request):
    """The admission tests' plan over a pool of twelve usable blocks (a
    sequence of 64 tokens takes eight): admission is short of blocks, not
    of slots."""
    net = _pipe_lm()
    eng = GenerationEngine(net, model_name="lm", num_blocks=13,
                           prefix_cache=request.param, **_ADMIT_PLAN)
    yield net, TransformerDecodeSpec(net), eng
    eng.stop()


def test_a_head_that_does_not_fit_admits_nobody(tight_lm):
    """A (six blocks) is admitted; its partner by rung, B (seven), does
    not fit beside it, which ends that pass's search. Then B heads the
    queue and five small requests of the other rung wait behind it with
    three slots and six blocks free: nobody is admitted until A's blocks
    come back, and B goes first."""
    net, spec, eng = tight_lm
    sizes, lengths = (40, 50, 5, 7, 3, 9, 11), (8, 6, 3, 1, 5, 2, 4)
    prompts = _prompts(47, sizes, seed=4346)
    refs = [naive_generate(net, p, n, pad_to=64, spec=spec)
            for p, n in zip(prompts, lengths)]
    with _Prefills(eng) as rec:
        outs, events = _serve_at_once(eng, prompts, lengths)
    assert outs == refs
    assert [launch["lens"] for launch in rec.seen[:2]] == [[40], [50]]
    assert rec.seen[1]["free"] == 4         # A had ended: B waited for it
    assert all(e["args"]["jumped"] == 0 for e in
               _named(events, "generation.admit", ph="i")
               if e["args"]["prompt_len"] in (40, 50))
    eng._get("lm")._check_quiesce()


def test_a_partner_that_does_not_fit_is_cut_back_to_a_whole_batch(tight_lm):
    """Six requests of one rung, four blocks each, four slots free and
    twelve blocks: three fit, the fourth ends the search, and three would
    launch a (4, 16) program with an empty row while others wait. The
    pass takes two; the third goes back to its place with its blocks and
    heads the next pass, so the order of arrival holds."""
    net, spec, eng = tight_lm
    sizes = (16, 15, 14, 13, 12, 11)
    lengths = [26 - n for n in sizes]
    prompts = _prompts(47, sizes, seed=4347)
    refs = [naive_generate(net, p, n, pad_to=64, spec=spec)
            for p, n in zip(prompts, lengths)]
    with _Prefills(eng) as rec:
        outs, events = _serve_at_once(eng, prompts, lengths)
    assert outs == refs
    assert rec.seen[0]["lens"] == [16, 15] and rec.seen[0]["P"] == 2
    assert [n for launch in rec.seen for n in launch["lens"]] == list(sizes)
    assert all(len(launch["lens"]) == launch["P"] for launch in rec.seen)
    admits = _named(events, "generation.admit", ph="i")
    assert [e["args"]["prompt_len"] for e in admits] == list(sizes)
    assert all(e["args"]["jumped"] == 0 for e in admits)
    rt = eng._get("lm")
    rt._check_quiesce()
    assert len(rt._slots_free) == 4 and eng.models()["lm"]["in_flight"] == 0


def test_a_speculating_cohort_drafts_the_candidates_the_pass_chose():
    """The draft's prefill takes the requests the admission pass chose:
    the same rows in the same (P, L) as the target's prefill of that pass,
    whatever the order the queue was admitted in, and speculation over
    the grouped order still emits the full recompute's tokens."""
    net = _lm()
    eng = GenerationEngine(net, model_name="lm", block_len=8, max_seq_len=64,
                           decode_slots=2, prefill_batches=(1, 2),
                           prompt_rungs=(16, 64), prefix_cache=False,
                           draft=truncated_draft(net, 1), spec_k=2)
    try:
        spec = TransformerDecodeSpec(net)
        sizes, lengths = (5, 30, 9, 41, 12, 22), (6, 3, 5, 4, 7, 2)
        prompts = _prompts(53, sizes, seed=4348)
        refs = [naive_generate(net, p, n, pad_to=64, spec=spec)
                for p, n in zip(prompts, lengths)]
        with _Prefills(eng) as rec:
            outs, events = _serve_at_once(eng, prompts, lengths)
        assert outs == refs
        assert rec.seen[0]["lens"] == [5, 9]         # grouped by rung
        assert [{k: launch[k] for k in ("P", "L", "lens", "slots")}
                for launch in rec.seen] == rec.draft
        assert any(e["args"]["jumped"] for e in
                   _named(events, "generation.admit", ph="i"))
        assert eng.metrics()["lm"]["speculative"]["verify_steps"] > 0
    finally:
        eng.stop()


# ------------- a launch takes ONE host array (ISSUE 45): every per-row
# argument of a decode step or a prefill rides one packed int32 array, a
# transfer a launch, and the program unpacks it as its first act
def _decode_rows(S=5, mb=3):
    rng = np.random.default_rng(4500)
    return (rng.integers(1, 50000, S).astype(np.int32),          # tokens
            np.asarray([True, False, True, False, False]),       # host_known
            rng.integers(0, 1000, S).astype(np.int32),           # pos
            rng.integers(1, 999, (S, mb)).astype(np.int32),      # tables
            np.asarray([False, True, True, False, True]),        # active
            np.asarray([0.0, 0.7, 1.3, 1e-3, 2.5], np.float32),  # temp
            np.asarray([0, 40, 1, 0, 50256], np.int32))          # topk


def _same_to_the_bit(got, want):
    got, want = np.asarray(got), np.asarray(want)
    assert got.dtype == want.dtype and got.shape == want.shape
    assert got.tobytes() == want.tobytes()


@pytest.mark.parametrize("where", ["host", "program"])
def test_decode_pack_unpack_round_trip(where):
    """Temperatures no integer holds, both masks and every slot's table
    row come back as they went in, on the host and out of a program."""
    import jax
    rows = _decode_rows()
    packed = pack_decode(*rows)
    assert packed.dtype == np.int32 and packed.shape == (5, 6 + 3)
    back = unpack_decode(packed) if where == "host" \
        else jax.jit(unpack_decode)(packed)
    for got, want in zip(back, rows):
        _same_to_the_bit(got, want)
    # a fresh array: writing what was packed does not reach it
    rows[0][:] = 0
    rows[3][:] = 0
    assert unpack_decode(packed)[0].all() and unpack_decode(packed)[3].all()


@pytest.mark.parametrize("where", ["host", "program"])
def test_prefill_pack_unpack_round_trip(where):
    """Prompts shorter than the rung beside one that fills it and a
    padding row: tokens, lengths, table rows, slots and a sampled row's
    temperature and top-k come back as they went in."""
    import jax
    P, L, mb, S = 4, 16, 2, 7
    rng = np.random.default_rng(4501)
    tokens = np.zeros((P, L), np.int32)
    lengths = np.asarray([3, 16, 9, 1], np.int32)
    for i, n in enumerate(lengths[:3]):
        tokens[i, :n] = rng.integers(1, 50000, n)
    tables = np.asarray([[4, 0], [9, 2], [5, 6], [0, 0]], np.int32)
    slots = np.asarray([2, 0, 5, S], np.int32)
    temp = np.asarray([0.0, 0.7, 1.3, 0.0], np.float32)
    topk = np.asarray([0, 40, 3, 0], np.int32)
    rows = (tokens, lengths, tables, slots, temp, topk)
    packed = pack_prefill(*rows)
    assert packed.dtype == np.int32 and packed.shape == (P, 4 + mb + L)
    back = unpack_prefill(packed, mb) if where == "host" \
        else jax.jit(unpack_prefill, static_argnums=1)(packed, mb)
    for got, want in zip(back, rows):
        _same_to_the_bit(got, want)
    # the scheduler's way in: a fresh array of padding rows, filled a row
    # a prompt through the host's views, is the same array
    filled = padding_prefill(P, L, mb, S)
    views = unpack_prefill(filled, mb)
    assert int(views[1][3]) == 1 and int(views[3][3]) == S
    for i in range(3):
        for view, a in zip(views, rows):
            view[i] = a[i]
    assert filled.tobytes() == packed.tobytes()


class _Launches:
    """Every call of ``programs._launch`` while the block runs: the
    program, how many numpy arrays the executable was handed and their
    bytes, and the arrays themselves."""

    def __init__(self, monkeypatch):
        from deeplearning4j_tpu.serving.generation import programs
        self.seen = []
        orig = programs._launch

        def launch(program, exe, *args, **kw):
            import jax
            arrays = [a for a in jax.tree.leaves(args)
                      if isinstance(a, np.ndarray)]
            self.seen.append((program, arrays, args))
            return orig(program, exe, *args, **kw)

        monkeypatch.setattr(programs, "_launch", launch)

    def of(self, program):
        return [(arrays, args) for name, arrays, args in self.seen
                if name == program]


@pytest.mark.parametrize("sizes,program", [
    ((7,), (1, 16)), ((5, 9), (2, 16)), ((5, 9, 12), (4, 16)),
    ((30,), (1, 64)), ((30, 5), (2, 64)), ((5, 30, 9), (4, 64))])
def test_a_launch_hands_the_executable_one_host_array(admit_lm, monkeypatch,
                                                      sizes, program):
    """Every warmed (P, L) prefill and every decode step behind it, the
    first after the idle period included (its ``prev`` is a device array
    too): ONE numpy array a call, of the packed shape, and the tokens are
    the full recompute's."""
    net, spec, eng = admit_lm
    cfg = eng._get("lm").config
    S, mb = cfg.decode_slots, cfg.blocks_per_seq
    prompts = _prompts(47, sizes, seed=4502)
    refs = [naive_generate(net, p, 4, pad_to=64, spec=spec) for p in prompts]
    rec = _Launches(monkeypatch)
    outs, _ = _serve_at_once(eng, prompts, [4] * len(sizes))
    assert outs == refs
    P, L = program
    ((arrays, _),) = rec.of("prefill")
    assert [a.shape for a in arrays] == [(P, 4 + mb + L)]
    steps = rec.of("decode")
    assert len(steps) == 3
    for arrays, args in steps:
        assert [(a.shape, a.dtype) for a in arrays] == [
            ((S, 6 + mb), np.dtype(np.int32))]
        assert len(args) == 6            # params, state, cache, packed, prev, key


def test_a_step_does_not_see_what_the_loop_writes_after_its_launch(admit_lm):
    """``launch_decode`` may read its host array after it returns and the
    loop writes its own arrays at once (positions, replayed tokens, the
    next admission's table): the packed array is fresh at every launch
    and shares no memory with the loop's arrays; and at the program set,
    a step whose sources are overwritten the moment the launch returns
    samples what the untouched step samples."""
    net, spec, eng = admit_lm
    rt = eng._get("lm")
    ps = rt.active_ps
    launch, seen = ps.launch_decode, []

    def spy(cache, packed, *rest):
        out = launch(cache, packed, *rest)
        coh = rt._cohorts[-1]
        for own in (rt._tokens, rt._host_known, rt._pos, rt._temp, rt._topk,
                    rt._active, coh.tables):
            assert not np.shares_memory(packed, own)
        seen.append(packed)
        return out

    prompts = _prompts(47, (5, 30, 9), seed=4503)
    refs = [naive_generate(net, p, 6, pad_to=64, spec=spec) for p in prompts]
    ps.launch_decode = spy
    try:
        outs, _ = _serve_at_once(eng, prompts, [6] * 3)
    finally:
        ps.launch_decode = launch
    assert outs == refs
    assert len(seen) == 5 and len({id(p) for p in seen}) == 5

    cfg = rt.config
    S, mb = cfg.decode_slots, cfg.blocks_per_seq
    tokens = np.zeros((1, 16), np.int32)
    tokens[0, :5] = prompts[0]
    table = np.zeros((1, mb), np.int32)
    table[0, :2] = [1, 2]
    results = []
    for scribble in (False, True):
        first, cache, key = ps.run_prefill(
            ps.make_cache(),
            pack_prefill(tokens, np.asarray([5], np.int32), table,
                         np.asarray([0], np.int32), np.zeros(1, np.float32),
                         np.zeros(1, np.int32)), ps.fresh_key())
        rows = [np.zeros(S, np.int32), np.ones(S, np.bool_),
                np.zeros(S, np.int32), np.zeros((S, mb), np.int32),
                np.zeros(S, np.bool_), np.zeros(S, np.float32),
                np.zeros(S, np.int32)]
        rows[0][0], rows[2][0], rows[4][0] = first[0], 5, True
        rows[3][0] = table[0]
        nxt, cache, key = ps.launch_decode(cache, pack_decode(*rows), None,
                                           key)
        if scribble:
            for a in rows:
                a[...] = 1
        results.append(int(ps.read_decode(nxt)[0]))
    assert results[0] == results[1] == refs[0][1]


def test_the_dispatch_span_says_what_came_from_the_host(admit_lm):
    """``generation.dispatch`` carries ``host_args`` = 1 and the packed
    array's bytes, for the prefill's launch and for every decode step's."""
    net, _, eng = admit_lm
    cfg = eng._get("lm").config
    S, mb = cfg.decode_slots, cfg.blocks_per_seq
    _, events = _serve_at_once(eng, _prompts(47, (30, 5), seed=4504), [4, 4])
    spans = _named(events, "generation.dispatch", ph="X", cat="span")
    by_program = {}
    for e in spans:
        by_program.setdefault(e["args"]["program"], []).append(e["args"])
    assert sorted(by_program) == ["decode", "prefill"]
    assert [(a["host_args"], a["host_bytes"])
            for a in by_program["prefill"]] == [(1, 2 * (4 + mb + 64) * 4)]
    assert len(by_program["decode"]) == 3
    assert all((a["host_args"], a["host_bytes"]) == (1, S * (6 + mb) * 4)
               for a in by_program["decode"])
    # counted once an executable, at warm()'s touch, and shared by a
    # hot-swapped set
    ps = eng._get("lm").active_ps
    assert ps.host_args[("decode",)] == (1, S * (6 + mb) * 4)
    assert set(ps.host_args) == set(ps._compiled) - {("cow",)}
