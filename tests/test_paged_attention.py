"""The paged decode-attention kernel (ops/pallas_paged_attention.py), in the
CPU interpreter, against the plain reference: gather every slot's whole
table (``paged_attention_reference``: what the decode step did before the
kernel) and attend under a mask.

One batch mixes what the serving loop can hand the kernel: the first
position, a page boundary from both sides, mid-table and the table's last
position, an idle slot, a slot whose table shares pages with another (the
prefix cache's copy-on-write case), and a trash block 0 full of NaN that
nothing may read.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.ops.pallas_paged_attention import (
    paged_attention_decode, paged_attention_reference)
from deeplearning4j_tpu.serving.generation.kvcache import (PagedStore,
                                                           PagedWindowStore,
                                                           make_pools)

BLK, MB = 16, 64                       # the serving cells' table: 1024 keys
CAP = BLK * MB
H, DH, LAYERS, LAYER = 4, 16, 3, 1
# the last row's position per slot, over a 64-block table
POS = (0, 15, 16, 17, 511, 1023)
IDLE, SHARER, SHARED_WITH = 6, 7, 4    # slot 7 shares slot 4's first pages


def _batch(dtype, W, seed=0):
    """(q, k_pool, v_pool, tables, lens, clean pools) with S = 8 slots:
    six at ``POS`` (row 0 at ``pos - W + 1`` or 0), one idle, one sharing
    20 full pages with slot 4 and then its own."""
    rng = np.random.default_rng(seed)
    S = len(POS) + 2
    first = [max(p - (W - 1), 0) for p in POS]         # row 0's position
    lens = np.array([p + 1 for p in first] + [0, 400], np.int32)
    nb = S * MB + 1
    pools = [rng.standard_normal((LAYERS, nb, BLK, H * DH)).astype(np.float32)
             for _ in range(2)]
    free = list(1 + rng.permutation(nb - 1))
    tables = np.zeros((S, MB), np.int32)               # unused entries: trash
    for s in range(S):
        if s == IDLE:
            continue
        n = -(-min(int(lens[s]) + W - 1, CAP) // BLK)
        tables[s, :n] = [free.pop() for _ in range(n)]
    tables[SHARER, :20] = tables[SHARED_WITH, :20]
    q = rng.standard_normal((S, H, W, DH)).astype(np.float32)
    clean = [jnp.asarray(p, dtype) for p in pools]
    dirty = []
    for p in pools:
        p = p.copy()               # jnp.asarray may alias a float32 array
        p[:, 0] = np.nan                               # the trash block
        dirty.append(jnp.asarray(p, dtype))
    return (jnp.asarray(q, dtype), *dirty, jnp.asarray(tables),
            jnp.asarray(lens), clean)


@pytest.mark.parametrize("W", [1, 5])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-6),
                                       (jnp.bfloat16, 1.6e-2)])
def test_kernel_matches_gather_and_mask(dtype, tol, W):
    """bfloat16's tolerance is two of its steps at the outputs' size (the
    reference rounds its scores and probabilities to bfloat16, the kernel
    keeps both in float32); float32's is reordered additions."""
    q, k_pool, v_pool, tables, lens, clean = _batch(dtype, W)
    got = jax.jit(paged_attention_decode, static_argnums=3)(
        q, k_pool, v_pool, LAYER, tables, lens)
    # the reference multiplies every masked key by 0, trash included, so
    # it reads the pools with a finite trash block
    want = paged_attention_reference(q, *clean, LAYER, tables, lens)
    assert got.shape == want.shape == q.shape and got.dtype == dtype
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.isfinite(got).all(), "the trash block reached an output"
    assert np.all(got[IDLE] == 0.0)
    live = [s for s in range(len(got)) if s != IDLE]
    assert np.abs(want[live]).max() > 0.1
    np.testing.assert_allclose(got[live], want[live], atol=tol, rtol=0)


def test_a_row_sees_exactly_the_keys_up_to_its_position():
    """Keys past a row's position change nothing, the key AT it does:
    float32, W = 3, the slot whose window straddles a page boundary."""
    q, k_pool, v_pool, tables, lens, _ = _batch(jnp.float32, 3)
    run = jax.jit(paged_attention_decode, static_argnums=3)
    base = np.asarray(run(q, k_pool, v_pool, LAYER, tables, lens))
    s = POS.index(17)                  # rows at positions 15, 16, 17
    page1 = int(tables[s, 1])

    def poke(offset):
        k = k_pool.at[LAYER, page1, offset].add(3.0)
        return np.asarray(run(q, k, v_pool, LAYER, tables, lens))
    after = poke(2)                    # position 18: beyond every row
    assert np.array_equal(after, base)
    after = poke(1)                    # position 17: the last row's own key
    assert np.array_equal(after[s, :, :2], base[s, :, :2])
    assert not np.array_equal(after[s, :, 2], base[s, :, 2])
    other = [i for i in range(len(base)) if i != s]
    assert np.array_equal(after[other], base[other])


@pytest.mark.parametrize("W", [1, 4])
def test_store_writes_then_attends_in_place(W):
    """The stores' contract: the window's K/V is in the pool before the
    kernel reads it, an idle slot writes to trash and gets zeros, and a
    window of W rows is W one-token steps' arithmetic row for row."""
    rng = np.random.default_rng(3)
    S, blk, mb = 3, 8, 4
    k_pool, v_pool = make_pools(2, S * mb + 1, blk, H, DH, jnp.float32)
    tables = jnp.asarray(1 + np.arange(S * mb).reshape(S, mb), jnp.int32)
    pos = jnp.asarray([0, 6, 13], jnp.int32)     # 6: the window crosses a page
    active = jnp.asarray([True, True, False])
    hist = [jnp.asarray(rng.standard_normal((S, 16, H, DH)), jnp.float32)
            for _ in range(2)]
    # positions 0..15 of every slot hold history
    k_pool, v_pool = (p.at[0, tables[:, :2]].set(
        h.reshape(S, 2, blk, H * DH)) for p, h in zip((k_pool, v_pool), hist))
    q = jnp.asarray(rng.standard_normal((S, H, W, DH)), jnp.float32)
    k_win, v_win = (jnp.asarray(rng.standard_normal((S, W, H, DH)),
                                jnp.float32) for _ in range(2))
    store = PagedWindowStore(k_pool, v_pool, tables, pos, active, blk, W)
    out = np.asarray(store.attend(0, q, k_win, v_win))
    assert np.all(out[2] == 0.0)
    # one token at a time through the one-token store, same pools
    steps = []
    pools = (k_pool, v_pool)
    for w in range(W):
        one = PagedStore(*pools, tables, pos + w, active, blk)
        steps.append(np.asarray(one.attend(0, q[:, :, w:w + 1], k_win[:, w],
                                           v_win[:, w])))
        pools = one.pools
    assert np.array_equal(out, np.concatenate(steps, axis=2))
    for got, want in zip(store.pools, pools):
        assert np.array_equal(np.asarray(got[0, 1:]), np.asarray(want[0, 1:]))
    # and the written keys are the ones attended to: dense softmax by hand
    s, w = 1, W - 1
    n = int(pos[s]) + w + 1
    keys = np.concatenate([np.asarray(hist[0][s, :int(pos[s])]),
                           np.asarray(k_win[s, :w + 1])])[:n]
    vals = np.concatenate([np.asarray(hist[1][s, :int(pos[s])]),
                           np.asarray(v_win[s, :w + 1])])[:n]
    sc = np.einsum("hd,thd->ht", np.asarray(q[s, :, w]), keys) / np.sqrt(DH)
    p = np.exp(sc - sc.max(-1, keepdims=True))
    want = np.einsum("ht,thd->hd", p / p.sum(-1, keepdims=True), vals)
    np.testing.assert_allclose(out[s, :, w], want, atol=1e-5, rtol=0)


def test_int8_tier_keeps_the_gather_and_says_so_in_the_span():
    """``gathered_tokens`` on the decode step's span is what the step
    reads per layer: live pages through the kernel, every slot's whole
    table where the int8 tier still gathers."""
    from deeplearning4j_tpu.models.zoo_extra import transformer_lm
    from deeplearning4j_tpu.serving import GenerationEngine
    from deeplearning4j_tpu.telemetry import get_registry
    net = transformer_lm(vocab_size=37, d_model=16, n_heads=2, n_blocks=1,
                         max_length=32, seed=3, dtype="float32",
                         token_input=True).init()
    for kv, want in ((None, [8, 8]), ("int8", [64, 64])):
        eng = GenerationEngine(net, model_name=f"lm-{kv}", block_len=8,
                               max_seq_len=32, decode_slots=2,
                               prefill_batches=(1,), prompt_rungs=(32,),
                               kv_cache_dtype=kv)
        try:
            seq0 = get_registry().last_seq
            eng.generate([1, 2, 3, 4, 5], max_tokens=3)
            steps = [e for e in get_registry().trace_events_since(seq0)
                     if e["name"] == "generation.decode_step"
                     and e.get("ph") == "X"]
            assert [e["args"]["live_tokens"] for e in steps] == [6, 7]
            assert [e["args"]["gathered_tokens"] for e in steps] == want
        finally:
            eng.stop()
