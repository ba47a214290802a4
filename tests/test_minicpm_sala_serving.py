"""The MiniCPM-SALA family (``model_type: minicpm_sala``: block-sparse
attention layers that choose the key blocks a query reads, beside lightning
linear-attention layers with a float32 matrix state a head) served through
``ComputationGraph``, ``GraphDecodeSpec`` and ``GenerationEngine`` (ISSUE
46), at a toy size in float32 on the CPU whose contexts pass the toy
selection's budget, against the benchmark's plain reference
(``benchmarks/families/minicpm_sala/reference.py``); the four kernels in
the interpreter against their XLA paths; the compressed keys; the
refusals; the spans and the new readers; the cell's rehearsal."""
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.families.minicpm_sala import (build, flops,  # noqa: E402
                                              kernel_costs, reference,
                                              weights)
from deeplearning4j_tpu.models.decode import (  # noqa: E402
    GraphDecodeSpec, SparseDecodeUnsupportedError,
    StatefulDecodeUnsupportedError)
from deeplearning4j_tpu.nn.layers import (LightningAttentionLayer,  # noqa: E402
                                          SelfAttentionLayer)
from deeplearning4j_tpu.ops import pallas_attention  # noqa: E402
from deeplearning4j_tpu.ops import pallas_linear_attention as la  # noqa: E402
from deeplearning4j_tpu.ops import pallas_paged_attention as paged  # noqa: E402
from deeplearning4j_tpu.ops import sparse_select as ss  # noqa: E402
from deeplearning4j_tpu.serving import GenerationEngine  # noqa: E402
from deeplearning4j_tpu.serving.generation.kvcache import (  # noqa: E402
    PagedStore, compressed_prefill_fill, make_compressed, make_pools)
from deeplearning4j_tpu.serving.generation.programs import (  # noqa: E402
    GenerationConfig, GenerationProgramSet)
from deeplearning4j_tpu.serving.generation.scheduler import (  # noqa: E402
    selected_keys, selection_rows)

with open(os.path.join(ROOT, "benchmarks", "configs", "minicpm-sala",
                       "config.json")) as _f:
    PUBLISHED = json.load(_f)

BLK, CAP = 8, 96
# the published ratios kept (kernel = 2 x stride, block = 4 x stride), the
# budget cut so that contexts of 64-96 lie well past it: 4 blocks of 8 in
# all, 1 initial and 2 local among them, dense below 24
SEL = dict(block=BLK, kernel=4, stride=2, topk=4, init_blocks=1,
           local_blocks=2, dense_len=24)
TOY = dict(
    PUBLISHED, hidden_size=128, intermediate_size=256, head_dim=32,
    num_attention_heads=8, num_key_value_heads=2, lightning_nh=4,
    lightning_nkv=4, lightning_head_dim=32, vocab_size=256,
    dim_model_base=32, num_hidden_layers=4,
    mixer_types=["minicpm4", "lightning-attn", "lightning-attn", "minicpm4"],
    served_context=CAP, assumed=dict(PUBLISHED["assumed"], sparse_config=SEL),
    precision={"serve": {"dtype": "float32", "compute_dtype": None},
               "train": {"dtype": "float32", "compute_dtype": None}})
SELECTION = ss.Selection.of(SEL)


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
                               prompt_rungs=(32, 64), prefill_batches=(1, 2))
    yield eng
    eng.stop()


def _prompts(seed, lengths):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, TOY["vocab_size"], n).astype(np.int32)
            for n in lengths]


def _layer_params(w, name):
    return {k.split("/")[1]: a for k, a in w.items()
            if k.startswith(name + "/")}


def _normed(seed, T, d=128):
    x = jax.random.normal(jax.random.PRNGKey(seed), (T, d), jnp.float32)
    return x, x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True)
                           + TOY["rms_norm_eps"])


def _unit_gain(w, i):
    return dict(w, **{f"l{i}_norm1/gain": jnp.ones((128,))})


# ------------------------------------------------------------ the forward
def test_the_configuration_is_the_published_one_cut_by_depth():
    """Every width as published; ``reduced`` names the two keys cut, with
    the published values beside them; the stage holds the two kinds of
    mixer in the model's own 1 : 3."""
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(json.loads(line) for line in f
                   if '"name": "MiniCPM-SALA"' in line)
    assert PUBLISHED["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in PUBLISHED["reduced"]:
            assert PUBLISHED[key] == value, key
    assert PUBLISHED["reduced"] == ["num_hidden_layers", "mixer_types"]
    assert PUBLISHED["published"]["num_hidden_layers"] == 32
    assert PUBLISHED["published"]["mixer_types"] == \
        row["config"]["mixer_types"]
    assert PUBLISHED["mixer_types"] == row["config"]["mixer_types"][9:17] \
        == ["minicpm4"] + ["lightning-attn"] * 6 + ["minicpm4"]
    assert PUBLISHED["num_hidden_layers"] == 8
    import math
    n = sum(math.prod(s) for s in weights.shapes(PUBLISHED).values())
    assert n == 2_820_642_536            # 5.64 GB in bfloat16


def test_the_graph_is_the_reference_past_the_selections_budget(toy):
    """``net.output`` over a sequence of 80 (the selection reads 4 blocks
    of 8 from position 32 on) against the reference's full forward: logits
    through the softmax, every position."""
    net, w = toy
    ids = _prompts(0, [80])[0]
    probs = np.asarray(net.output(ids[None]))[0]
    want = np.asarray(jax.nn.softmax(reference.forward(w, TOY, ids), -1))
    np.testing.assert_allclose(probs, want, rtol=2e-4, atol=1e-7)


@pytest.mark.parametrize("key,value", [("scale_depth", 32 ** 0.5),
                                       ("scale_emb", 1.0),
                                       ("dim_model_base", 128)])
def test_each_scaling_of_the_model_is_in_the_graph(toy, key, value):
    """``scale_depth / sqrt(32)`` on every mixer and MLP, ``scale_emb`` on
    the embedding and ``/ (hidden_size / dim_model_base)`` before the head:
    a reference with any one of them taken out (set so that it multiplies
    by 1) no longer agrees with the graph."""
    net, w = toy
    ids = _prompts(1, [40])[0]
    got = np.asarray(net.output(ids[None]))[0]
    want = np.asarray(jax.nn.softmax(reference.forward(
        w, dict(TOY, **{key: value}), ids), -1))
    assert np.abs(got - want).max() > 1e-3
    assert build.residual_scale(TOY) == pytest.approx(1.4 / 32 ** 0.5)


# ------------------------------------------------------- lightning layers
def test_the_lightning_layer_is_the_references_mixer_in_all_its_forms(toy):
    """``LightningAttentionLayer.apply`` (the chunked form) against the
    reference's mixer (the recurrence, row by row) of the same weights;
    the chunked and the recurrent XLA forms against each other; the final
    state float32 whatever the model's dtype."""
    net, w = toy
    layer = build.mixer(TOY, 1)
    layer.n_in = 128
    p = _layer_params(w, "l1_mixer")
    assert set(p) == set(layer.init(jax.random.PRNGKey(0), None,
                                    jnp.float32)[0])
    x, u = _normed(3, 70)
    got, S = layer.apply_with_final_state(p, {}, u[None])
    want = (reference.mixer(x, _unit_gain(w, 1), TOY, 1)[0] - x) \
        / build.residual_scale(TOY)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               rtol=2e-4, atol=2e-5)
    assert S.dtype == jnp.float32 and S.shape == (1, 4, 32, 32)
    assert layer.zero_state(5, jnp.bfloat16).dtype == jnp.float32
    q, k, v = (a.transpose(0, 2, 1, 3) for a in layer.project_qkv(p, u[None]))
    for chunk in (16, 70, 256):
        o1, S1 = la.lightning_attention_xla(q, k, v, layer.slopes(),
                                            scale=layer.scale, chunk=chunk)
        o2, S2 = la.lightning_recurrent(q, k, v, layer.slopes(),
                                        scale=layer.scale)
        np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(S1), np.asarray(S2),
                                   rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(S), np.asarray(S2), rtol=1e-4,
                               atol=1e-5)


def test_state_at_reads_a_padded_prompts_true_end(toy):
    """``state_at`` at lengths inside a padded batch equals the final
    state of each sequence run alone to its true length; a continuation
    from that state equals the whole sequence's tail."""
    _, w = toy
    layer = build.mixer(TOY, 2)
    layer.n_in = 128
    p = _layer_params(w, "l2_mixer")
    u = jnp.stack([_normed(5, 64)[1], _normed(6, 64)[1]])
    lengths = jnp.asarray([64, 37])
    got = layer.state_at(p, u, lengths)
    assert got.dtype == jnp.float32
    for b, n in enumerate((64, 37)):
        _, want = layer.apply_with_final_state(p, {}, u[b:b + 1, :n])
        np.testing.assert_allclose(np.asarray(got[b]), np.asarray(want[0]),
                                   rtol=1e-4, atol=1e-5)
    whole, _ = layer.apply_with_final_state(p, {}, u[1:2])
    # the rotation counts positions from the sequence's start: continue
    # through the decode step, which takes them
    pool = jnp.zeros((1, 2, 4, 32, 32), jnp.float32).at[0, 0].set(got[1])
    out, pool = layer.decode_step(p, u[1:2, 37:38], jnp.asarray([[37]]), pool,
                                  0, jnp.asarray([True]))
    np.testing.assert_allclose(np.asarray(out[0, 0]), np.asarray(whole[0, 37]),
                               rtol=2e-4, atol=2e-5)
    same, kept = layer.decode_step(p, u[1:2, 38:39], jnp.asarray([[38]]),
                                   pool, 0, jnp.asarray([False]))
    np.testing.assert_array_equal(np.asarray(kept), np.asarray(pool))


# ----------------------------------------------------------- sparse layers
def _sparse_layer(w, i=0):
    layer = build.mixer(TOY, i)
    layer.n_in = 128
    return layer, _layer_params(w, f"l{i}_mixer")


def test_the_sparse_layer_chooses_the_references_blocks_and_gives_its_output(
        toy):
    """One sparse ``SelfAttentionLayer.apply`` over 96 positions against
    the reference's mixer: the OUTPUT, and the blocks every position and
    group chose (``chosen_mask`` against the reference's ``chosen``):
    per query position, 4 in all past the budget, the first and the two
    nearest always among them."""
    _, w = toy
    layer, p = _sparse_layer(w)
    assert set(p) == set(layer.init(jax.random.PRNGKey(0), None,
                                    jnp.float32)[0])
    x, u = _normed(4, 256)
    x, u = x[:96], u[:96]
    xp = jnp.pad(x, ((0, 160), (0, 0)))
    y, want_chosen, margin = reference.mixer(xp, _unit_gain(w, 0), TOY, 0)
    want = (y[:96] - x) / build.residual_scale(TOY)
    got, _ = layer.apply(p, {}, u[None])
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               rtol=2e-4, atol=2e-5)
    q, k, _ = layer.project_qkv(p, u[None])
    chosen = np.asarray(ss.chosen_mask(
        q, ss.compress_keys(k, SELECTION), SELECTION, 32 ** -0.5))[0] > 0
    ref = np.asarray(want_chosen)[:96, :, :12].transpose(1, 2, 0)
    np.testing.assert_array_equal(chosen, ref)       # [group, block, t]
    counts = chosen.sum(axis=1)                      # [group, t]
    t = np.arange(96)
    np.testing.assert_array_equal(
        counts[0], np.where(t < 32, t // BLK + 1, 4))
    assert chosen[:, 0].all()                        # the initial block
    for tt in (40, 77, 95):
        assert chosen[:, tt // BLK, tt].all() and \
            chosen[:, tt // BLK - 1, tt].all()       # the two nearest
    # the two groups choose for themselves, and neighbours differ: the
    # selection is per query position, not per tile of queries
    assert (chosen[0] != chosen[1]).any()
    assert any((chosen[0, :, tt] != chosen[0, :, tt + 1]).any()
               for tt in range(40, 47))
    assert np.isfinite(np.asarray(margin)[40:96]).all()
    assert np.isinf(np.asarray(margin)[:24]).all()   # dense: no choice


def test_below_the_threshold_the_sparse_layer_is_plain_attention(toy):
    """A sequence no longer than ``dense_len`` runs the plain attention
    path: the same numbers as the layer without a selection, to the bit."""
    _, w = toy
    layer, p = _sparse_layer(w)
    plain = SelfAttentionLayer(**{
        **{f: getattr(layer, f) for f in (
            "n_in", "n_out", "n_heads", "n_kv_heads", "head_size", "causal",
            "bias", "qk_norm", "qk_norm_eps", "out_gate")}, "sparse": None})
    _, u = _normed(8, 24)
    a, _ = layer.apply(p, {}, u[None])
    b, _ = plain.apply(p, {}, u[None])
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # and past it, the first 24 rows (every block read) still agree
    _, u = _normed(8, 64)
    a, _ = layer.apply(p, {}, u[None])
    b, _ = plain.apply(p, {}, u[None])
    np.testing.assert_allclose(np.asarray(a[0, :32]), np.asarray(b[0, :32]),
                               rtol=1e-5, atol=1e-6)
    assert np.abs(np.asarray(a[0, 40:]) - np.asarray(b[0, 40:])).max() > 1e-4


def test_lists_and_mask_are_one_choice_and_ties_go_to_the_earlier_block():
    """``chosen_lists`` (a decode step's form) and ``choose_blocks`` (a
    prefill's) name the same blocks at every position; a window that
    straddles two blocks gives both its score, and the earlier is taken."""
    rng = jax.random.split(jax.random.PRNGKey(2), 2)
    q = jax.random.normal(rng[0], (1, 96, 8, 32))
    k = jax.random.normal(rng[1], (1, 96, 2, 32))
    c = ss.compress_keys(k, SELECTION)
    t = jnp.arange(96)[None]
    R = ss.block_scores(q, c, t, SELECTION, 32 ** -0.5)
    mask = np.asarray(ss.choose_blocks(R, t, SELECTION))[0]
    blocks, counts = (np.asarray(a)[0] for a in ss.chosen_lists(
        R, t, SELECTION))
    assert blocks.shape[-1] == SELECTION.list_len(12) == 4
    for tt in range(96):
        for g in range(2):
            listed = blocks[tt, g, :counts[tt, g]]
            assert list(listed) == sorted(listed) and \
                listed[-1] == tt // BLK          # its own block comes last
            np.testing.assert_array_equal(np.nonzero(mask[tt, g])[0], listed)
    tied = jnp.zeros((1, 1, 1, 12)).at[..., 3].set(0.5).at[..., 4].set(0.5)
    got = np.asarray(ss.choose_blocks(tied, jnp.asarray([[95]]),
                                      SELECTION))[0, 0, 0]
    assert list(np.nonzero(got)[0]) == [0, 3, 10, 11]


@pytest.mark.parametrize("sel,T", [
    (SELECTION, 96),
    (ss.Selection(dense_len=1024, topk=16, local_blocks=4), 4096)])
def test_the_choice_without_a_sort_is_the_sorted_one(sel, T):
    """``choose_blocks`` (the k-th largest score found bit by bit, the
    earliest of the blocks that equal it) against the ``lax.top_k`` choice
    kept here, on scores rounded so that ties are everywhere, and on real
    scores."""
    def sorted_choice(R, t):
        nb = R.shape[-1]
        score, tb = ss._ranked(R, t, sel)
        k = min(sel.topk, nb)
        idx = jax.lax.top_k(score, k)[1]     # the lower index first
        idx = jnp.where(jnp.arange(k) < jnp.minimum(k, tb + 1), idx, nb)
        listed = (idx[..., :, None] == jnp.arange(nb)).any(axis=-2)
        return (jnp.arange(nb) <= tb) & (listed | ss._dense(t, tb, sel))

    key = jax.random.PRNGKey(T)
    t = jnp.arange(T)[None]
    tied = jnp.round(jax.random.uniform(key, (1, T, 2, T // sel.block)) * 8) / 8
    q = jax.random.normal(key, (1, T, 8, 32))
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, T, 2, 32))
    real = ss.block_scores(q, ss.compress_keys(k, sel), t, sel, 32 ** -0.5)
    for R in (tied, real):
        np.testing.assert_array_equal(np.asarray(ss.choose_blocks(R, t, sel)),
                                      np.asarray(sorted_choice(R, t)))


def test_compressed_keys_appended_step_by_step_equal_pooling_the_whole_k():
    """A slot's compressed rows after a prefill of 21 positions and 43
    decode steps equal ``compress_keys`` over the 64 keys at every entry
    whose window is complete (mean of 4 rows every 2), and entries a
    prefill made from padding are written anew before any position sees
    them."""
    rng = np.random.default_rng(0)
    Hkv, Dh, S, T, n0 = 2, 32, 2, 64, 21
    k_all = jnp.asarray(rng.normal(size=(S, T, Hkv, Dh)), jnp.float32)
    v_all = jnp.asarray(rng.normal(size=(S, T, Hkv, Dh)), jnp.float32)
    q_all = jnp.asarray(rng.normal(size=(S, T, 8, Dh)), jnp.float32)
    k_pool, v_pool = make_pools(1, 1 + S * 12, BLK, Hkv, Dh, jnp.float32)
    tables = jnp.asarray(1 + np.arange(S * 12).reshape(S, 12), jnp.int32)
    comp = make_compressed(1, S, CAP, 2, Hkv, Dh, jnp.float32)
    # the prefill: 21 live rows of a rung of 32, the rest padding
    padded = k_all[:, :32].at[:, n0:].set(99.0)
    rows = ss.compress_keys(padded, SELECTION).reshape(S, 16, Hkv * Dh)
    comp = compressed_prefill_fill(comp, [rows], jnp.arange(S))
    for s in range(S):
        for t in range(n0):
            k_pool = k_pool.at[0, tables[s, t // BLK], t % BLK].set(
                k_all[s, t].reshape(-1))
            v_pool = v_pool.at[0, tables[s, t // BLK], t % BLK].set(
                v_all[s, t].reshape(-1))
    outs = []
    for t in range(n0, T):
        store = PagedStore(k_pool, v_pool, tables, jnp.full((S,), t),
                           jnp.asarray([True, True]), BLK, comp=comp)
        outs.append(store.attend(0, q_all[:, t].reshape(S, 8, 1, Dh),
                                 k_all[:, t], v_all[:, t], select=SELECTION,
                                 select_index=0))
        k_pool, v_pool, comp = store.cache
    want = ss.compress_keys(k_all, SELECTION).reshape(S, 32, Hkv * Dh)
    complete = (T - SELECTION.kernel) // SELECTION.stride + 1
    np.testing.assert_allclose(np.asarray(comp[0, :S, :complete]),
                               np.asarray(want[:, :complete]), rtol=1e-6,
                               atol=1e-6)
    # and each step's output is the row a whole forward gives
    qt, kt, vt = (a.transpose(0, 2, 1, 3) for a in (q_all, k_all, v_all))
    chosen = ss.chosen_mask(q_all, ss.compress_keys(k_all, SELECTION),
                            SELECTION, Dh ** -0.5)
    whole = ss.sparse_attention_xla(qt, kt, vt, chosen, SELECTION,
                                    Dh ** -0.5)
    for t, o in zip(range(n0, T), outs):
        np.testing.assert_allclose(np.asarray(o[:, :, 0]),
                                   np.asarray(whole[:, :, t]), rtol=1e-4,
                                   atol=1e-5)


# ---------------------------------------------------------------- kernels
@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setenv("DL4J_TPU_FUSED_ATTN_INTERPRET", "1")


def test_flash_attention_sparse_fwd_is_the_xla_path(interpreted):
    """The sparse flash kernel in the interpreter against dense masked
    attention under the same lists, grouped heads read in place; the
    tile rule's new case counts the strips the lists reach."""
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    T, Dh = 512, 128
    sel = ss.Selection.of(dict(SEL, dense_len=100))
    q = jax.random.normal(ks[0], (1, T, 4, Dh))
    k = jax.random.normal(ks[1], (1, T, 2, Dh))
    v = jax.random.normal(ks[2], (1, T, 2, Dh))
    chosen = ss.chosen_mask(q, ss.compress_keys(k, sel), sel, Dh ** -0.5)
    qt, kt, vt = (a.transpose(0, 2, 1, 3) for a in (q, k, v))
    want = ss.sparse_attention_xla(qt, kt, vt, chosen, sel, Dh ** -0.5)
    got = pallas_attention.flash_attention_sparse(qt, kt, vt, chosen)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4,
                               atol=1e-5)
    one = np.asarray(chosen[0, 0]) > 0                   # [blocks, T]
    visited, masked, total = pallas_attention.tile_schedule(T, True,
                                                            chosen=one)
    causal = pallas_attention.tile_schedule(T, True)
    assert total == causal[2] and visited <= causal[0] and masked == visited
    # a selection that names the first block and the diagonal alone: the
    # tiles between are skipped
    thin = np.zeros_like(one)
    thin[0] = True
    thin[np.arange(T) // BLK, np.arange(T)] = True
    assert pallas_attention.tile_schedule(T, True, chosen=thin)[0] == 3
    assert pallas_attention.tile_kind(256, 256, 0, 256, True, None,
                                      (False, False)) == pallas_attention.SKIP


def test_paged_attention_sparse_decode_is_the_gather(interpreted):
    """The selected paged kernel in the interpreter against the gather of
    the listed pages: lists of different lengths a slot and group, an idle
    slot, the last page partly filled."""
    rng = np.random.default_rng(3)
    S, Hq, Hkv, Dh, nb, L = 4, 8, 2, 128, 60, 6
    k_pool = jnp.asarray(rng.normal(size=(2, nb, BLK, Hkv * Dh)), jnp.float32)
    v_pool = jnp.asarray(rng.normal(size=(2, nb, BLK, Hkv * Dh)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(S, Hq, 1, Dh)), jnp.float32)
    pages = jnp.asarray(rng.permutation(nb - 1)[:S * Hkv * L].reshape(
        S, Hkv, L) + 1, jnp.int32)
    counts = jnp.asarray([[6, 3], [1, 1], [4, 4], [2, 5]], jnp.int32)
    lens = jnp.asarray([45, 3, 0, 64], jnp.int32)
    want = paged.paged_attention_sparse_reference(q, k_pool, v_pool, 1,
                                                  pages, counts, lens)
    got = paged._one_device_sparse(jnp.int32(1), q, k_pool, v_pool, pages,
                                   counts, lens, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-6)
    assert not np.asarray(got[2]).any()              # the idle slot: zeros


def test_lightning_kernels_are_the_xla_forms():
    """``lightning_attention_fwd`` and ``lightning_decode`` in the
    interpreter against the chunked XLA form and the XLA step; idle slots
    and the other layers' states are left as they were."""
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    B, H, T, D = 2, 4, 2 * la.CHUNK, 128
    q, k, v = (jax.random.normal(kk, (B, H, T, D)) for kk in ks[:3])
    sl = la.slopes(H)
    got = la.lightning_attention_fwd(q, k, v, sl, scale=0.1, interpret=True)
    want, _ = la.lightning_attention_xla(q, k, v, sl, scale=0.1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4,
                               atol=1e-4)
    S = 3
    pool = jax.random.normal(ks[3], (2, S + 1, H, D, D))
    qd, kd, vd = (a[0, :, :S].transpose(1, 0, 2) for a in (q, k, v))
    active = jnp.asarray([True, False, True])
    oa, pa = la.lightning_decode_xla(qd, kd, vd, pool, 1, active, sl,
                                     scale=0.1)
    ob, pb = la.lightning_decode(qd, kd, vd, pool + 0, 1, active, sl,
                                 scale=0.1, interpret=True)
    np.testing.assert_allclose(np.asarray(ob), np.asarray(oa), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(pb), np.asarray(pa), rtol=1e-6,
                               atol=1e-6)
    np.testing.assert_array_equal(np.asarray(pb[0]), np.asarray(pool[0]))
    np.testing.assert_array_equal(np.asarray(pb[1, 1]),
                                  np.asarray(pool[1, 1]))


# ------------------------------------------------------- through the cache
def test_the_specification_groups_the_layers_by_what_they_keep(toy):
    net, _ = toy
    spec = GraphDecodeSpec(net)
    assert spec.full_names == spec.sparse_names == ["l0_mixer", "l3_mixer"]
    assert spec.recurrent_names == ["l1_mixer", "l2_mixer"]
    assert spec.window_names == [] and spec.selection == SELECTION
    assert spec.recurrent_state_specs(5) == [((2, 5, 4, 32, 32),
                                              jnp.dtype(jnp.float32))]
    assert spec.recurrent_state_shape(5) == (2, 5, 4, 32, 32)
    assert spec.final_name == "norm_f_s" and not spec.supports_head_sharding(2)


def test_prefill_then_decode_through_the_cache_is_the_reference(engine, toy):
    """Prompts of 45, 61 and 30 (past, past and under the selection's
    budget at their first sampled token), 30 tokens each through the
    engine's prefill and decode programs: every served token is the
    reference's argmax over the whole sequence, at a gap of 0."""
    _, w = toy
    for p in _prompts(2, [45, 61, 30]):
        toks, reason = engine.generate(p, max_tokens=30, stream=False)
        assert reason == "length" and len(toks) == 30
        seq = np.concatenate([p, np.asarray(toks, np.int32)])
        logits = np.asarray(reference.forward(w, TOY, seq))
        rows = np.arange(len(p) - 1, len(seq) - 1)
        gaps = logits[rows].max(-1) - logits[rows, np.asarray(toks)]
        assert gaps.max() < 1e-4, gaps.max()


def test_the_engine_says_what_the_model_keeps_and_its_spans_what_it_reads(
        engine):
    """The engine's record (the selection's sizes, the compressed keys'
    bytes a token, the states' bytes a slot, ``conv_state_bytes`` kept for
    the older readers), the prefix cache skipped and counted, and the
    counts on the two spans."""
    from deeplearning4j_tpu import telemetry
    info = engine.models()["lm"]
    assert info["sparse_layers"] == 2 and info["linear_layers"] == 2
    assert info["selection"] == SEL
    assert info["state_bytes_per_slot"] == 2 * 4 * 32 * 32 * 4
    assert info["conv_state_bytes"] == 4 * info["state_bytes_per_slot"]
    assert info["index_bytes_per_token"] == 2 * 2 * 32 * 4 / 2
    assert info["prefix_cache"] is False
    reg = telemetry.get_registry()
    seq0 = reg.last_seq
    before = engine.metrics()["lm"]["prefix"]["skipped_stateful"]
    p = _prompts(5, [50])[0]
    engine.generate(p, max_tokens=4, stream=False)
    assert engine.metrics()["lm"]["prefix"]["skipped_stateful"] == before + 1
    ev = [e for e in reg.trace_events_since(seq0) if e.get("ph") == "X"]
    pre = [e for e in ev if e["name"] == "generation.prefill"][-1]["args"]
    t = np.arange(50)
    assert pre["attn_selected_key_rows"] == int(np.where(
        t < 32, t + 1, 3 * BLK + t % BLK + 1).sum())
    assert pre["attn_index_rows"] == int(np.maximum((t - 3) // 2 + 1, 0).sum())
    assert pre["linear_rows"] == 50
    step = [e for e in ev if e["name"] == "generation.decode_step"][-1]["args"]
    pos = step["live_tokens"] - 1
    assert step["selected_tokens"] == 3 * BLK + pos % BLK + 1
    assert step["index_rows"] == (pos - 3) // 2 + 1
    assert step["state_bytes"] == info["state_bytes_per_slot"]
    assert step["cache_row_bytes"] == 2 * 2 * 32 * 4
    assert selected_keys(np.asarray([5, 40]), SELECTION).tolist() == [6, 25]
    assert selection_rows(np.asarray([50]), SELECTION) == (
        pre["attn_selected_key_rows"], pre["attn_index_rows"])


def test_what_cannot_carry_the_new_kinds_refuses_the_model_by_name(toy):
    net, _ = toy
    cfg = dict(block_len=BLK, max_seq_len=CAP, decode_slots=2,
               prompt_rungs=(32,), prefill_batches=(1,))
    with pytest.raises(StatefulDecodeUnsupportedError, match="int8"):
        GenerationProgramSet(net, config=GenerationConfig(
            kv_cache_dtype="int8", **cfg))
    with pytest.raises(StatefulDecodeUnsupportedError, match="speculative"):
        GenerationProgramSet(net, config=GenerationConfig(**cfg),
                             draft_net=net)
    with pytest.raises(ValueError, match="pages of its selection's block"):
        GenerationProgramSet(net, config=GenerationConfig(
            **dict(cfg, block_len=16)))
    # a model of sparse layers alone is refused for its selection
    only = dict(TOY, num_hidden_layers=2, mixer_types=["minicpm4"] * 2)
    sparse_net = build.build(only, TOY["hyperparameters"], "serve")
    build.install(sparse_net, weights.make(only, 1, "serve"))
    with pytest.raises(SparseDecodeUnsupportedError, match="int8"):
        GenerationProgramSet(sparse_net, config=GenerationConfig(
            kv_cache_dtype="int8", **cfg))
    with pytest.raises(SparseDecodeUnsupportedError, match="speculative"):
        GenerationProgramSet(sparse_net, config=GenerationConfig(**cfg),
                             draft_net=sparse_net)
    ps = GenerationProgramSet(sparse_net, config=GenerationConfig(**cfg))
    assert not ps.prefix_enabled and ps.prefix_skipped_stateful
    assert ps.n_rec == 0 and len(ps.make_cache()) == 3
    from jax.sharding import Mesh
    devs = np.array(jax.devices()[:2]).reshape(1, 2)
    with pytest.raises(SparseDecodeUnsupportedError, match="model-sharded"):
        GenerationProgramSet(sparse_net, config=GenerationConfig(**cfg),
                             mesh=Mesh(devs, ("data", "model")))
    with pytest.raises((SparseDecodeUnsupportedError,
                        StatefulDecodeUnsupportedError),
                       match="model-sharded"):
        GenerationProgramSet(net, config=GenerationConfig(**cfg),
                             mesh=Mesh(devs, ("data", "model")))


# ------------------------------------------------- the benchmark's own parts
def test_the_costs_count_the_work_and_the_readers_read_them():
    """The kernels' costs from the spans' counts at the published widths;
    the seven new readers on hand-made observations; nothing where the
    program lacks the spans (a parent commit's trace)."""
    from benchmarks import run as harness
    f, b = kernel_costs.sparse_decode_cost(PUBLISHED, 16 * 4096, 1024)
    assert b == 2 * 16 * 4096 * 1024 and f == 2 * 32 * 16 * 4096 * 2 * 256
    f, b = kernel_costs.lightning_decode_cost(PUBLISHED, 16, 16 * 12582912)
    assert b == 2 * 16 * 12582912 and f == 6 * 16 * 32 * 4 * 128 * 128
    f, b = kernel_costs.sparse_prefill_cost(PUBLISHED, 1e6, 1000)
    assert f == 2 * 32 * 1e6 * 512 and b == 2 * 1000 * 68 * 128 * 2
    f, b = kernel_costs.lightning_prefill_cost(PUBLISHED, 1000)
    assert f == 6 * 1000 * 32 * 4 * 128 * 128 and b == 6 * 1000 * 4 * 4096 * 2
    # the reckoning the traffic file's list is held to
    per = flops.forward_flops_per_token(PUBLISHED, 12288.5) \
        - flops.head_flops_per_token(PUBLISHED)
    assert 4.44e9 < per < 4.8e9
    assert flops.selected_context(PUBLISHED, 4000) == 4000
    assert 4000 < flops.selected_context(PUBLISHED, 16384) < 4200
    dev = {"kind": "TPU v5 lite"}
    t0 = 1_000_000
    ev = [{"ph": "X", "cat": "span", "name": "generation.decode_step",
           "ts": t0 + i * 10_000, "dur": 9_000,
           "args": {"slots": 16, "live_tokens": 16 * 24576,
                    "selected_tokens": 16 * 4064, "state_bytes":
                    16 * 12582912, "cache_row_bytes": 1024}}
          for i in range(100)]
    ev += [{"ph": "X", "cat": "span", "name": "generation.prefill",
            "ts": t0 + 5_000 + i * 100_000, "dur": 50_000,
            "args": {"tokens": 24576, "attn_selected_key_rows": 8.0e7,
                     "linear_rows": 24576}} for i in range(10)]
    obs = {"kind": "closed_loop", "events": ev, "device": dev,
           "config": PUBLISHED, "epoch_ns": 0, "seconds": 1.0,
           "window_perf": (t0 / 1e6, t0 / 1e6 + 1.0),
           "trace": {"busy_s": 1.0, "window_s": 1.0, "by_op_s": {
               "flash_attention_sparse_fwd": 0.2, "lightning_decode": 0.05,
               "paged_attention_sparse_decode": 0.04,
               "lightning_attention_fwd": 0.06, "fusion": 0.5,
               "while": 0.03}}}
    names = ["attn.sparse_busy_pct.tput", "attn.linear_busy_pct.tput",
             "kvcache.sparse_read_pct.tput",
             "kernels.sparse_prefill_roofline_pct.tput",
             "kernels.sparse_decode_roofline_pct.tput",
             "kernels.lightning_prefill_roofline_pct.tput",
             "kernels.lightning_decode_roofline_pct.tput"]
    got = {n: harness.load_reader(n).read(obs) for n in names}
    # the two kernels and the loops that hold the scoring and the choice
    assert got["attn.sparse_busy_pct.tput"] == pytest.approx(27.0)
    assert got["attn.linear_busy_pct.tput"] == pytest.approx(11.0)
    assert got["kvcache.sparse_read_pct.tput"] == pytest.approx(
        100 * 4064 / 24576)
    for n in names[3:]:
        assert 0 < got[n] < 100, (n, got[n])
    # a program without the spans' counts or the kernels: nothing, no raise
    bare = dict(obs, events=[dict(e, args={"slots": 16, "tokens": 1,
                                           "live_tokens": 5})
                             for e in ev],
                trace=dict(obs["trace"], by_op_s={"fusion": 0.5,
                                                  "while": 0.03}))
    assert all(harness.load_reader(n).read(bare) is None for n in names)
    bench = harness.load_json(ROOT, "BENCHMARK.json")
    cell = "minicpm-sala-serve-longctx"
    mine = [m for m in bench["per_layer"] if m["name"] in names]
    assert len(mine) == 7 and all(
        m["workloads"] == [cell] and m["moves"] == "serve_tokens_per_s"
        for m in mine)
    tr = harness.load_json(harness.HERE, "traffic", "serve-longctx.json")
    assert tr["engine"]["block_len"] == \
        PUBLISHED["assumed"]["sparse_config"]["block"]
    assert tr["lengths"]["max_total"] == PUBLISHED["served_context"] == \
        tr["engine"]["max_seq_len"]


def test_the_reference_imports_nothing_of_the_program():
    import ast
    path = os.path.join(ROOT, "benchmarks", "families", "minicpm_sala",
                        "reference.py")
    tree = ast.parse(open(path).read())
    mods = [n.module for n in ast.walk(tree)
            if isinstance(n, ast.ImportFrom)] + \
        [a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
         for a in n.names]
    assert not any(m.startswith("deeplearning4j_tpu") for m in mods), mods
    assert reference.HIGHEST == jax.lax.Precision.HIGHEST


# ----------------------------------------- the cell's rehearsal on the CPU
def test_the_closed_loop_kind_runs_the_family_and_both_controls_fail(
        toy, engine, monkeypatch):
    """``minicpm-sala-serve-longctx`` rehearsed at the toy size: the
    closed-loop kind's own ``run`` (engine, callers, window, sampling, the
    reference's check) with the family's modules, then the two controls in
    the program's place (float8; full precision reading the forced blocks
    alone), each of which must stand off from the reference where the
    float32 program sits on it. Nothing hangs on the machine's speed: one
    completion is enough for the kind's run, and how far the controls
    stand off is read over a FIXED sample of a hundred served tokens (a
    slow window's handful of tokens may leave float8 on the reference's
    own choice everywhere: ``tests/test_lfm2_serving.py``'s finding),
    handed to the comparison the kind's run makes (``check_outputs``), so
    the controls fail THROUGH the harness. The toy is held to limits of
    its own: no position left out for its selection margin."""
    import types
    from benchmarks import run as harness
    from benchmarks.kinds import _serve, closed_loop
    from benchmarks.lib.correct import Checks
    fam = {k: __import__(f"benchmarks.families.minicpm_sala.{k}",
                         fromlist=[k])
           for k in ("build", "weights", "reference", "flops")}
    traffic = {
        "kind": "closed_loop", "callers": 4, "preroll_s": 0.5,
        "timeout_s": 300.0, "block": 8, "blocks": 400,
        "lengths": {"prompt": {"kind": "uniform", "lo": 36, "hi": 60},
                    "output": {"kind": "uniform", "lo": 2, "hi": 6},
                    "max_total": CAP, "pairing_seed": 1},
        "engine": {"block_len": BLK, "max_seq_len": CAP, "decode_slots": 3,
                   "prompt_rungs": [64], "prefill_batches": [1, 2]},
        "check": {"min_tokens": 40, "max_requests": 12}}
    limits = {"widest_logit_gap": 1e-3, "routing_margin": 0.0,
              "close_margin_share": 0.0}
    monkeypatch.setattr(reference, "cell_limits", lambda cfg: limits)
    out = {}
    for control in (False, True):
        ctx = {"cell": {"name": "toy", "chips": 1}, "config": TOY,
               "traffic": traffic, "limits": limits,
               "seed": 2 ** 31 + 5, "seconds": 6.0, "trace": False,
               "rehearsal": True,
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
    assert res["obs"]["engine"]["state_bytes_per_slot"] > 0
    assert res["obs"]["engine"]["sparse_layers"] == 2
    assert out[True][0]["control_result"]["tokens"] > 0
    done = [{"prompt": p,
             "tokens": engine.generate(p, max_tokens=25, stream=False)[0]}
            for p in _prompts(11, [40, 48, 55, 62])]
    both = types.SimpleNamespace(token_gaps=reference.token_gaps,
                                 CONTROL=reference.CONTROLS)
    fixed = dict(out[True][0], checks=Checks(), seed=7,   # the toy's weights
                 family=dict(fam, reference=both),
                 traffic=dict(traffic, check={"min_tokens": 100,
                                              "max_requests": 4}))
    _serve.check_outputs(fixed, done, 0)
    c = fixed["control_result"]
    assert fixed["checks"].correct, fixed["checks"].rows
    assert c["tokens"] == 100 and c["kept_widest_gap"] < 1e-3
    assert set(c["control_widest_gaps"]) == {"float8", reference.LOCAL_ONLY}
    assert c["control_widest_gaps"]["float8"] > 0.02
    assert c["control_widest_gaps"][reference.LOCAL_ONLY] > 0.02
    assert c["control_widest_gap"] == min(c["control_widest_gaps"].values())


def test_the_margin_at_the_cut_skips_an_equal_pair():
    """``cut_margin``: the last score taken less the first left out, as a
    share of the former; where the two are equal (the earlier block is
    taken on both sides) the nearer of the scores around the pair; inf
    where nothing is left out by score."""
    top = jnp.asarray([[jnp.inf, 5.0, 4.0, 4.0, 3.0]])
    np.testing.assert_allclose(reference.cut_margin(top, 2), [0.2])
    np.testing.assert_allclose(reference.cut_margin(top, 3), [0.25])
    np.testing.assert_allclose(reference.cut_margin(top[:, :4], 3), [0.25])
    np.testing.assert_allclose(reference.cut_margin(top, 4), [0.25])
    assert np.isinf(np.asarray(reference.cut_margin(top, 5)))[0]
    assert np.isinf(np.asarray(reference.cut_margin(
        jnp.asarray([[jnp.inf, jnp.inf, jnp.inf, -jnp.inf]]), 3)))[0]


def test_the_margin_rule_leaves_close_positions_out_and_counts_them(
        toy, engine, monkeypatch):
    """``token_gaps`` under a margin: positions whose selection was nearer
    than the margin are left out of the widest gap and counted; a share
    of them over its limit fails the comparison whatever the gaps; a
    position that reads every block has no margin at all. Read here at
    both sparse layers, whichever of them the seeded weights make bear."""
    monkeypatch.setitem(weights.SPARSE, "bearing_layers", 2)
    served = [(p, engine.generate(p, max_tokens=25, stream=False)[0])
              for p in _prompts(12, [10, 50])]
    base = {"widest_logit_gap": 1e-3, "routing_margin": 0.0,
            "close_margin_share": 0.0}
    c = reference.token_gaps(toy[1], TOY, served, limits=base)
    m = c["margins"]
    # the first request stays under dense_len + topk blocks for a while
    assert np.isinf(m[:8]).all() and np.isfinite(m[25:]).all()
    assert (m[25:] >= 0).all() and c["close_margin_share"] == 0.0
    cut = float(np.median(m[25:]))
    some = reference.token_gaps(toy[1], TOY, served, limits=dict(
        base, routing_margin=cut, close_margin_share=0.9))
    assert some["positions_left_out"] == int((m < cut).sum()) > 0
    assert some["widest_gap"] == some["kept_widest_gap"] < 1e-3
    over = reference.token_gaps(toy[1], TOY, served, limits=dict(
        base, routing_margin=cut, close_margin_share=0.1))
    assert over["widest_gap"] >= 1e-3 * over["close_margin_share"] / 0.1
