"""Multi-step on-device training: scan-fused step windows
(fit(steps_per_dispatch=K)) + the sync-free deferred-score listener
protocol.

The contract under test (ISSUE 2 tentpole): K prefetched device-resident
batches run through ONE jitted, buffer-donated lax.scan program whose
result is BIT-IDENTICAL to K sequential single-step dispatches — including
label/feature masks, the ragged final window, and the K=1 degenerate case
— while listeners never force a per-step device sync (scores stay
device-resident until log/flush time).

Bit-identity holds exactly under this suite's config (conftest enables
x64, so weak-typed updater scalars ride f64); in pure-f32 runs a
stateful updater's fused elementwise chain can differ by <= 1 ulp per
step between the scan body and the standalone program (same math,
different XLA fusion) — see the README numerics footnote.
"""
import logging

import jax
import numpy as np
import pytest

from deeplearning4j_tpu import (InputType, MultiLayerNetwork,
                                NeuralNetConfiguration)
from deeplearning4j_tpu.datasets.dataset import (DataSet, DataSetIterator,
                                                 ListDataSetIterator)
from deeplearning4j_tpu.datasets.iterators import MultiDataSet
from deeplearning4j_tpu.datasets.prefetch import (BatchWindow,
                                                  DevicePrefetchIterator,
                                                  iter_windows)
from deeplearning4j_tpu.nn.layers.core import DenseLayer, OutputLayer
from deeplearning4j_tpu.optimize.listeners import (
    CollectScoresIterationListener, ScoreIterationListener, score_to_float)
from deeplearning4j_tpu.optimize.updaters import Adam, Sgd


def _tiny_net(seed=12, updater=None):
    conf = (NeuralNetConfiguration(seed=seed, updater=updater or Sgd(0.1))
            .list(DenseLayer(n_in=4, n_out=8, activation="tanh"),
                  OutputLayer(n_out=3, activation="softmax", loss="mcxent"))
            .build())
    return MultiLayerNetwork(conf).init()


def _toy(rng, n=64):
    x = rng.normal(size=(n, 4)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, size=n)]
    return x, y


def _it(x, y, bs=8):
    return ListDataSetIterator(features=x, labels=y, batch_size=bs)


def _assert_trees_equal(a, b):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


# ---------------------------------------------------------------- parity
@pytest.mark.parametrize("k", [1, 2, 4, 8])
def test_scan_window_bit_identical_params_and_opt_state(rng, k):
    """fit(steps_per_dispatch=K) == K sequential single steps, bit for
    bit, for params AND updater state (Adam: stateful moments make a
    divergence visible immediately); K=1 is the degenerate case."""
    x, y = _toy(rng)
    a = _tiny_net(updater=Adam(1e-2)).fit(iterator=_it(x, y), epochs=3)
    b = _tiny_net(updater=Adam(1e-2)).fit(iterator=_it(x, y), epochs=3,
                                          steps_per_dispatch=k)
    _assert_trees_equal(a.params, b.params)
    _assert_trees_equal(a.opt_state, b.opt_state)
    assert a.iteration_count == b.iteration_count


def test_scan_window_ragged_final_window(rng):
    """10 batches at K=4: two fused windows + a 2-batch per-step ragged
    tail — results still bit-identical, all 10 iterations counted."""
    x, y = _toy(rng, n=80)
    a = _tiny_net().fit(iterator=_it(x, y), epochs=2)
    b = _tiny_net().fit(iterator=_it(x, y), epochs=2, steps_per_dispatch=4)
    assert a.iteration_count == b.iteration_count == 20
    _assert_trees_equal(a.params, b.params)


def test_scan_window_with_label_mask(rng):
    """Per-example label masks ride the stacked window unchanged."""
    x, y = _toy(rng, n=32)
    mask = np.ones((32,), np.float32)
    mask[1::2] = 0.0
    dss = [DataSet(x[i:i + 8], y[i:i + 8], labels_mask=mask[i:i + 8])
           for i in range(0, 32, 8)]
    a = _tiny_net().fit(iterator=ListDataSetIterator(list(dss)), epochs=3)
    b = _tiny_net().fit(iterator=ListDataSetIterator(list(dss)), epochs=3,
                        steps_per_dispatch=2)
    _assert_trees_equal(a.params, b.params)


def test_scan_window_with_feature_and_label_masks(rng):
    """Time-series batches with BOTH [B,T] masks (the recurrent masking
    contract) through a fused window: bit-identical."""
    from deeplearning4j_tpu.nn.layers import LSTM, RnnOutputLayer
    B, T = 4, 6
    x = np.random.default_rng(3).normal(size=(16, T, 5)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[
        np.random.default_rng(4).integers(0, 3, size=(16, T))]
    fmask = np.ones((16, T), np.float32)
    fmask[:, -2:] = 0.0
    lmask = np.ones((16, T), np.float32)
    lmask[:, 0] = 0.0

    def build():
        conf = (NeuralNetConfiguration(seed=21, updater=Sgd(0.05))
                .list(LSTM(n_out=7, activation="tanh"),
                      RnnOutputLayer(n_out=3, activation="softmax",
                                     loss="mcxent"))
                .set_input_type(InputType.recurrent(5, T))
                .build())
        return MultiLayerNetwork(conf).init()

    dss = [DataSet(x[i:i + B], y[i:i + B], features_mask=fmask[i:i + B],
                   labels_mask=lmask[i:i + B]) for i in range(0, 16, B)]
    a = build().fit(iterator=ListDataSetIterator(list(dss)), epochs=2)
    b = build().fit(iterator=ListDataSetIterator(list(dss)), epochs=2,
                    steps_per_dispatch=4)
    _assert_trees_equal(a.params, b.params)


def test_scan_window_with_prefetched_iterator(rng):
    """Windows assembled from DevicePrefetchIterator's device-resident
    queue (the intended production pairing) stay bit-identical."""
    x, y = _toy(rng)
    a = _tiny_net().fit(iterator=_it(x, y), epochs=2, async_prefetch=False)
    b = _tiny_net().fit(iterator=_it(x, y).prefetch(depth=3), epochs=2,
                        steps_per_dispatch=4)
    _assert_trees_equal(a.params, b.params)


def test_scan_window_scores_match_per_step(rng):
    """Per-step losses surfaced from the scan's ys equal the per-step
    path's scores — same values, same iteration indices."""
    x, y = _toy(rng)
    ca, cb = CollectScoresIterationListener(), CollectScoresIterationListener()
    _tiny_net().set_listeners(ca).fit(iterator=_it(x, y), epochs=2)
    _tiny_net().set_listeners(cb).fit(iterator=_it(x, y), epochs=2,
                                      steps_per_dispatch=4)
    assert [i for i, _ in ca.scores] == [i for i, _ in cb.scores]
    np.testing.assert_array_equal(np.asarray([s for _, s in ca.scores]),
                                  np.asarray([s for _, s in cb.scores]))


def test_scan_window_computation_graph_bit_identical(rng):
    """The shared Solver serves ComputationGraph too: fused CG windows
    are bit-identical to per-step CG training."""
    from deeplearning4j_tpu.nn.graph.graph import ComputationGraph

    def build():
        g = (NeuralNetConfiguration(seed=5, updater=Adam(5e-3))
             .graph_builder()
             .add_inputs("in")
             .add_layer("d1", DenseLayer(n_out=16, activation="tanh"), "in")
             .add_layer("out", OutputLayer(n_out=3, activation="softmax",
                                           loss="mcxent"), "d1")
             .set_outputs("out")
             .set_input_types(InputType.feed_forward(4)))
        return ComputationGraph(g.build()).init()

    x, y = _toy(rng)
    a = build().fit(iterator=_it(x, y), epochs=2)
    b = build().fit(iterator=_it(x, y), epochs=2, steps_per_dispatch=4)
    _assert_trees_equal(a.params, b.params)


# ------------------------------------------------------------- fallbacks
def test_tbptt_falls_back_to_per_step(rng):
    """tBPTT keeps the chunked per-step path under steps_per_dispatch>1
    (documented auto-fallback) — same results as without the knob."""
    from deeplearning4j_tpu.nn.layers import LSTM, RnnOutputLayer
    x = rng.normal(size=(8, 12, 5)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, size=(8, 12))]

    def build():
        conf = (NeuralNetConfiguration(seed=9, updater=Sgd(0.05))
                .list(LSTM(n_out=6, activation="tanh"),
                      RnnOutputLayer(n_out=3, activation="softmax",
                                     loss="mcxent"))
                .set_input_type(InputType.recurrent(5, 12))
                .tbptt_length(4)
                .build())
        return MultiLayerNetwork(conf).init()

    a = build().fit(x, y, epochs=2, batch_size=4)
    b = build().fit(x, y, epochs=2, batch_size=4, steps_per_dispatch=8)
    _assert_trees_equal(a.params, b.params)


def test_second_order_falls_back_to_per_step(rng):
    """Second-order solvers (line search needs host control flow) ignore
    steps_per_dispatch rather than breaking."""
    x, y = _toy(rng, n=32)
    conf = (NeuralNetConfiguration(seed=3, updater=Sgd(0.5),
                                   optimization_algorithm="lbfgs")
            .list(DenseLayer(n_in=4, n_out=8, activation="tanh"),
                  OutputLayer(n_out=3, activation="softmax", loss="mcxent"))
            .build())
    net = MultiLayerNetwork(conf).init()
    net.fit(iterator=_it(x, y, bs=16), epochs=1, steps_per_dispatch=4)
    assert np.all(np.isfinite(np.asarray(net.params_flat())))


def test_steps_per_dispatch_validation(rng):
    x, y = _toy(rng, n=16)
    with pytest.raises(ValueError, match="steps_per_dispatch"):
        _tiny_net().fit(iterator=_it(x, y), steps_per_dispatch=0)


# --------------------------------------------------------- window maker
def test_iter_windows_groups_and_ragged_tail(rng):
    x, y = _toy(rng, n=72)           # 9 batches of 8
    items = list(iter_windows(_it(x, y), 4))
    assert [type(i).__name__ for i in items] == \
        ["BatchWindow", "BatchWindow", "DataSet"]
    assert all(len(w) == 4 for w in items[:2])
    # order + content preserved across the grouping
    flat = [d for i in items for d in (i.datasets
                                       if isinstance(i, BatchWindow) else [i])]
    want = list(_it(x, y))
    assert len(flat) == len(want) == 9
    for g, w in zip(flat, want):
        np.testing.assert_array_equal(np.asarray(g.features), w.features)


def test_iter_windows_mixed_shapes_fall_back(rng):
    """A shape change mid-window degrades that whole group to per-step
    batches (order preserved) instead of mis-stacking."""
    x, y = _toy(rng, n=20)           # batches: 8, 8, 4 — last is ragged
    items = list(iter_windows(_it(x, y), 3))
    assert all(isinstance(i, DataSet) for i in items)
    assert [i.num_examples() for i in items] == [8, 8, 4]


def test_iter_windows_multidataset_falls_back(rng):
    x = rng.normal(size=(8, 4)).astype(np.float32)
    mds = [MultiDataSet([x], [x]) for _ in range(4)]

    class It(DataSetIterator):
        def __iter__(self):
            return iter(mds)

    items = list(iter_windows(It(), 2))
    assert all(isinstance(i, MultiDataSet) for i in items)


def test_prefetch_windows_stack_on_device(rng):
    """DevicePrefetchIterator.windows(k): stacked feeds are [K, B, ...]
    device arrays built from the already-shipped queue entries."""
    x, y = _toy(rng)
    it = DevicePrefetchIterator(_it(x, y), depth=2, dtype="float32")
    wins = [w for w in it.windows(4) if isinstance(w, BatchWindow)]
    assert len(wins) == 2
    xs, ys, lms, fms = wins[0].stacked()
    assert isinstance(xs, jax.Array) and xs.shape == (4, 8, 4)
    assert ys.shape == (4, 8, 3) and lms is None and fms is None
    assert wins[0].num_examples() == 32


# ------------------------------------------- sync-free listener protocol
class _ProbeScore:
    """Duck-typed device scalar that counts host materializations — any
    float()/str()/format() is what a device sync would be."""

    def __init__(self):
        self.syncs = 0

    def __float__(self):
        self.syncs += 1
        return 0.5


def test_score_listener_no_sync_per_step():
    """ScoreIterationListener never materializes the score in the
    dispatch path: off-cycle iterations don't touch it, and on-cycle the
    readback is deferred past the logging gate (no handler -> no sync)."""
    probe = _ProbeScore()
    lst = ScoreIterationListener(10)
    logger = logging.getLogger("deeplearning4j_tpu")
    old = logger.level
    logger.setLevel(logging.WARNING)    # INFO gated off: nothing may sync
    try:
        for i in range(100):
            lst.iteration_done(None, i, probe)
    finally:
        logger.setLevel(old)
    assert probe.syncs == 0


def test_collect_scores_defers_sync_to_flush():
    """CollectScoresIterationListener keeps the device scalar per
    iteration; the readbacks happen only when .scores is first read."""
    probes = [_ProbeScore() for _ in range(50)]
    lst = CollectScoresIterationListener()
    for i, p in enumerate(probes):
        lst.iteration_done(None, i, p)
    assert sum(p.syncs for p in probes) == 0     # collection: sync-free
    scores = lst.scores                          # flush point
    assert len(scores) == 50
    assert all(p.syncs == 1 for p in probes)
    assert lst.scores is scores or lst.scores == scores  # idempotent


def test_collect_scores_bounded_retention():
    """flush_every bounds live device-scalar retention: a run that never
    reads .scores still materializes in batches, not per step."""
    probes = [_ProbeScore() for _ in range(10)]
    lst = CollectScoresIterationListener(flush_every=4)
    for i, p in enumerate(probes):
        lst.iteration_done(None, i, p)
    assert sum(p.syncs for p in probes) == 8      # flushed at 4 and 8
    assert len(lst._raw) == 2                     # only the tail retained
    assert len(lst.scores) == 10                  # final flush on access


def test_collect_scores_interleaves_flush_and_collect():
    lst = CollectScoresIterationListener()
    lst.iteration_done(None, 0, 1.5)
    assert lst.scores == [(0, 1.5)]
    lst.iteration_done(None, 1, 2.5)
    assert lst.scores == [(0, 1.5), (1, 2.5)]
    lst.scores = []                 # pre-protocol reset idiom still works
    assert lst.scores == []
    lst.iteration_done(None, 2, 3.5)
    assert lst.scores == [(2, 3.5)]


def test_score_to_float_handles_device_scalars():
    import jax.numpy as jnp
    assert score_to_float(jnp.float32(1.25)) == 1.25
    assert score_to_float(0.5) == 0.5


def test_fused_loop_never_syncs_on_scores(rng, monkeypatch):
    """End-to-end: with collecting + printing listeners attached, the fit
    loop (fused AND K=1) performs ZERO score materializations until the
    flush point. score_to_float is THE protocol sync point (the probe
    tests above pin that listeners have no other conversion path), so
    counting its calls counts the readbacks."""
    import deeplearning4j_tpu.optimize.listeners as L
    x, y = _toy(rng, n=32)
    calls = {"n": 0}
    orig = L.score_to_float

    def counting(s):
        calls["n"] += 1
        return orig(s)

    logger = logging.getLogger("deeplearning4j_tpu")
    old = logger.level
    logger.setLevel(logging.WARNING)
    try:
        for k in (1, 2):
            calls["n"] = 0
            net = _tiny_net()
            collect = CollectScoresIterationListener()
            net.set_listeners(collect, ScoreIterationListener(2))
            monkeypatch.setattr(L, "score_to_float", counting)
            net.fit(iterator=_it(x, y), epochs=2, steps_per_dispatch=k,
                    async_prefetch=False)
            in_loop = calls["n"]
            assert in_loop == 0, \
                f"K={k}: {in_loop} score readbacks inside the fit loop"
            assert len(collect.scores) == 8          # flush works after
            assert calls["n"] == 8                   # exactly one per score
    finally:
        logger.setLevel(old)


# -------------------------------------------------------- ParallelWrapper
def test_parallel_wrapper_windowed_bit_identical(rng):
    from deeplearning4j_tpu.parallel.data_parallel import ParallelWrapper
    x, y = _toy(rng)
    a = _tiny_net()
    ParallelWrapper(a).fit(_it(x, y, bs=16), epochs=3)
    b = _tiny_net()
    ParallelWrapper(b, steps_per_dispatch=2).fit(_it(x, y, bs=16), epochs=3)
    _assert_trees_equal(a.params, b.params)
    _assert_trees_equal(a.opt_state, b.opt_state)
    assert a.iteration_count == b.iteration_count == 12


def test_parallel_wrapper_windowed_ragged(rng):
    from deeplearning4j_tpu.parallel.data_parallel import ParallelWrapper
    x, y = _toy(rng, n=48)           # 3 batches of 16: one window + ragged
    a = _tiny_net()
    ParallelWrapper(a).fit(_it(x, y, bs=16), epochs=2)
    b = _tiny_net()
    ParallelWrapper(b, steps_per_dispatch=2).fit(_it(x, y, bs=16), epochs=2)
    _assert_trees_equal(a.params, b.params)
    assert b.iteration_count == 6


def test_parallel_wrapper_rejects_accumulator_with_windows():
    from deeplearning4j_tpu.parallel.accumulation import PsumAccumulator
    from deeplearning4j_tpu.parallel.data_parallel import ParallelWrapper
    with pytest.raises(ValueError, match="steps_per_dispatch"):
        ParallelWrapper(_tiny_net(), steps_per_dispatch=4,
                        gradient_accumulator=PsumAccumulator())
