"""Overlapped input pipeline: DevicePrefetchIterator (device-side prefetch),
the thread-pool shard reader, fit() routing, and ETL-wait observability.

Reference: AsyncDataSetIterator.java:30 (host prefetch) +
PerformanceListener.java:111,178 (ETL time per iteration). The device-side
half is TPU-new (datasets/prefetch.py): batch N+1 ships via jax.device_put
while step N computes. These tests pin the contract: bit-identical training
results, bounded in-flight depth, pre-sharded placement, clean shutdown,
and preserved back-pressure for live streams.
"""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from deeplearning4j_tpu import MultiLayerNetwork, NeuralNetConfiguration
from deeplearning4j_tpu.datasets.dataset import (DataSet, DataSetIterator,
                                                 ListDataSetIterator)
from deeplearning4j_tpu.datasets.export import (ShardedFileDataSetIterator,
                                                export_dataset_iterator)
from deeplearning4j_tpu.datasets.iterators import (ExistingDataSetIterator,
                                                   MultiDataSet)
from deeplearning4j_tpu.datasets.prefetch import DevicePrefetchIterator
from deeplearning4j_tpu.nn.layers.core import DenseLayer, OutputLayer
from deeplearning4j_tpu.optimize.listeners import PerformanceListener
from deeplearning4j_tpu.optimize.updaters import Sgd
from deeplearning4j_tpu.parallel.mesh import data_sharding, make_mesh
from deeplearning4j_tpu.parallel.streaming import StreamingDataSetIterator


def _tiny_net(seed=12):
    conf = (NeuralNetConfiguration(seed=seed, updater=Sgd(0.1))
            .list(DenseLayer(n_in=4, n_out=8, activation="tanh"),
                  OutputLayer(n_out=3, activation="softmax", loss="mcxent"))
            .build())
    return MultiLayerNetwork(conf).init()


def _toy(rng, n=64):
    x = rng.normal(size=(n, 4)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, size=n)]
    return x, y


class CountingIterator(DataSetIterator):
    """Instrumented base: counts how many batches the consumer side has
    pulled out of it (the prefetcher's look-ahead)."""

    def __init__(self, data):
        self.data = list(data)
        self.pulled = 0

    def __iter__(self):
        for ds in self.data:
            self.pulled += 1
            yield ds


def _prefetch_threads():
    return [t for t in threading.enumerate()
            if t.name == "device-prefetch" and t.is_alive()]


def _await_no_prefetch_threads(timeout=5.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if not _prefetch_threads():
            return True
        time.sleep(0.02)
    return False


# ------------------------------------------------------------- correctness
def test_training_results_bit_exact_vs_unwrapped(rng):
    """The tentpole contract: prefetched fit == serial fit, bit for bit."""
    x, y = _toy(rng)
    a = _tiny_net().fit(iterator=ListDataSetIterator(
        features=x, labels=y, batch_size=16), epochs=3)
    b = _tiny_net().fit(iterator=ListDataSetIterator(
        features=x, labels=y, batch_size=16), epochs=3, async_prefetch=False)
    np.testing.assert_array_equal(np.asarray(a.params_flat()),
                                  np.asarray(b.params_flat()))


def test_explicit_prefetched_iterator_bit_exact(rng):
    """A caller-supplied DevicePrefetchIterator (the .prefetch() sugar)
    trains identically too."""
    x, y = _toy(rng)
    a = _tiny_net().fit(iterator=ListDataSetIterator(
        features=x, labels=y, batch_size=16).prefetch(depth=3), epochs=2)
    b = _tiny_net().fit(iterator=ListDataSetIterator(
        features=x, labels=y, batch_size=16), epochs=2, async_prefetch=False)
    np.testing.assert_array_equal(np.asarray(a.params_flat()),
                                  np.asarray(b.params_flat()))


def test_stream_values_and_order_preserved(rng):
    x, y = _toy(rng, n=40)
    base = ListDataSetIterator(features=x, labels=y, batch_size=8)
    got = list(DevicePrefetchIterator(base, depth=2, dtype="float32"))
    want = list(ListDataSetIterator(features=x, labels=y, batch_size=8))
    assert len(got) == len(want) == 5
    for g, w in zip(got, want):
        assert isinstance(g.features, jax.Array)
        np.testing.assert_array_equal(np.asarray(g.features), w.features)
        np.testing.assert_array_equal(np.asarray(g.labels), w.labels)


def test_dtype_cast_floats_only(rng):
    """Float arrays land as the requested dtype; ints (uint8 wire images,
    token ids) pass through untouched — the 4x-less-wire contract."""
    ds = DataSet(rng.integers(0, 255, (4, 3)).astype(np.uint8),
                 rng.normal(size=(4, 2)).astype(np.float64))
    out = next(iter(DevicePrefetchIterator(
        ExistingDataSetIterator([ds]), depth=1, dtype="float32")))
    assert out.features.dtype == np.uint8
    assert out.labels.dtype == np.float32


def test_multidataset_batches_ship_per_input(rng):
    """ComputationGraph multi-input batches: every array of the per-input
    lists lands on device, None mask holes survive."""
    mds = MultiDataSet([rng.normal(size=(4, 3)).astype(np.float32),
                        rng.normal(size=(4, 5)).astype(np.float32)],
                       [rng.normal(size=(4, 2)).astype(np.float32)],
                       labels_mask=[None])
    out = next(iter(DevicePrefetchIterator(
        ExistingDataSetIterator([mds]), depth=1, dtype="float32")))
    assert isinstance(out, MultiDataSet)
    assert all(isinstance(f, jax.Array) for f in out.features)
    assert out.labels_mask == [None]
    np.testing.assert_array_equal(np.asarray(out.features[1]),
                                  mds.features[1])


# ------------------------------------------------------------------- depth
def test_in_flight_depth_respected(rng):
    """The producer never runs more than depth (queue) + 1 (in hand)
    batches ahead of the consumer."""
    x, y = _toy(rng, n=240)
    depth = 2
    base = CountingIterator(ListDataSetIterator(features=x, labels=y,
                                                batch_size=8).data)
    it = iter(DevicePrefetchIterator(base, depth=depth))
    consumed = 0
    for _ in range(10):
        next(it)
        consumed += 1
        time.sleep(0.05)       # let the producer run as far as it can
        assert base.pulled <= consumed + depth + 1, (
            f"pulled {base.pulled} with only {consumed} consumed")
    it.close()


# ---------------------------------------------------------------- sharding
def test_sharded_device_put_placement(rng):
    """With a NamedSharding over a 2-device mesh, batches land PRE-SHARDED
    on the data axis."""
    mesh = make_mesh((2,), ("data",), jax.devices()[:2])
    sh = data_sharding(mesh)
    x, y = _toy(rng, n=32)
    base = ListDataSetIterator(features=x, labels=y, batch_size=16)
    for ds in DevicePrefetchIterator(base, depth=2, sharding=sh,
                                     dtype="float32"):
        assert ds.features.sharding == sh
        assert ds.labels.sharding == sh
        # the batch dim is actually split: each device holds half
        shards = ds.features.addressable_shards
        assert {s.data.shape[0] for s in shards} == {8}
    np.testing.assert_array_equal(
        np.asarray(jax.device_put(x[:16], sh)), x[:16])


def test_remainder_batch_ships_unsharded_instead_of_failing(rng):
    """A final batch that doesn't tile the mesh must not kill the epoch."""
    mesh = make_mesh((2,), ("data",), jax.devices()[:2])
    sh = data_sharding(mesh)
    x, y = _toy(rng, n=21)     # 16 + remainder 5
    base = ListDataSetIterator(features=x, labels=y, batch_size=16)
    got = list(DevicePrefetchIterator(base, depth=2, sharding=sh))
    assert [g.features.shape[0] for g in got] == [16, 5]


def test_parallel_wrapper_sync_uses_device_prefetch(rng):
    """ParallelWrapper's per-step all-reduce path trains through the
    sharded device prefetcher and matches the host-fed result."""
    from deeplearning4j_tpu.parallel.data_parallel import ParallelWrapper
    x, y = _toy(rng)
    pw = ParallelWrapper(_tiny_net(), workers=2)
    perf = PerformanceListener(frequency=1)
    pw.net.set_listeners(perf)
    pw.fit(ListDataSetIterator(features=x, labels=y, batch_size=16),
           epochs=2)
    single = _tiny_net().fit(iterator=ListDataSetIterator(
        features=x, labels=y, batch_size=16), epochs=2, async_prefetch=False)
    np.testing.assert_allclose(np.asarray(pw.net.params_flat()),
                               np.asarray(single.params_flat()),
                               rtol=2e-5, atol=2e-6)
    rec = perf.history[-1]
    assert rec["etl_wait_ms_per_iteration"] >= 0.0
    assert rec["device_ms_per_iteration"] > 0.0


# ---------------------------------------------------------------- shutdown
def test_early_break_stops_producer_thread(rng):
    x, y = _toy(rng, n=800)
    base = CountingIterator(ListDataSetIterator(features=x, labels=y,
                                                batch_size=8).data)
    for i, _ in enumerate(DevicePrefetchIterator(base, depth=2)):
        if i == 1:
            break
    assert _await_no_prefetch_threads(), "producer thread leaked after break"
    pulled = base.pulled
    time.sleep(0.15)
    assert base.pulled == pulled, "producer kept pulling after shutdown"
    assert base.pulled < len(base.data)


def test_consumer_exception_stops_producer(rng):
    x, y = _toy(rng, n=800)
    base = CountingIterator(ListDataSetIterator(features=x, labels=y,
                                                batch_size=8).data)
    with pytest.raises(RuntimeError, match="boom"):
        for i, _ in enumerate(DevicePrefetchIterator(base, depth=2)):
            if i == 2:
                raise RuntimeError("boom")
    assert _await_no_prefetch_threads()


def test_base_exception_propagates_to_consumer(rng):
    x, y = _toy(rng, n=32)

    class Exploding(DataSetIterator):
        def __iter__(self):
            yield from ListDataSetIterator(features=x, labels=y,
                                           batch_size=16)
            raise ValueError("disk on fire")

    with pytest.raises(ValueError, match="disk on fire"):
        list(DevicePrefetchIterator(Exploding(), depth=2))
    assert _await_no_prefetch_threads()


# --------------------------------------------------------------- streaming
def test_streaming_back_pressure_preserved_under_prefetch():
    """The prefetcher's bounded queue must NOT turn a live stream into an
    unbounded buffer: once topic capacity + prefetch depth (+1 in flight)
    are saturated, non-blocking publishes are rejected; consuming frees
    slots again."""
    topic = StreamingDataSetIterator(capacity=2)
    pf = DevicePrefetchIterator(topic, depth=1)
    x = np.ones((2, 3), np.float32)
    y = np.ones((2, 1), np.float32)
    assert topic.publish(x, y, block=False)
    it = iter(pf)
    next(it)                            # starts the producer thread

    accepted, rejections = 0, 0
    for _ in range(200):
        if topic.publish(x, y, block=False):
            accepted += 1
            rejections = 0
        else:
            rejections += 1
            if rejections >= 5:
                break
        time.sleep(0.01)
    assert rejections >= 5, "publish never saw back-pressure"
    # bound: topic queue (2) + prefetch queue (1) + 1 in the producer's hand
    assert accepted <= 2 + 1 + 1

    next(it)                            # consume one -> a slot frees up
    ok = False
    for _ in range(100):
        if topic.publish(x, y, block=False):
            ok = True
            break
        time.sleep(0.01)
    assert ok, "slot did not free after consuming"
    topic.end_of_stream()
    list(it)                            # drain + clean exit
    assert _await_no_prefetch_threads()


# ------------------------------------------------- fit() routing smoke test
def test_fit_routes_iterator_feeds_through_prefetcher(rng, monkeypatch):
    """CI guard: a regression back to serial feeding must fail tier-1, not
    only show up as input wait on the chip."""
    from deeplearning4j_tpu.optimize import solver as solver_mod
    used = []

    class Spy(DevicePrefetchIterator):
        def __iter__(self):
            used.append(True)
            return super().__iter__()

    monkeypatch.setattr(solver_mod, "DevicePrefetchIterator", Spy)
    x, y = _toy(rng)
    _tiny_net().fit(iterator=ListDataSetIterator(features=x, labels=y,
                                                 batch_size=16), epochs=1)
    assert used, ("fit() no longer routes iterator feeds through "
                  "DevicePrefetchIterator")


def test_etl_wait_and_device_ms_surfaced_by_listener(rng):
    """PerformanceListener history carries the reference's ETL split:
    etl_wait_ms (feed block) vs device_ms (dispatch + compute)."""
    net = _tiny_net()
    perf = PerformanceListener(frequency=1)
    net.set_listeners(perf)
    x, y = _toy(rng)
    net.fit(iterator=ListDataSetIterator(features=x, labels=y,
                                         batch_size=16), epochs=2)
    assert perf.history
    rec = perf.history[-1]
    assert rec["etl_wait_ms_per_iteration"] >= 0.0
    assert rec["device_ms_per_iteration"] > 0.0
    # back-compat alias for pre-overlap consumers
    assert rec["etl_ms_per_iteration"] == rec["etl_wait_ms_per_iteration"]


# ------------------------------------------------- thread-pool shard reads
def _export_shards(tmp_path, rng, n_batches=7):
    def gen():
        for _ in range(n_batches):
            yield DataSet(rng.normal(size=(8, 4)).astype(np.float32),
                          np.eye(3, dtype=np.float32)[
                              rng.integers(0, 3, 8)])
    export_dataset_iterator(gen(), str(tmp_path), batches_per_shard=2)


def test_prefetch_buffer_zero_means_no_prefetch(rng):
    """Back-compat: ParallelWrapper(prefetch_buffer=0) and
    fit(prefetch_depth=0) opt OUT of prefetching instead of raising."""
    from deeplearning4j_tpu.parallel.data_parallel import ParallelWrapper
    x, y = _toy(rng)
    pw = ParallelWrapper(_tiny_net(), workers=2, prefetch_buffer=0)
    pw.fit(ListDataSetIterator(features=x, labels=y, batch_size=16),
           epochs=1)
    _tiny_net().fit(iterator=ListDataSetIterator(features=x, labels=y,
                                                 batch_size=16),
                    epochs=1, prefetch_depth=0)


def test_pooled_shard_reader_bit_identical_to_serial(tmp_path, rng):
    _export_shards(tmp_path, rng)
    # pooling is opt-in: the default keeps the lazy one-batch footprint
    assert ShardedFileDataSetIterator(str(tmp_path)).reader_threads == 1
    serial = list(ShardedFileDataSetIterator(str(tmp_path),
                                             reader_threads=1))
    pooled = list(ShardedFileDataSetIterator(str(tmp_path),
                                             reader_threads=3))
    assert len(serial) == len(pooled) == 7
    for a, b in zip(serial, pooled):
        np.testing.assert_array_equal(a.features, b.features)
        np.testing.assert_array_equal(a.labels, b.labels)


def test_pooled_shard_reader_early_break(tmp_path, rng):
    _export_shards(tmp_path, rng, n_batches=12)
    it = ShardedFileDataSetIterator(str(tmp_path), reader_threads=2)
    for i, _ in enumerate(it):
        if i == 2:
            break
    # a second full pass still works (no wedged pool state)
    assert len(list(it)) == 12


def test_full_overlapped_pipeline_end_to_end(tmp_path, rng):
    """Shards on disk -> thread-pool reads -> device prefetch -> fit():
    same params as the serial, host-fed path."""
    x, y = _toy(rng)

    def gen():
        for s in range(0, 64, 16):
            yield DataSet(x[s:s + 16], y[s:s + 16])
    export_dataset_iterator(gen(), str(tmp_path), batches_per_shard=2)

    piped = ShardedFileDataSetIterator(str(tmp_path), reader_threads=2)
    a = _tiny_net().fit(iterator=piped.prefetch(depth=2), epochs=2)
    b = _tiny_net().fit(iterator=ListDataSetIterator(
        features=x, labels=y, batch_size=16), epochs=2, async_prefetch=False)
    np.testing.assert_array_equal(np.asarray(a.params_flat()),
                                  np.asarray(b.params_flat()))


# ----------------------------------------- staging pool + bandwidth gauge
def test_host_to_device_gbps_gauge_published(rng):
    """The producer's periodic blocking transfer sample must land on the
    iterator attribute AND the prefetch.host_to_device_gbps gauge."""
    from deeplearning4j_tpu import telemetry
    telemetry.reset()
    x = rng.normal(size=(64, 4)).astype(np.float64)
    y = np.eye(3, dtype=np.float64)[rng.integers(0, 3, 64)]
    it = DevicePrefetchIterator(
        ListDataSetIterator(features=x, labels=y, batch_size=16),
        depth=2, dtype="float32")
    list(it)
    assert it.host_to_device_gbps > 0
    gauge = telemetry.get_registry().gauge("prefetch.host_to_device_gbps")
    assert gauge.value == pytest.approx(it.host_to_device_gbps)


def test_cast_batches_correct_with_staging_pool(rng):
    """The staging pool must NEVER corrupt shipped batches — on this
    zero-copy CPU backend every aliased slot is retired instead of
    reused, and the data of every batch (two epochs) stays exact."""
    x = rng.normal(size=(160, 4)).astype(np.float64)
    y = np.eye(3, dtype=np.float64)[rng.integers(0, 3, 160)]
    it = DevicePrefetchIterator(
        ListDataSetIterator(features=x, labels=y, batch_size=16),
        depth=2, dtype="float32")
    for _ in range(2):
        for i, b in enumerate(it):
            np.testing.assert_array_equal(
                np.asarray(b.features),
                x[i * 16:(i + 1) * 16].astype(np.float32))
            np.testing.assert_array_equal(
                np.asarray(b.labels),
                y[i * 16:(i + 1) * 16].astype(np.float32))


def test_staging_pool_is_private_to_each_iteration(rng):
    """Regression: the staging pool was shared per-instance, so a stale
    producer thread that outlived an early-broken epoch by one batch
    could stage into the SAME slots as the next epoch's producer and
    overwrite a buffer whose transfer was still in flight. Each __iter__
    must own a fresh pool (the stale producer keeps its old one), and
    data after an early break must stay exact."""
    x = rng.normal(size=(160, 4)).astype(np.float64)
    y = np.eye(3, dtype=np.float64)[rng.integers(0, 3, 160)]
    it = DevicePrefetchIterator(
        ListDataSetIterator(features=x, labels=y, batch_size=16),
        depth=2, dtype="float32")
    for b in it:                     # early break: producer may still be
        break                        # one batch deep in its epoch
    pool_first = it._staging
    it.reset()
    for i, b in enumerate(it):
        np.testing.assert_array_equal(
            np.asarray(b.features),
            x[i * 16:(i + 1) * 16].astype(np.float32))
    assert it._staging is not pool_first


def test_staging_pool_reuses_buffers_on_copying_backend():
    """Pool mechanics against a fake COPYING backend: allocations stop at
    the slot count, every rotated slot waits for its previous transfer,
    and an alias-suspected slot is retired, never overwritten."""
    from deeplearning4j_tpu.datasets.prefetch import (_NEVER_REUSE,
                                                      _StagingPool)

    class Copied:
        def __init__(self):
            self.blocked = False

        def devices(self):
            return [type("D", (), {"platform": "tpu"})()]

        def block_until_ready(self):
            self.blocked = True

    pool = _StagingPool(3)
    a = np.arange(8, dtype=np.float64)
    fakes = []
    for i in range(7):
        slot = pool.stage(a + i, np.float32)
        np.testing.assert_array_equal(slot[0], (a + i).astype(np.float32))
        fake = Copied()
        pool.mark(slot, fake)
        fakes.append(fake)
    assert pool.allocations == 3
    # slots rotated 4 times; each rotation blocked on the prior transfer
    assert sum(f.blocked for f in fakes) == 4

    class Aliased:
        def devices(self):
            return [type("D", (), {"platform": "cpu"})()]

        def unsafe_buffer_pointer(self):
            return self.buf.ctypes.data

    pool2 = _StagingPool(2)
    s1 = pool2.stage(a, np.float32)
    al = Aliased()
    al.buf = s1[0]
    pool2.mark(s1, al)
    assert s1[1] is _NEVER_REUSE
    buf_before = s1[0]
    pool2.stage(a + 1, np.float32)      # fills slot 2
    pool2.mark(pool2.stage(a + 2, np.float32), Copied())  # retires slot 1
    # the aliased buffer was left untouched (the device array owns it)
    np.testing.assert_array_equal(buf_before, a.astype(np.float32))
    assert pool2.allocations == 3
