"""serving/ production inference engine: bucket ladder, AOT warm-up with
zero steady-state recompiles, admission control + deadlines, drain-then-stop,
multi-model registry + zero-downtime hot-swap, HTTP surface, metrics.

Heavy soak/hammer variants are marked ``slow``; the tier-1 versions keep
the same assertions at a handful-of-requests scale."""
import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu import MultiLayerNetwork, NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.optimize.updaters import Adam, Sgd
from deeplearning4j_tpu.serving import (BucketLadder, DeadlineExceededError,
                                        DrainingError, InferenceEngine,
                                        QueueFullError, ServingHTTPServer,
                                        ServingMetrics, ShapeMismatchError,
                                        UnknownModelError, xla_compile_count)

R = np.random.default_rng(77)
# engine-vs-direct-forward agreement for these softmax outputs: a few f32
# ulps of values <= 1 (the tolerance this file's other parity checks use)
_ATOL = 1e-6


def _net(seed=3, n_in=4, n_out=3):
    conf = (NeuralNetConfiguration(seed=seed, updater=Sgd(0.1),
                                   dtype="float32")
            .list(DenseLayer(n_in=n_in, n_out=16, activation="tanh"),
                  OutputLayer(n_out=n_out, activation="softmax",
                              loss="mcxent"))
            .build())
    return MultiLayerNetwork(conf).init()


def _post(url, payload, timeout=30):
    req = urllib.request.Request(url, json.dumps(payload).encode(),
                                 {"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, json.loads(r.read())


# ------------------------------------------------------------ bucket ladder
def test_bucket_ladder():
    lad = BucketLadder((32, 1, 8, 8))
    assert lad.rungs == (1, 8, 32)
    assert lad.bucket_for(1) == 1
    assert lad.bucket_for(2) == 8
    assert lad.bucket_for(8) == 8
    assert lad.bucket_for(9) == 32
    assert lad.padding_waste(24) == pytest.approx(8 / 32)
    with pytest.raises(ValueError):
        lad.bucket_for(33)
    with pytest.raises(ValueError):
        BucketLadder(())
    with pytest.raises(ValueError):
        BucketLadder((0, 4))


# ------------------------------------------------- parity + zero recompiles
def test_bucketed_output_bit_identical_to_net_output():
    """Padded-bucket forward sliced back to the caller's rows must be
    BIT-identical to the unbatched net.output — padding must not leak."""
    net = _net()
    sizes = [1, 2, 5, 8, 17, 32]
    xs = [R.normal(size=(n, 4)).astype(np.float32) for n in sizes]
    expected = [np.asarray(net.output(x)) for x in xs]
    eng = InferenceEngine(net, feature_shape=(4,), buckets=(1, 8, 32),
                          batch_window_ms=0.5)
    try:
        for x, want in zip(xs, expected):
            got = eng.predict(x)
            assert got.dtype == want.dtype
            assert np.array_equal(got, want)
    finally:
        eng.stop()


def test_zero_recompiles_after_warmup():
    """Tier-1 guard (ISSUE acceptance): after warm-up, mixed-size concurrent
    traffic through two buckets triggers ZERO new XLA compilations — checked
    against the process-wide jax.monitoring backend-compile counter AND the
    engine's own trace hook."""
    net = _net(seed=9)
    sizes = [1, 3, 4, 8, 6, 2, 7, 5]
    # build every jit program the test itself needs BEFORE snapshotting
    expected = {n: np.asarray(net.output(R.normal(size=(n, 4))
                                         .astype(np.float32)))
                for n in sizes}  # warms net.output's per-shape cache
    eng = InferenceEngine(net, feature_shape=(4,), buckets=(4, 8),
                          batch_window_ms=1.0)
    assert eng.trace_count == 2            # one trace per bucket at warm-up
    compiles0 = xla_compile_count()
    traces0 = eng.trace_count

    results = {}

    def worker(i, n):
        x = R.normal(size=(n, 4)).astype(np.float32)
        results[i] = (x, eng.predict(x))

    threads = [threading.Thread(target=worker, args=(i, n))
               for i, n in enumerate(sizes)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    eng.stop()
    for x, out in results.values():
        assert out.shape == (x.shape[0], 3)
    assert eng.trace_count == traces0, "serving path re-traced a program"
    assert xla_compile_count() == compiles0, \
        "steady-state serving triggered an XLA compilation"
    snap = eng.metrics()["default"]
    assert snap["requests"] == len(sizes)
    assert set(snap["per_bucket"]) <= {4, 8}


def test_mesh_sharded_serving_matches_single_host():
    """Merged batch lands on the 'data' axis (same mapping as
    parallel/inference.py); results must match the unsharded forward."""
    from deeplearning4j_tpu.parallel import make_mesh
    net = _net(seed=21)
    x = R.normal(size=(5, 4)).astype(np.float32)
    want = np.asarray(net.output(x))
    mesh = make_mesh()     # 8 virtual CPU devices on 'data'
    eng = InferenceEngine(net, feature_shape=(4,), buckets=(8, 16),
                          mesh=mesh, batch_window_ms=0.5)
    try:
        got = eng.predict(x)
        np.testing.assert_allclose(got, want, atol=1e-6)
    finally:
        eng.stop()
    with pytest.raises(ValueError, match="not divisible"):
        InferenceEngine(net, feature_shape=(4,), buckets=(1, 8), mesh=mesh)


# ----------------------------------------------- admission control + deadlines
def test_queue_full_fast_fails():
    """With the dispatcher gated on a slow batch, the bounded queue fills
    and the next submit fast-fails with QueueFullError (HTTP 429)."""
    net = _net()
    eng = InferenceEngine(net, feature_shape=(4,), buckets=(1,),
                          queue_limit=2, batch_window_ms=0.1)
    entry = eng.registry.get()
    real_runner = entry.batcher._runner
    gate = threading.Event()

    def gated_runner(padded):
        gate.wait(10.0)
        return real_runner(padded)

    entry.batcher._runner = gated_runner
    x = R.normal(size=(1, 4)).astype(np.float32)
    done = []
    threads = [threading.Thread(
        target=lambda: done.append(eng.predict(x, timeout=20)))
        for _ in range(3)]           # 1 in flight (gated) + 2 queued
    try:
        for t in threads:
            t.start()
            time.sleep(0.05)
        assert entry.batcher.queue_depth == 2
        with pytest.raises(QueueFullError):
            eng.predict(x, timeout=5)
        assert eng.metrics()["default"]["rejected"]["full"] == 1
    finally:
        gate.set()
        for t in threads:
            t.join(timeout=10)
            assert not t.is_alive()
        eng.stop()
    assert len(done) == 3            # the gated requests all completed


def test_deadline_expires_instead_of_blocking():
    """A request whose deadline lapses while queued raises
    DeadlineExceededError promptly — callers can never hang."""
    net = _net()
    eng = InferenceEngine(net, feature_shape=(4,), buckets=(1, 8),
                          batch_window_ms=500.0)   # long collect window
    try:
        x = R.normal(size=(1, 4)).astype(np.float32)
        t0 = time.monotonic()
        with pytest.raises(DeadlineExceededError):
            eng.predict(x, timeout=0.05)
        assert time.monotonic() - t0 < 2.0
        assert eng.metrics()["default"]["rejected"]["deadline"] == 1
    finally:
        eng.stop(drain=False)


def test_shape_mismatch_rejected():
    net = _net()
    eng = InferenceEngine(net, feature_shape=(4,), buckets=(1, 8),
                          batch_window_ms=0.5)
    try:
        with pytest.raises(ShapeMismatchError):
            eng.predict(np.zeros((2, 5), np.float32))
        with pytest.raises(ShapeMismatchError):
            eng.predict(np.zeros((0, 4), np.float32))
    finally:
        eng.stop()


# --------------------------------------------------------------- lifecycle
def test_drain_then_stop_resolves_everything():
    """stop(drain=True): queued work flushes; new work gets DrainingError;
    stop(drain=False): queued work is failed, not hung."""
    net = _net()
    eng = InferenceEngine(net, feature_shape=(4,), buckets=(1, 8),
                          batch_window_ms=50.0)
    x = R.normal(size=(2, 4)).astype(np.float32)
    want = np.asarray(net.output(x))
    results, errors = [], []

    def client():
        try:
            results.append(eng.predict(x, timeout=10))
        except Exception as e:
            errors.append(e)

    threads = [threading.Thread(target=client) for _ in range(4)]
    for t in threads:
        t.start()
    time.sleep(0.02)              # let them enqueue inside the window
    eng.stop(drain=True)          # must flush all four
    for t in threads:
        t.join(timeout=5)
        assert not t.is_alive(), "caller left hanging across stop()"
    assert not errors, errors
    assert len(results) == 4
    for out in results:
        assert np.allclose(out, want, atol=1e-6)
    with pytest.raises(DrainingError):
        eng.predict(x)


def test_stop_without_drain_fails_pending():
    net = _net()
    eng = InferenceEngine(net, feature_shape=(4,), buckets=(1,),
                          batch_window_ms=300.0)
    x = R.normal(size=(1, 4)).astype(np.float32)
    errors, results = [], []

    def client():
        try:
            results.append(eng.predict(x, timeout=10))
        except Exception as e:
            errors.append(e)

    threads = [threading.Thread(target=client) for _ in range(3)]
    for t in threads:
        t.start()
    time.sleep(0.05)
    eng.stop(drain=False)
    for t in threads:
        t.join(timeout=5)
        assert not t.is_alive()
    # every caller resolved: served (the one already collected) or failed
    assert len(errors) + len(results) == 3
    assert all(isinstance(e, DrainingError) for e in errors)


# ------------------------------------------------------- registry + hot-swap
def test_multi_model_routing_and_unknown_model():
    net_a, net_b = _net(seed=1), _net(seed=2)
    eng = InferenceEngine(net_a, feature_shape=(4,), buckets=(8,),
                          batch_window_ms=0.5)
    eng.add_model("b", net_b, feature_shape=(4,), buckets=(8,))
    try:
        x = R.normal(size=(3, 4)).astype(np.float32)
        np.testing.assert_allclose(eng.predict(x),
                                   np.asarray(net_a.output(x)), atol=1e-6)
        np.testing.assert_allclose(eng.predict(x, model="b"),
                                   np.asarray(net_b.output(x)), atol=1e-6)
        with pytest.raises(UnknownModelError):
            eng.predict(x, model="nope")
        info = eng.models()
        assert set(info) == {"default", "b"}
        assert info["default"]["version"] == 1
    finally:
        eng.stop()


def _hot_swap_under_load(n_clients, min_requests, post_swap_requests):
    """Shared body for the tier-1 and slow hot-swap tests: hammer the
    engine while swapping mid-load; ZERO failures allowed, every result
    must be the old or the new model's answer, and any request SUBMITTED
    after the cutover must see the new model. "Is model X's answer" is
    _ATOL-close to ``net.output``, not bit-equal: the engine runs the
    rows inside a padded bucket program of another batch shape than the
    direct call, and whether two differently shaped XLA programs agree in
    the last ulp is the compiler's choice (they did on jax 0.4, they do
    not on 0.9), not a property of this code. The models differ by far
    more than _ATOL, so the classification is unambiguous."""
    net_old, net_new = _net(seed=5), _net(seed=6)
    x = R.normal(size=(3, 4)).astype(np.float32)
    want_old = np.asarray(net_old.output(x))
    want_new = np.asarray(net_new.output(x))
    # swap must be observable, by a margin that dwarfs the tolerance
    assert np.max(np.abs(want_old - want_new)) > 1e3 * _ATOL
    eng = InferenceEngine(net_old, feature_shape=(4,), buckets=(4, 8),
                          batch_window_ms=0.5)
    compiles0 = xla_compile_count()
    failures, outputs = [], []
    out_lock = threading.Lock()
    swapped = threading.Event()

    def client():
        k = post_swap = 0
        # run at least min_requests, and keep going until this client has
        # made post_swap_requests submissions entirely after the cutover
        while k < min_requests or post_swap < post_swap_requests:
            k += 1
            submitted_after_swap = swapped.is_set()
            try:
                out = eng.predict(x, timeout=10)
            except Exception as e:       # pragma: no cover - must not happen
                failures.append(e)
                return
            post_swap += submitted_after_swap
            with out_lock:
                outputs.append((submitted_after_swap, out))

    threads = [threading.Thread(target=client) for _ in range(n_clients)]
    for t in threads:
        t.start()
    time.sleep(0.02)
    version = eng.hot_swap("default", net_new)
    swapped.set()
    for t in threads:
        t.join(timeout=60)
        assert not t.is_alive()
    eng.stop()
    assert not failures, f"hot-swap failed requests: {failures[:3]}"
    assert version == 2
    # same architecture: the swap must not have compiled anything
    assert xla_compile_count() == compiles0
    n_old = n_new = 0
    for submitted_after_swap, out in outputs:
        if np.allclose(out, want_old, rtol=0, atol=_ATOL):
            n_old += 1
            assert not submitted_after_swap, \
                "request submitted after the cutover served by the old model"
        elif np.allclose(out, want_new, rtol=0, atol=_ATOL):
            n_new += 1
        else:                            # pragma: no cover
            raise AssertionError("output matches neither model")
    assert n_old + n_new == len(outputs)
    assert n_new >= n_clients * post_swap_requests


def test_hot_swap_zero_downtime():
    _hot_swap_under_load(n_clients=4, min_requests=8, post_swap_requests=2)


@pytest.mark.slow
def test_hot_swap_soak():
    _hot_swap_under_load(n_clients=8, min_requests=200,
                         post_swap_requests=10)


def test_hot_swap_changed_architecture_warms_before_cutover(tmp_path):
    """A swap to a DIFFERENT architecture compiles the new ladder before
    the cutover; serving keeps answering throughout."""
    conf_big = (NeuralNetConfiguration(seed=8, updater=Sgd(0.1),
                                       dtype="float32")
                .list(DenseLayer(n_in=4, n_out=32, activation="relu"),
                      OutputLayer(n_out=3, activation="softmax",
                                  loss="mcxent"))
                .build())
    net_big = MultiLayerNetwork(conf_big).init()
    eng = InferenceEngine(_net(seed=5), feature_shape=(4,), buckets=(4,),
                          batch_window_ms=0.5)
    try:
        x = R.normal(size=(2, 4)).astype(np.float32)
        eng.predict(x)
        traces0 = eng.trace_count
        version = eng.hot_swap("default", net_big)
        assert version == 2
        assert eng.trace_count == traces0 + 1    # re-warmed the one bucket
        np.testing.assert_allclose(eng.predict(x),
                                   np.asarray(net_big.output(x)), atol=1e-6)
    finally:
        eng.stop()


def test_reload_from_checkpoint_zip(tmp_path):
    from deeplearning4j_tpu.util.serialization import write_model
    net_a, net_b = _net(seed=30), _net(seed=31)
    path = str(tmp_path / "model_b.zip")
    write_model(net_b, path)
    eng = InferenceEngine(net_a, feature_shape=(4,), buckets=(4,),
                          batch_window_ms=0.5)
    try:
        x = R.normal(size=(2, 4)).astype(np.float32)
        assert np.allclose(eng.predict(x), np.asarray(net_a.output(x)),
                           atol=1e-6)
        eng.reload_from_checkpoint("default", path)
        np.testing.assert_allclose(eng.predict(x),
                                   np.asarray(net_b.output(x)), atol=1e-5)
    finally:
        eng.stop()


# -------------------------------------------------------------------- HTTP
def test_http_surface_status_codes(tmp_path):
    from deeplearning4j_tpu.util.serialization import write_model
    net = _net(seed=40)
    net2 = _net(seed=41)
    zip_path = str(tmp_path / "v2.zip")
    write_model(net2, zip_path)
    eng = InferenceEngine(net, feature_shape=(4,), buckets=(1, 8),
                          batch_window_ms=0.5)
    srv = ServingHTTPServer(eng)
    port = srv.start()
    base = f"http://127.0.0.1:{port}"
    try:
        x = R.normal(size=(3, 4)).astype(np.float32)
        # predict 200 + parity
        code, body = _post(f"{base}/predict", {"features": x.tolist()})
        assert code == 200
        np.testing.assert_allclose(np.asarray(body["output"]),
                                   np.asarray(net.output(x)), atol=1e-5)
        # health 200 with queue depths
        with urllib.request.urlopen(f"{base}/health", timeout=10) as r:
            h = json.loads(r.read())
        assert h["status"] == "ok" and "default" in h["queue_depth"]
        # models + metrics
        with urllib.request.urlopen(f"{base}/models", timeout=10) as r:
            m = json.loads(r.read())
        assert m["default"]["buckets"] == [1, 8]
        with urllib.request.urlopen(f"{base}/metrics", timeout=10) as r:
            snap = json.loads(r.read())["default"]
        assert snap["requests"] >= 1 and "p99" in snap["latency_ms"]
        # malformed JSON -> 400
        with pytest.raises(urllib.error.HTTPError) as ei:
            req = urllib.request.Request(f"{base}/predict", b"{not json",
                                         {"Content-Type": "application/json"})
            urllib.request.urlopen(req, timeout=10)
        assert ei.value.code == 400
        # bad feature payload -> 400
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(f"{base}/predict", {"features": [["a", "b"]]})
        assert ei.value.code == 400
        # wrong trailing shape -> 400 (ShapeMismatch taxonomy)
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(f"{base}/predict", {"features": [[1.0, 2.0]]})
        assert ei.value.code == 400
        # unknown model -> 404
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(f"{base}/predict/ghost", {"features": x.tolist()})
        assert ei.value.code == 404
        # reload -> hot swap through the wire
        code, body = _post(f"{base}/reload",
                           {"model": "default", "path": zip_path})
        assert code == 200 and body["version"] == 2
        code, body = _post(f"{base}/predict", {"features": x.tolist()})
        np.testing.assert_allclose(np.asarray(body["output"]),
                                   np.asarray(net2.output(x)), atol=1e-5)
        # reload unknown model -> 404
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(f"{base}/reload", {"model": "ghost", "path": zip_path})
        assert ei.value.code == 404
    finally:
        srv.stop()
    # draining after stop: engine rejects
    with pytest.raises(DrainingError):
        eng.predict(np.zeros((1, 4), np.float32))


def test_http_draining_health_503():
    net = _net(seed=50)
    eng = InferenceEngine(net, feature_shape=(4,), buckets=(1,),
                          batch_window_ms=0.5)
    srv = ServingHTTPServer(eng)
    port = srv.start()
    base = f"http://127.0.0.1:{port}"
    try:
        eng.stop(drain=True)       # engine drains; listener still up
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(f"{base}/health", timeout=10)
        assert ei.value.code == 503
        assert json.loads(ei.value.read())["status"] == "draining"
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(f"{base}/predict", {"features": [[0, 0, 0, 0]]})
        assert ei.value.code == 503
    finally:
        srv.stop()


# ------------------------------------------------------------------ metrics
def test_metrics_snapshot_and_stats_storage_bridge():
    from deeplearning4j_tpu.ui.storage import InMemoryStatsStorage
    net = _net(seed=60)
    eng = InferenceEngine(net, feature_shape=(4,), buckets=(8,),
                          batch_window_ms=0.5)
    try:
        for n in (2, 6, 8):
            eng.predict(R.normal(size=(n, 4)).astype(np.float32))
        snap = eng.metrics()["default"]
        assert snap["requests"] == 3 and snap["rows"] == 16
        assert snap["batches"] >= 1
        assert 0.0 < snap["batch_occupancy"] <= 1.0
        assert snap["padding_waste"] == pytest.approx(
            1.0 - snap["batch_occupancy"])
        assert snap["latency_ms"]["p99"] >= snap["latency_ms"]["p50"] >= 0
        store = InMemoryStatsStorage()
        eng.publish_metrics(store)
        ups = store.get_updates("serving", "default")
        assert ups and ups[-1]["requests"] == 3
    finally:
        eng.stop()


def test_oversized_request_chunks_across_max_bucket():
    net = _net(seed=70)
    eng = InferenceEngine(net, feature_shape=(4,), buckets=(8,),
                          batch_window_ms=0.5)
    try:
        x = R.normal(size=(21, 4)).astype(np.float32)
        np.testing.assert_array_equal(eng.predict(x),
                                      np.asarray(net.output(x)))
    finally:
        eng.stop()


# ------------------------------------------------------------ hammer (soak)
def _hammer(eng, net, n_threads, n_requests, sizes):
    """Every caller must get exactly its own rows back (to _ATOL of the
    direct forward — see _hot_swap_under_load on why not bit-equal; any
    other request's rows are O(1) away)."""
    failures = []

    def client(tid):
        rng = np.random.default_rng(1000 + tid)
        for k in range(n_requests):
            n = sizes[(tid + k) % len(sizes)]
            x = rng.normal(size=(n, 4)).astype(np.float32)
            # salt row 0 with an id so cross-request row mixups can't
            # accidentally produce the right answer
            x[0, 0] = tid * 1000 + k
            try:
                out = eng.predict(x, timeout=30)
                want = np.asarray(net.output(x))
                if out.shape != want.shape or not np.allclose(
                        out, want, rtol=0, atol=_ATOL):
                    failures.append((tid, k, "mismatch"))
            except Exception as e:
                failures.append((tid, k, repr(e)))

    threads = [threading.Thread(target=client, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
        assert not t.is_alive()
    assert not failures, failures[:5]


def test_concurrent_hammer_result_integrity():
    net = _net(seed=80)
    sizes = [1, 2, 3, 5, 8]
    for n in sizes:                       # pre-warm net.output's jit cache
        net.output(np.zeros((n, 4), np.float32))
    eng = InferenceEngine(net, feature_shape=(4,), buckets=(4, 8),
                          batch_window_ms=1.0, queue_limit=512)
    try:
        _hammer(eng, net, n_threads=6, n_requests=6, sizes=sizes)
    finally:
        eng.stop()


@pytest.mark.slow
def test_concurrent_hammer_soak():
    net = _net(seed=81)
    sizes = [1, 2, 3, 5, 8, 13, 21, 32]
    for n in sizes:
        net.output(np.zeros((n, 4), np.float32))
    eng = InferenceEngine(net, feature_shape=(4,), buckets=(8, 32, 64),
                          batch_window_ms=1.0, queue_limit=2048)
    try:
        _hammer(eng, net, n_threads=16, n_requests=100, sizes=sizes)
        snap = eng.metrics()["default"]
        assert snap["requests"] == 16 * 100
        assert snap["rejected"]["deadline"] == 0
    finally:
        eng.stop()


def test_unwarmed_engine_raises_clear_error():
    from deeplearning4j_tpu.serving import ServingError
    net = _net(seed=90)
    eng = InferenceEngine(net, feature_shape=(4,), buckets=(8,),
                          batch_window_ms=0.5, warm=False)
    try:
        with pytest.raises(ServingError, match="no warmed program"):
            eng.predict(np.zeros((2, 4), np.float32), timeout=5)
        eng.warm_up()
        assert eng.predict(np.zeros((2, 4), np.float32)).shape == (2, 3)
    finally:
        eng.stop()


def test_hot_swap_changed_arch_keeps_custom_forward_fn():
    """A changed-architecture swap must re-warm with the model's custom
    forward_fn, not silently fall back to the default forward."""
    net_a, net_b = _net(seed=91), _net(seed=92, n_in=4)
    net_b.conf.layers = net_b.conf.layers  # same conf class, new params

    def fwd_a(params, state, x):
        return net_a._output_pure(params, state, x) + 1.0

    def check(eng, net, x):
        return np.allclose(eng.predict(x),
                           np.asarray(net.output(x)) + 1.0, atol=1e-6)

    eng = InferenceEngine(net_a, feature_shape=(4,), buckets=(4,),
                          batch_window_ms=0.5, forward_fn=fwd_a)
    try:
        x = R.normal(size=(2, 4)).astype(np.float32)
        assert check(eng, net_a, x)
        # force the changed-shape path: a wider hidden layer
        conf_big = (NeuralNetConfiguration(seed=93, updater=Sgd(0.1),
                                           dtype="float32")
                    .list(DenseLayer(n_in=4, n_out=24, activation="tanh"),
                          OutputLayer(n_out=3, activation="softmax",
                                      loss="mcxent"))
                    .build())
        net_big = MultiLayerNetwork(conf_big).init()
        eng.hot_swap("default", net_big)
        # the custom fwd closes over net_a's ARCHITECTURE but runs the
        # swapped params; with the default-forward bug this returned
        # net_big.output(x) WITHOUT the +1.0 marker
        np.testing.assert_allclose(
            eng.predict(x), np.asarray(net_big.output(x)) + 1.0, atol=1e-6)
    finally:
        eng.stop()


def test_hot_swap_same_shapes_different_arch_rewarms():
    """Regression: the fast-path signature must catch same-SHAPED nets with
    a different architecture (tanh vs relu) — reusing the old executables
    would silently serve the old activation with the new params."""
    def build(act):
        conf = (NeuralNetConfiguration(seed=94, updater=Sgd(0.1),
                                       dtype="float32")
                .list(DenseLayer(n_in=4, n_out=16, activation=act),
                      OutputLayer(n_out=3, activation="softmax",
                                  loss="mcxent"))
                .build())
        return MultiLayerNetwork(conf).init()

    net_tanh, net_relu = build("tanh"), build("relu")
    eng = InferenceEngine(net_tanh, feature_shape=(4,), buckets=(4,),
                          batch_window_ms=0.5)
    try:
        x = R.normal(size=(2, 4)).astype(np.float32)
        traces0 = eng.trace_count
        eng.hot_swap("default", net_relu)
        assert eng.trace_count == traces0 + 1   # forced full re-warm
        # the relu net's answer (to _ATOL: bucket-of-4 program vs a direct
        # batch-2 call), and nowhere near the old activation's
        np.testing.assert_allclose(eng.predict(x),
                                   np.asarray(net_relu.output(x)),
                                   rtol=0, atol=_ATOL)
        assert not np.allclose(eng.predict(x),
                               np.asarray(net_tanh.output(x)), atol=1e-3)
        # seed-only difference stays on the free fast path
        net_relu2 = build("relu")
        net_relu2.init(seed=12345)
        traces1 = eng.trace_count
        eng.hot_swap("default", net_relu2)
        assert eng.trace_count == traces1       # no re-warm
        np.testing.assert_allclose(eng.predict(x),
                                   np.asarray(net_relu2.output(x)),
                                   rtol=0, atol=_ATOL)
    finally:
        eng.stop()
