"""Data-parallel tests on the 8-device virtual CPU mesh (the analogue of the
reference's Spark local[n] tests, SURVEY.md §4): sync DP convergence parity,
averaging-frequency emulation, ParallelInference batching."""
import threading

import jax
import numpy as np
import pytest

from deeplearning4j_tpu import MultiLayerNetwork, NeuralNetConfiguration
from deeplearning4j_tpu.datasets.dataset import ListDataSetIterator
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.optimize.updaters import Adam, Sgd
from deeplearning4j_tpu.parallel import (ParallelInference, ParallelWrapper,
                                         make_mesh)


def _net(seed=3, updater=None):
    conf = (NeuralNetConfiguration(seed=seed, updater=updater or Sgd(0.1))
            .list(DenseLayer(n_in=4, n_out=16, activation="tanh"),
                  OutputLayer(n_out=3, activation="softmax", loss="mcxent"))
            .build())
    return MultiLayerNetwork(conf).init()


def _data(n=256, seed=0):
    r = np.random.default_rng(seed)
    x = r.normal(size=(n, 4)).astype(np.float32)
    yi = (x.sum(-1) > 0).astype(int) + (x[:, 0] > 1).astype(int)
    return x, np.eye(3, dtype=np.float32)[yi]


def test_mesh_has_8_devices():
    assert len(jax.devices()) == 8
    mesh = make_mesh()
    assert mesh.devices.size == 8


def test_sync_dp_matches_single_device_math():
    """Per-step all-reduce DP over sharded batch must equal the single-device
    step on the full batch (same global batch, SGD)."""
    x, y = _data(64)
    it = ListDataSetIterator(features=x, labels=y, batch_size=64)
    net_a = _net(seed=11)
    net_b = _net(seed=11)
    assert np.allclose(np.asarray(net_a.params_flat()),
                       np.asarray(net_b.params_flat()))
    net_a.fit(x, y, epochs=3, batch_size=64)
    ParallelWrapper(net_b, training_mode="shared_gradients").fit(it, epochs=3)
    assert np.allclose(np.asarray(net_a.params_flat()),
                       np.asarray(net_b.params_flat()), atol=1e-5)


@pytest.mark.parametrize("kw", [{}, {"training_mode": "averaging",
                                     "averaging_frequency": 2}])
def test_token_ids_reach_a_bf16_net_as_integers(kw):
    """Regression: ParallelWrapper cast EVERY feed to the net's dtype, so a
    bf16 language model got its token ids through bf16 — above 256 another
    token (301 -> 300). One Adam step moves exactly the embedding rows of
    the ids that were fed; a rounded id moves its neighbour's row."""
    from deeplearning4j_tpu import InputType
    from deeplearning4j_tpu.nn.layers import (EmbeddingSequenceLayer,
                                              RnnOutputLayer)
    conf = (NeuralNetConfiguration(seed=3, updater=Adam(1e-2),
                                   dtype="bfloat16")
            .list(EmbeddingSequenceLayer(n_in=512, n_out=8),
                  RnnOutputLayer(n_out=512, activation="softmax",
                                 loss="mcxent"))
            .set_input_type(InputType.recurrent(1, 8)).build())
    net = MultiLayerNetwork(conf).init()
    before = np.asarray(net.params[0]["W"], np.float32)
    ids = np.array([[301, 303, 257, 259, 411, 413, 499, 501]] * 4, np.int32)
    onehot = np.eye(512, dtype=np.float32)[np.roll(ids, -1, 1)]
    ParallelWrapper(net, workers=2, **kw).fit(
        ListDataSetIterator(features=ids, labels=onehot, batch_size=4),
        epochs=2)
    after = np.asarray(net.params[0]["W"], np.float32)
    moved = set(np.nonzero(np.any(after != before, axis=1))[0].tolist())
    assert moved == set(ids[0].tolist())


def test_averaging_frequency_mode_converges():
    x, y = _data(512)
    it = ListDataSetIterator(features=x, labels=y, batch_size=64)
    net = _net(seed=5, updater=Adam(5e-3))
    pw = ParallelWrapper(net, averaging_frequency=4, training_mode="averaging")
    s0 = net.score(x, y)
    pw.fit(it, epochs=15)
    assert net.score(x, y) < s0
    ev = net.evaluate(x, y)
    assert ev.accuracy() > 0.8


def test_parallel_inference_batched():
    net = _net()
    x, _ = _data(64)
    expected = np.asarray(net.output(x))
    pi = ParallelInference(net, batch_limit=64)
    results = {}

    def worker(i):
        results[i] = pi.output(x[i * 8:(i + 1) * 8])

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    pi.shutdown()
    for i in range(8):
        assert np.allclose(results[i], expected[i * 8:(i + 1) * 8], atol=1e-6), i


def test_parallel_inference_sequential():
    net = _net()
    x, _ = _data(16)
    pi = ParallelInference(net, inference_mode="sequential")
    out = pi.output(x)
    assert np.allclose(out, np.asarray(net.output(x)), atol=1e-6)


# ------------------------------------------- ParallelInference regressions
class _RecordingNet:
    """Stub with the one method ParallelInference needs; records every
    merged batch size it is asked to serve."""

    def __init__(self, block_event=None):
        self.batch_sizes = []
        self._block = block_event

    def output(self, x):
        if self._block is not None:
            self._block.wait(10.0)
        self.batch_sizes.append(x.shape[0])
        return np.asarray(x) * 2.0


def test_parallel_inference_never_exceeds_batch_limit():
    """Regression: the dispatch loop checked `total < batch_limit` BEFORE
    popping but appended whatever it popped, so merged batches could
    overshoot the limit. Overflow requests must be deferred, not merged."""
    import time as _time
    gate = threading.Event()
    stub = _RecordingNet(block_event=gate)
    pi = ParallelInference(stub, batch_limit=8, max_wait_ms=50.0)
    xs = [np.full((5, 3), float(i), np.float32) for i in range(4)]
    results = {}

    def worker(i):
        results[i] = pi.output(xs[i])

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
        _time.sleep(0.02)        # deterministic arrival order
    gate.set()                   # release the first dispatch
    for t in threads:
        t.join(timeout=10)
        assert not t.is_alive()
    pi.shutdown()
    assert stub.batch_sizes, "nothing dispatched"
    assert max(stub.batch_sizes) <= 8, stub.batch_sizes
    assert sum(stub.batch_sizes) == 20  # every row served exactly once
    for i in range(4):
        assert np.allclose(results[i], xs[i] * 2.0), i


def test_parallel_inference_shutdown_contract():
    """Regression: output() after shutdown() used to enqueue a request no
    worker would ever serve (caller hung forever), and shutdown() never
    resolved queued requests. Now: post-shutdown submit raises, and every
    pending request is resolved (served or failed) — nobody hangs."""
    import time as _time
    gate = threading.Event()
    stub = _RecordingNet(block_event=gate)
    pi = ParallelInference(stub, batch_limit=4, max_wait_ms=1.0)
    outcomes = []

    def worker():
        try:
            outcomes.append(("ok", pi.output(np.ones((2, 3), np.float32))))
        except RuntimeError as e:
            outcomes.append(("err", e))

    threads = [threading.Thread(target=worker) for _ in range(3)]
    for t in threads:
        t.start()
    _time.sleep(0.05)            # first batch blocked on the gate, rest queued
    shut = threading.Thread(target=pi.shutdown)
    shut.start()
    _time.sleep(0.05)
    gate.set()                   # release the in-flight batch
    shut.join(timeout=10)
    assert not shut.is_alive(), "shutdown() hung"
    for t in threads:
        t.join(timeout=10)
        assert not t.is_alive(), "caller left hanging across shutdown()"
    assert len(outcomes) == 3    # every caller resolved, one way or another
    with pytest.raises(RuntimeError, match="shut down"):
        pi.output(np.ones((1, 3), np.float32))


def test_model_server_status_codes_and_drain_health():
    """Regression: do_POST collapsed every failure to 400. Malformed
    payloads are 400, model-side failures 500; /health reports queue depth
    and 503 while draining."""
    import json as _json
    import urllib.error
    import urllib.request
    from deeplearning4j_tpu.parallel.model_server import ModelServingServer

    class _FlakyNet:
        def __init__(self):
            self.fail = False

        def output(self, x):
            if self.fail:
                raise RuntimeError("device-side boom")
            return np.asarray(x) * 2.0

    net = _FlakyNet()
    srv = ModelServingServer(net, batched=False)
    port = srv.start()
    base = f"http://127.0.0.1:{port}"

    def post(payload_bytes):
        req = urllib.request.Request(f"{base}/predict", payload_bytes,
                                     {"Content-Type": "application/json"})
        return urllib.request.urlopen(req, timeout=10)

    try:
        # happy path
        body = _json.dumps({"features": [[1.0, 2.0]]}).encode()
        assert post(body).status == 200
        # malformed JSON -> 400
        with pytest.raises(urllib.error.HTTPError) as ei:
            post(b"{nope")
        assert ei.value.code == 400
        # missing/bad features -> 400
        with pytest.raises(urllib.error.HTTPError) as ei:
            post(_json.dumps({"features": [["a"]]}).encode())
        assert ei.value.code == 400
        # model-side failure -> 500
        net.fail = True
        with pytest.raises(urllib.error.HTTPError) as ei:
            post(body)
        assert ei.value.code == 500
        net.fail = False
        # health: ok + queue depth
        with urllib.request.urlopen(f"{base}/health", timeout=10) as r:
            h = _json.loads(r.read())
        assert h["status"] == "ok" and h["queue_depth"] == 0
        # draining -> 503 on health AND predict
        srv._draining = True
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(f"{base}/health", timeout=10)
        assert ei.value.code == 503
        assert _json.loads(ei.value.read())["status"] == "draining"
        with pytest.raises(urllib.error.HTTPError) as ei:
            post(body)
        assert ei.value.code == 503
        srv._draining = False
    finally:
        srv.stop()
