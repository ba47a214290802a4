"""Unified telemetry (ISSUE 4 tentpole): MetricsRegistry + structured
spans + jax signal capture, wired through the training/prefetch/serving
hot paths WITHOUT adding device syncs.

Acceptance contracts pinned here:
- a short fused-window run produces a Chrome-trace whose spans nest
  fit -> epoch -> window (-> dispatch), with XLA compile events attributed
  to the span they happened under;
- RecompileDetector flags an intentionally shape-unstable loop (naming
  the offending span path) while the warmed serving path stays at zero;
- the instrumented fit path performs ZERO extra device->host transfers vs
  uninstrumented (score_to_float counting harness from test_scan_window +
  the HostSyncDetector tripwire), and a disabled registry is a near-no-op;
- each instrument (registry spans, fit tracing + training watch, perf
  accounting, request tracing + SLO watchdog, fleet collector + spool)
  leaves the workload's outputs
  bit-equal to a run without it, and leaves a record of its own.
"""
import contextlib
import json
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import (MultiLayerNetwork, NeuralNetConfiguration,
                                telemetry)
from deeplearning4j_tpu.datasets.dataset import ListDataSetIterator
from deeplearning4j_tpu.nn.layers.core import DenseLayer, OutputLayer
from deeplearning4j_tpu.optimize.listeners import (
    CollectScoresIterationListener, PerformanceListener,
    ScoreIterationListener)
from deeplearning4j_tpu.optimize.updaters import Adam, Sgd
from deeplearning4j_tpu.telemetry import (HostSyncDetector, HostSyncError,
                                          MetricsRegistry, RecompileDetector,
                                          current_span_path, span)


@pytest.fixture
def fresh_registry():
    """Isolate each test in its own enabled registry (the built-in
    instrumentation resolves get_registry() live, so swapping works in
    any test order — the reversed-order harness included)."""
    reg = MetricsRegistry(enabled=True)
    prev = telemetry.set_registry(reg)
    try:
        yield reg
    finally:
        telemetry.set_registry(prev)


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


# ------------------------------------------------------------- registry core
def test_registry_counters_gauges_histograms(fresh_registry):
    reg = fresh_registry
    reg.counter("c").inc()
    reg.counter("c").inc(4)
    reg.gauge("g").set(2.0)
    reg.gauge("g").set(1.0)
    for v in range(100):
        reg.histogram("h_ms").observe(float(v))
    snap = reg.snapshot()
    assert snap["counters"]["c"] == 5
    assert snap["gauges"]["g"] == {"value": 1.0, "max": 2.0}
    h = snap["histograms"]["h_ms"]
    assert h["count"] == 100 and h["p50"] == 50.0
    # nearest-rank on 0..99: round(q * 99)
    assert h["p95"] == 94.0 and h["p99"] == 98.0
    # same-name accessors return the same object (cheap hot-path lookups)
    assert reg.counter("c") is reg.counter("c")


def test_registry_prometheus_dump(fresh_registry):
    reg = fresh_registry
    reg.counter("train.iterations").inc(7)
    reg.gauge("prefetch.queue_depth").set(3)
    reg.histogram("serving.default.latency_ms").observe(4.0)
    text = reg.to_prometheus_text()
    assert "# TYPE dl4j_tpu_train_iterations counter" in text
    assert "dl4j_tpu_train_iterations 7" in text
    assert "dl4j_tpu_prefetch_queue_depth 3" in text
    # ISSUE 13: conformant histogram exposition — _bucket with le labels
    assert "# TYPE dl4j_tpu_serving_default_latency_ms histogram" in text
    assert 'dl4j_tpu_serving_default_latency_ms_bucket{le="5"} 1' in text
    assert 'dl4j_tpu_serving_default_latency_ms_bucket{le="2.5"} 0' in text
    assert 'dl4j_tpu_serving_default_latency_ms_bucket{le="+Inf"} 1' in text
    assert "dl4j_tpu_serving_default_latency_ms_count 1" in text
    # the pre-ISSUE-13 ad-hoc quantile keys survive under the compat flag
    compat = reg.to_prometheus_text(compat_quantiles=True)
    assert 'dl4j_tpu_serving_default_latency_ms{quantile="0.99"} 4.0' \
        in compat
    assert "_bucket" not in compat


def test_trace_seq_cursoring(fresh_registry):
    """ISSUE 19: every recorded event carries a monotonic ``seq`` and
    ``trace_events_since`` returns only the delta — the incremental-pull
    contract the replica's /debug/trace route and the fleet collector's
    cursors are built on."""
    reg = fresh_registry
    assert reg.last_seq == 0
    with span("a"):
        pass
    with span("b"):
        pass
    events = reg.trace_events()
    seqs = [e["seq"] for e in events]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
    cursor = seqs[0]
    delta = reg.trace_events_since(cursor)
    assert [e["seq"] for e in delta] == [s for s in seqs if s > cursor]
    assert reg.trace_events_since(reg.last_seq) == []
    # a stale (pre-ring) cursor returns the whole ring, never raises
    assert len(reg.trace_events_since(-1)) == len(events)


def test_trace_events_since_while_other_threads_record(fresh_registry):
    """A reader pulls the ring while the serving loop still records into
    it (the benchmark does, at the window's end; the fleet collector does,
    all day): a deque that is appended to while Python code iterates it
    raises ``RuntimeError: deque mutated during iteration``. Seen on the
    chip once the long-prompt cell recorded a quarter more events a second
    (PR 33)."""
    import sys
    import threading
    import time
    reg = fresh_registry
    for i in range(20000):                # a ring worth walking
        reg.record_event({"name": "old", "ph": "i", "ts": i})
    stop = threading.Event()

    def writer():
        while not stop.is_set():
            reg.record_event({"name": "new", "ph": "i", "ts": 0})

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    t = threading.Thread(target=writer, daemon=True)
    t.start()
    try:
        deadline = time.monotonic() + 1.5
        pulls = 0
        while time.monotonic() < deadline:
            cursor = reg.last_seq - 100
            delta = reg.trace_events_since(cursor)
            assert all(e["seq"] > cursor for e in delta)
            pulls += 1
    finally:
        stop.set()
        t.join(timeout=10.0)
        sys.setswitchinterval(interval)
    assert not t.is_alive() and pulls > 10


def test_raw_metrics_round_trips_histogram_buckets(fresh_registry):
    """raw_metrics() is the mergeable wire format: cumulative buckets on
    the canonical ladder, counter values, gauge value+max."""
    reg = fresh_registry
    reg.counter("c").inc(3)
    reg.gauge("g").set(2.5)
    h = reg.histogram("lat_ms")
    for v in (1.0, 4.0, 900.0):
        h.observe(v)
    raw = reg.raw_metrics()
    assert raw["counters"]["c"] == 3
    assert raw["gauges"]["g"]["value"] == 2.5
    hr = raw["histograms"]["lat_ms"]
    assert hr["count"] == 3 and hr["cumulative"][-1] == 3
    assert hr["bounds"] == list(h.bounds)
    # cumulative is monotone non-decreasing
    assert all(a <= b for a, b in zip(hr["cumulative"],
                                      hr["cumulative"][1:]))


def test_trace_spool_round_trip_and_skip(fresh_registry, tmp_path):
    """The crash-durable black box: flush writes an atomic, parseable
    spill of ring tail + raw metrics; an unchanged ring skips the disk
    write; stop() force-flushes the final state."""
    from deeplearning4j_tpu.telemetry import TraceSpool, read_spool
    reg = fresh_registry
    path = str(tmp_path / "replica-r7.spool.json")
    spool = TraceSpool(path, replica_id="r7", registry=reg, capacity=4)
    with span("work"):
        reg.counter("done").inc()
    assert spool.flush() is True
    spill = read_spool(path)
    assert spill["replica"] == "r7" and spill["seq"] == reg.last_seq
    assert spill["metrics"]["counters"]["done"] == 1
    assert [e["name"] for e in spill["events"]] == ["work"]
    # no ring advance -> flush is a no-op (idle replicas cost zero I/O)
    assert spool.flush() is False and spool.skipped == 1
    for i in range(8):
        with span(f"s{i}"):
            pass
    assert spool.flush() is True
    spill = read_spool(path)
    assert len(spill["events"]) == 4         # capacity bounds the tail
    assert spill["events"][-1]["name"] == "s7"
    # absent / garbage files read as None, never raise
    assert read_spool(str(tmp_path / "nope.json")) is None
    (tmp_path / "junk.json").write_text("{not json")
    assert read_spool(str(tmp_path / "junk.json")) is None


def test_registry_stats_storage_bridge(fresh_registry):
    from deeplearning4j_tpu.ui import InMemoryStatsStorage
    reg = fresh_registry
    reg.counter("jax.compiles").inc(2)
    store = InMemoryStatsStorage()
    snap = reg.publish(store, session_id="telemetry", worker_id="runtime")
    assert snap["counters"]["jax.compiles"] == 2
    got = store.get_latest_update("telemetry", "runtime")
    assert got["counters"]["jax.compiles"] == 2


def test_disabled_registry_is_near_noop(fresh_registry):
    reg = fresh_registry
    reg.enabled = False
    reg.counter("c").inc()
    reg.gauge("g").set(1.0)
    reg.histogram("h").observe(1.0)
    with span("nothing", k=1):
        pass
    reg.enabled = True
    snap = reg.snapshot()
    assert snap["counters"] == {} and snap["gauges"] == {}
    assert snap["histograms"] == {}
    assert reg.trace_events() == []
    # disabled span() returns the shared no-op (no allocation per call)
    reg.enabled = False
    assert span("a") is span("b")
    reg.enabled = True


# ------------------------------------- spans on the profiler's own clock
def _host_plane_event_names(trace_dir) -> set:
    """Names of the host planes' events in the ``.xplane.pb`` a
    ``jax.profiler`` capture wrote under ``trace_dir``."""
    import glob
    import os
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(str(trace_dir), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    return {e.name for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events}


def _profiled(trace_dir, body):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    return _host_plane_event_names(trace_dir)


def test_spans_are_events_of_the_profilers_host_plane(fresh_registry,
                                                      tmp_path):
    """While a profile is being taken every span is an annotation named
    by its PATH, in the same file as the runtime's own events."""
    def body():
        with span("outer24", k=1):
            with span("inner24"):
                jnp.ones((8, 8)).sum().block_until_ready()
        s = span("manual24").start()
        s.end()
    names = _profiled(tmp_path, body)
    assert {"outer24", "outer24/inner24", "manual24"} <= names
    # and the registry's own record is what it was
    assert [e["args"]["path"] for e in fresh_registry.trace_events()
            if e["cat"] == "span"] == ["outer24/inner24", "outer24",
                                       "manual24"]


def test_a_spans_named_attributes_ride_its_annotation(fresh_registry,
                                                        tmp_path):
    """``annotate`` names the attributes that also go to the span's
    ``TraceAnnotation`` as metadata: a reader of the ``.xplane.pb`` finds
    them as the event's stats (the serving loop's ``step``), and the
    event keeps the span's path as its name."""
    import glob
    import os
    from jax.profiler import ProfileData

    def body():
        with span("pass42", annotate=("step",), step=7, slots=3):
            with span("read42", annotate=("step",), step=6):
                pass
        with span("plain42", step=9):
            pass
    assert {"pass42", "pass42/read42", "plain42"} <= _profiled(tmp_path, body)
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    stats = {e.name: dict(e.stats)
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.endswith("42")}
    assert stats["pass42"] == {"step": 7}         # not ``slots``
    assert stats["pass42/read42"] == {"step": 6}
    assert stats["plain42"] == {}
    # the registry's events carry the attributes as they always did
    args = {e["name"]: e["args"] for e in fresh_registry.trace_events()}
    assert (args["pass42"]["step"], args["pass42"]["slots"]) == (7, 3)


def test_a_parent_span_sums_its_childrens_durations(fresh_registry):
    """``child_ms``: a caller that wants its callee's time reads the
    callee's own stopwatch."""
    with span("outer42") as outer:
        assert outer.child_ms is None
        with span("launch42") as a:
            pass
        with span("launch42") as b:
            pass
        with span("read42") as c:
            with span("deep42"):
                pass
    assert outer.child_ms == {
        "launch42": pytest.approx(a.dur_ms + b.dur_ms),
        "read42": pytest.approx(c.dur_ms)}
    assert set(c.child_ms) == {"deep42"} and a.child_ms is None
    assert sum(outer.child_ms.values()) <= outer.dur_ms
    fresh_registry.enabled = False
    with span("outer42") as off:
        with span("launch42"):
            pass
    fresh_registry.enabled = True
    assert off.child_ms is None


def test_disabled_registry_opens_no_annotation(fresh_registry, tmp_path):
    fresh_registry.enabled = False

    def body():
        with span("silent24"):
            with jax.profiler.TraceAnnotation("control24"):
                pass
    names = _profiled(tmp_path, body)
    fresh_registry.enabled = True
    assert "control24" in names          # the capture itself works
    assert not [n for n in names if "silent24" in n]


def test_span_keeps_its_duration_after_end(fresh_registry):
    with span("timed24") as s:
        pass
    (ev,) = fresh_registry.trace_events()
    assert s.dur_ms >= 0.0 and abs(ev["dur"] - s.dur_ms * 1000) <= 1.0
    h = fresh_registry.snapshot()["histograms"]["span.timed24_ms"]
    assert h["count"] == 1 and h["sum"] == pytest.approx(s.dur_ms, abs=1e-6)
    fresh_registry.enabled = False
    with span("timed24") as off:
        pass
    fresh_registry.enabled = True
    assert off.dur_ms == 0.0             # the shared no-op times nothing


# ------------------------------------------------------------------- spans
def test_span_nesting_and_paths(fresh_registry):
    reg = fresh_registry
    with span("outer", a=1):
        assert current_span_path() == "outer"
        with span("inner"):
            assert current_span_path() == "outer/inner"
        assert current_span_path() == "outer"
    assert current_span_path() == ""
    paths = [e["args"]["path"] for e in reg.trace_events()]
    assert paths == ["outer/inner", "outer"]     # children close first
    # spans auto-feed duration histograms
    assert reg.histogram("span.outer_ms").count == 1


def test_span_manual_start_end_tolerates_interleaving(fresh_registry):
    reg = fresh_registry
    # a manually-opened span (ProfilerListener pattern) survives lexical
    # spans opening and closing around it
    s = span("capture").start()
    with span("step"):
        pass
    s.end()
    names = [e["name"] for e in reg.trace_events()]
    assert names == ["step", "capture"]
    ev = {e["name"]: e for e in reg.trace_events()}
    assert ev["capture"]["args"]["path"] == "capture"
    assert ev["step"]["args"]["path"] == "capture/step"


def test_chrome_trace_file_format(fresh_registry, tmp_path):
    reg = fresh_registry
    with span("a"):
        with span("b"):
            pass
    path = reg.write_chrome_trace(str(tmp_path / "t.trace.json"))
    text = open(path).read()
    events = json.loads(text)                    # valid JSON array
    assert [e["name"] for e in events] == ["b", "a"]
    # one event per line (JSONL-style body: Perfetto + line tools friendly)
    body = [ln for ln in text.splitlines() if ln not in ("[", "]")]
    assert len(body) == 2
    for ln in body:
        json.loads(ln.rstrip(","))
    for e in events:                             # Chrome-trace complete events
        assert e["ph"] == "X" and "ts" in e and "dur" in e


# ----------------------------------------------- fit -> trace (acceptance)
def test_fused_fit_trace_nests_and_attributes_compiles(fresh_registry,
                                                       tmp_path, rng):
    """A short fused-window run: spans nest fit -> epoch -> window ->
    dispatch, compile events carry the span path they happened under, and
    the registry counts iterations/windows."""
    reg = fresh_registry
    x, y = _toy(rng)
    net = _tiny_net(updater=Adam(1e-2))
    net.fit(iterator=_it(x, y), epochs=2, steps_per_dispatch=4)

    events = json.load(open(reg.write_chrome_trace(
        str(tmp_path / "fit.trace.json"))))
    spans_ = [e for e in events if e.get("cat") == "span"]
    paths = {e["args"]["path"] for e in spans_}
    assert {"fit", "fit/epoch", "fit/epoch/window",
            "fit/epoch/window/dispatch"} <= paths
    by_name = {}
    for e in spans_:
        by_name.setdefault(e["name"], []).append(e)
    assert len(by_name["fit"]) == 1
    assert len(by_name["epoch"]) == 2
    assert len(by_name["window"]) == 4           # 8 batches / K=4, 2 epochs
    # parent spans contain their children in time (ts/dur nesting)
    fit_ev = by_name["fit"][0]
    for e in by_name["window"]:
        assert fit_ev["ts"] <= e["ts"]
        assert e["ts"] + e["dur"] <= fit_ev["ts"] + fit_ev["dur"] + 1000
    # the first window traced + compiled: events attributed to fit spans
    compiles = [e for e in events if e.get("cat") == "compile"]
    assert compiles, "no backend-compile events captured"
    assert any(e["args"]["path"].startswith("fit/epoch/window")
               for e in compiles)
    snap = reg.snapshot()
    assert snap["counters"]["train.iterations"] == 16
    assert snap["counters"]["train.windows"] == 4
    assert snap["counters"]["jax.compiles"] >= 1
    assert reg.histogram("span.dispatch_ms").count == 4


def test_parallel_wrapper_fit_emits_spans(fresh_registry, rng):
    from deeplearning4j_tpu.parallel.data_parallel import ParallelWrapper
    reg = fresh_registry
    x, y = _toy(rng)
    net = _tiny_net()
    ParallelWrapper(net, steps_per_dispatch=2).fit(_it(x, y, bs=16), epochs=1)
    paths = {e["args"]["path"] for e in reg.trace_events()
             if e.get("cat") == "span"}
    assert "fit/epoch/window/dispatch" in paths
    assert reg.snapshot()["counters"]["train.iterations"] == 4


def test_prefetch_reports_queue_and_stall(fresh_registry, rng):
    from deeplearning4j_tpu.datasets.prefetch import DevicePrefetchIterator
    reg = fresh_registry
    x, y = _toy(rng)
    it = DevicePrefetchIterator(_it(x, y), depth=2, dtype="float32")
    batches = list(it)
    assert len(batches) == 8
    snap = reg.snapshot()
    assert snap["counters"]["prefetch.batches"] == 8
    assert snap["histograms"]["prefetch.wait_ms"]["count"] == 8
    assert snap["histograms"]["prefetch.ship_ms"]["count"] == 8
    assert "prefetch.queue_depth" in snap["gauges"]


# -------------------------------------------------------- recompile detector
def test_recompile_detector_flags_shape_unstable_loop(fresh_registry,
                                                      caplog):
    """Acceptance: an intentionally shape-unstable loop is flagged, with
    the offending span path in the warning."""
    f = jax.jit(lambda a: (a * 2.0).sum())
    with caplog.at_level(logging.WARNING, logger="deeplearning4j_tpu"):
        with RecompileDetector(allowed=0) as det:
            with span("unstable_loop"):
                for n in (3, 4, 5):          # new shape -> retrace, each call
                    f(jnp.ones((n,), jnp.float32))
    assert det.count >= 3
    assert det.recompiles == det.count
    assert {e["span_path"] for e in det.events} == {"unstable_loop"}
    assert any("unstable_loop" in r.message for r in caplog.records)
    assert fresh_registry.snapshot()["counters"]["jax.compiles"] >= 3


def test_recompile_detector_scoped_and_stable_loop_clean(fresh_registry):
    g = jax.jit(lambda a: a + 1.0)
    g(jnp.ones((4,), jnp.float32))               # compile OUTSIDE the scope
    with RecompileDetector(warn=False) as det:
        for _ in range(5):
            g(jnp.ones((4,), jnp.float32))       # steady state: no traces
    assert det.count == 0


def test_serving_warm_path_zero_recompiles_under_detector(fresh_registry):
    """Steady-state serving through the warmed engine stays at ZERO
    compiles — now asserted via the first-class detector, not just the
    raw counter."""
    from deeplearning4j_tpu.serving import InferenceEngine
    net = _tiny_net(seed=31)
    rng = np.random.default_rng(5)
    sizes = [1, 3, 8, 5, 2, 8]
    for n in sizes:                              # warm net.output shapes
        net.output(rng.normal(size=(n, 4)).astype(np.float32))
    eng = InferenceEngine(net, feature_shape=(4,), buckets=(4, 8),
                          batch_window_ms=0.5)
    try:
        eng.predict(rng.normal(size=(3, 4)).astype(np.float32))  # settle
        with RecompileDetector(allowed=0) as det:
            for n in sizes:
                out = eng.predict(rng.normal(size=(n, 4)).astype(np.float32))
                assert out.shape == (n, 3)
        assert det.count == 0, det.events
    finally:
        eng.stop()


# -------------------------------------------------------- host-sync detector
def test_host_sync_detector_flags_readback_with_span_path(fresh_registry):
    with HostSyncDetector(action="count") as det:
        with span("fused_window"):
            v = jax.jit(lambda a: a.sum())(jnp.arange(4.0))
            float(v)                              # the accidental sync
    assert det.count == 1
    assert det.events[0]["span_path"] == "fused_window"
    assert fresh_registry.snapshot()["counters"]["jax.host_syncs_flagged"] == 1


def test_host_sync_detector_raise_mode(fresh_registry):
    with pytest.raises(HostSyncError, match="device->host"):
        with HostSyncDetector(action="raise"):
            float(jax.jit(lambda a: a.sum())(jnp.arange(3.0)))


def test_host_sync_detector_scope_and_cached_reads(fresh_registry):
    with HostSyncDetector(action="count"):
        pass              # the tripwire installs at the first arming (it
    # must see the pre-scope read below: the CPU backend keeps no host
    # cache of its own on jax 0.9.0, the tripwire's mark stands in)
    v = jax.jit(lambda a: a * 2.0)(jnp.arange(4.0))
    float(v.sum())                                # outside: not flagged
    w = jax.jit(lambda a: a * 3.0)(jnp.arange(4.0))
    wsum = w.sum()
    float(wsum)                                   # materialized BEFORE scope
    with HostSyncDetector(action="count") as det:
        float(wsum)                               # cached: no device sync
        float(wsum)
    assert det.count == 0
    with HostSyncDetector(action="count") as det:
        fresh = jax.jit(lambda a: a.sum() * 5.0)(jnp.arange(4.0))
        float(fresh)                              # first read: flagged
        float(fresh)                              # re-read: free
    assert det.count == 1


# ------------------------------------------------- sync-freedom (acceptance)
def test_instrumented_fit_adds_zero_host_syncs(fresh_registry, rng,
                                               monkeypatch):
    """The tier-1 sync-freedom contract: the INSTRUMENTED fit path (spans +
    counters live) performs zero score readbacks inside the loop (the
    score_to_float harness from test_scan_window) and zero device->host
    materializations (HostSyncDetector tripwire) — identical to a
    disabled-registry run, in both fused and per-step modes."""
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
        monkeypatch.setattr(L, "score_to_float", counting)
        for enabled in (True, False):
            fresh_registry.enabled = enabled
            for k in (1, 2):
                net = _tiny_net()
                collect = CollectScoresIterationListener()
                net.set_listeners(collect, ScoreIterationListener(2))
                # warm-up epoch first: jit tracing may legitimately touch
                # host values; the contract is about the steady-state loop
                net.fit(iterator=_it(x, y), epochs=1, steps_per_dispatch=k,
                        async_prefetch=False)
                calls["n"] = 0
                with HostSyncDetector(action="count") as det:
                    net.fit(iterator=_it(x, y), epochs=1,
                            steps_per_dispatch=k, async_prefetch=False)
                assert calls["n"] == 0, \
                    f"enabled={enabled} K={k}: {calls['n']} score readbacks"
                assert det.count == 0, \
                    f"enabled={enabled} K={k}: syncs at " \
                    f"{[e['span_path'] for e in det.events]}"
                assert len(collect.scores) == 8    # flush still works after
    finally:
        fresh_registry.enabled = True
        logger.setLevel(old)


# ----------------------------------------------- PerformanceListener fusion
def test_performance_listener_window_aligned_reports(fresh_registry):
    """K-fused accounting: a report falling due mid-window defers to the
    window's last step, every fused step is counted, and the record
    carries windowed_steps_per_sec + steps_per_dispatch. Log format is
    unchanged."""
    lst = PerformanceListener(frequency=2)
    it = 0
    for _ in range(2):                       # two windows of K=4
        lst.note_window(4)
        for _ in range(4):
            lst.note_batch(8, etl_wait_ms=0.5, device_ms=1.0)
            lst.iteration_done(None, it, 0.25)
            it += 1
    # iteration 2 was report-due mid-window -> deferred to window end (3);
    # iterations 4 and 6 due mid second window -> deferred to 7
    assert [r["iteration"] for r in lst.history] == [3, 7]
    r = lst.history[0]
    assert r["steps_per_dispatch"] == 4.0
    assert r["windowed_steps_per_sec"] == r["batches_per_sec"] > 0
    assert r["samples_per_sec"] > 0
    assert r["score"] == 0.25
    # shared-registry mirror
    snap = fresh_registry.snapshot()
    assert snap["gauges"]["train.steps_per_dispatch"]["value"] == 4.0
    assert snap["histograms"]["train.etl_wait_ms"]["count"] == 2


def test_performance_listener_per_step_reports_unchanged(fresh_registry):
    lst = PerformanceListener(frequency=2)
    for it in range(7):
        lst.note_batch(8, etl_wait_ms=0.1, device_ms=0.2)
        lst.iteration_done(None, it, 1.0)
    assert [r["iteration"] for r in lst.history] == [2, 4, 6]
    r = lst.history[-1]
    assert r["steps_per_dispatch"] == 1.0
    assert r["etl_wait_ms_per_iteration"] == pytest.approx(0.1)
    assert r["etl_ms_per_iteration"] == r["etl_wait_ms_per_iteration"]


def test_performance_listener_fused_fit_history(fresh_registry, rng):
    """End to end through the fused Solver path: history rows carry the
    fused-dispatch fields and samples/sec counts every fused step."""
    x, y = _toy(rng)
    net = _tiny_net()
    perf = PerformanceListener(frequency=4)
    net.set_listeners(perf)
    net.fit(iterator=_it(x, y), epochs=3, steps_per_dispatch=4,
            async_prefetch=False)
    assert perf.history, "no reports"
    for r in perf.history:
        assert r["steps_per_dispatch"] == 4.0
        assert r["windowed_steps_per_sec"] > 0


# ------------------------------------------------------ serving integration
def test_serving_metrics_mirror_into_registry(fresh_registry):
    from deeplearning4j_tpu.serving.metrics import ServingMetrics
    m = ServingMetrics(name="digits")
    m.record_request(4.2, rows=3)
    m.record_queue_wait(1.1)
    m.record_batch(bucket=8, rows=6)
    m.record_rejection("full")
    m.record_swap()
    snap = m.snapshot()                      # GET /metrics payload: stable
    assert snap["requests"] == 1 and snap["rows"] == 3
    assert set(snap) == {"requests", "rows", "batches", "latency_ms",
                         "queue_wait_ms", "batch_occupancy", "padding_waste",
                         "per_bucket", "rejected", "hot_swaps", "uptime_s"}
    reg = fresh_registry.snapshot()
    assert reg["counters"]["serving.digits.requests"] == 1
    assert reg["counters"]["serving.digits.rejected.full"] == 1
    assert reg["counters"]["serving.digits.hot_swaps"] == 1
    assert reg["histograms"]["serving.digits.latency_ms"]["count"] == 1
    assert reg["gauges"]["serving.digits.batch_occupancy"]["value"] == \
        pytest.approx(0.75)


def test_engine_metrics_reach_shared_registry(fresh_registry):
    from deeplearning4j_tpu.serving import InferenceEngine
    net = _tiny_net(seed=77)
    eng = InferenceEngine(net, feature_shape=(4,), buckets=(4,),
                          batch_window_ms=0.5)
    try:
        x = np.random.default_rng(1).normal(size=(2, 4)).astype(np.float32)
        eng.predict(x)
    finally:
        eng.stop()
    snap = fresh_registry.snapshot()
    assert snap["counters"]["serving.default.requests"] == 1
    assert snap["histograms"]["serving.default.latency_ms"]["count"] == 1
    # one surface: training-style prometheus dump carries serving p99
    assert "dl4j_tpu_serving_default_latency_ms" in \
        fresh_registry.to_prometheus_text()


# ------------------------------------------------------------ dashboard card
def test_dashboard_renders_telemetry_card(fresh_registry, rng):
    from deeplearning4j_tpu.ui import InMemoryStatsStorage, StatsListener
    from deeplearning4j_tpu.ui.dashboard import render_dashboard_html
    reg = fresh_registry
    reg.counter("jax.compiles").inc(3)
    reg.histogram("prefetch.wait_ms").observe(1.5)
    reg.histogram("serving.default.latency_ms").observe(9.0)
    store = InMemoryStatsStorage()
    net = _tiny_net()
    net.set_listeners(StatsListener(store, session_id="s"))
    x, y = _toy(rng, n=16)
    net.fit(x, y, epochs=1, batch_size=16)
    page = render_dashboard_html(store)
    assert "Runtime telemetry" in page
    assert "XLA compiles" in page
    assert "prefetch stall p95 (ms)" in page
    assert "serving p99 [default] (ms)" in page
    assert "train.iterations" in page            # fit's own counters render


def test_dashboard_without_telemetry_omits_card(fresh_registry):
    from deeplearning4j_tpu.ui import InMemoryStatsStorage
    from deeplearning4j_tpu.ui.dashboard import render_dashboard_html
    fresh_registry.enabled = False
    store = InMemoryStatsStorage()
    store.put_static_info("s", "w", {"a": 1})
    store.put_update("s", "w", {"iteration": 0, "score": 1.0})
    page = render_dashboard_html(store)
    assert "Runtime telemetry" not in page
    fresh_registry.enabled = True


# ----------------------------------------------------------- trace2summary
def test_trace2summary_folds_trace(fresh_registry, tmp_path, rng, capsys):
    import os
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from tools.trace2summary import format_table, load_events, main, summarize
    x, y = _toy(rng, n=32)
    net = _tiny_net()
    net.fit(iterator=_it(x, y), epochs=1, steps_per_dispatch=4,
            async_prefetch=False)
    path = fresh_registry.write_chrome_trace(str(tmp_path / "t.json"))
    rows = summarize(load_events(path))
    phases = {r["phase"]: r for r in rows}
    assert phases["fit/epoch/window"]["count"] == 1
    # share = phase total / trace wall window. A backend_compile event's
    # REPORTED duration can exceed its wall footprint (XLA compiles on
    # multiple threads), stretching the window past the fit span — so pin
    # the invariant, not an exact 1.0: fit dominates and never exceeds it.
    assert 0.3 < phases["fit"]["share"] <= 1.0
    # compile events fold into their own [backend_compile] bucket
    assert any("[backend_compile]" in p for p in phases)
    assert "fit/epoch/window" in format_table(rows)
    assert main([path, "--top", "3"]) == 0
    assert "phase" in capsys.readouterr().out
    # bare JSONL (no array brackets) loads too
    jsonl = tmp_path / "t.jsonl"
    jsonl.write_text("\n".join(json.dumps(e)
                               for e in fresh_registry.trace_events()))
    assert len(load_events(str(jsonl))) == len(fresh_registry.trace_events())


# ------------------------------------------------------- ProfilerListener
def test_profiler_listener_tolerates_active_trace(fresh_registry, tmp_path):
    """Regression (ISSUE 4 satellite): start_trace raising (another trace
    already active — jax allows one per process) must not propagate out of
    iteration_done or leave the listener half-armed."""
    from deeplearning4j_tpu.util.checkpointing import ProfilerListener
    jax.profiler.start_trace(str(tmp_path / "outer"))
    try:
        lst = ProfilerListener(str(tmp_path / "inner"), start_iteration=0,
                               n_iterations=2)
        lst.iteration_done(None, 0, 0.0)        # start_trace raises inside
        assert lst._done and not lst._active    # retired cleanly
        lst.iteration_done(None, 1, 0.0)        # inert afterwards
        lst.on_epoch_end(None)                  # must NOT stop the outer trace
    finally:
        jax.profiler.stop_trace()


def test_profiler_listener_capture_emits_span(fresh_registry, tmp_path):
    from deeplearning4j_tpu.util.checkpointing import ProfilerListener
    lst = ProfilerListener(str(tmp_path / "prof"), start_iteration=1,
                           n_iterations=2)
    for it in range(5):
        lst.iteration_done(None, it, 0.0)
    assert lst._done and not lst._active
    spans_ = [e for e in fresh_registry.trace_events()
              if e["name"] == "profiler_capture"]
    assert len(spans_) == 1
    assert spans_[0]["args"]["start_iteration"] == 1


def test_device_memory_gauges_smoke(fresh_registry):
    from deeplearning4j_tpu.telemetry import device_memory_gauges
    out = device_memory_gauges(fresh_registry)
    # CPU backend exposes no memory_stats; on real devices gauges appear
    for name, val in out.items():
        assert val >= 0
        assert fresh_registry.gauge(name).value == val


# ------------------------------------- instruments leave the results alone
def _fit_flat(k, rows=64):
    """The tiny MLP's parameters after two epochs at K steps a dispatch
    (a fresh net and the same data every call)."""
    x, y = _toy(np.random.default_rng(17), n=rows)
    net = _tiny_net(seed=44)
    net.fit(iterator=_it(x, y), epochs=2, steps_per_dispatch=k,
            async_prefetch=False)
    return np.asarray(net.params_flat())


def _registry_spans(on, reg, tmp_path):
    reg.enabled = on
    flat = _fit_flat(k=1)
    return flat, [e for e in reg.trace_events() if e.get("cat") == "span"]


def _tracing_and_watch(on, reg, tmp_path):
    from deeplearning4j_tpu.telemetry import (TrainingWatch,
                                              new_trace_context,
                                              set_training_watch,
                                              use_trace_context)
    reg.enabled = on
    if not on:
        return _fit_flat(k=8), None
    ctx = new_trace_context()
    watch = TrainingWatch(window=8, registry=reg, dump_on_unhealthy=False)
    set_training_watch(watch)
    try:
        with use_trace_context(ctx):
            flat = _fit_flat(k=8)
        assert watch.drain() and watch.healthy
        assert watch.steps_seen == 16
    finally:
        set_training_watch(None)
        watch.close()
    return flat, [e for e in reg.trace_events()
                  if e["args"].get("trace_id") == ctx.trace_id]


def _perf_accounting(on, reg, tmp_path):
    from deeplearning4j_tpu.telemetry.perf import (ProgramCostIndex,
                                                   set_cost_index)
    reg.enabled = on
    idx = ProgramCostIndex()
    prev = set_cost_index(idx)
    try:
        flat = _fit_flat(k=8, rows=256)     # four windows an epoch
    finally:
        set_cost_index(prev)
    if on:
        assert idx.get("fit/epoch/window") is not None
    return flat, {n: h["count"]
                  for n, h in reg.snapshot()["histograms"].items()
                  if n.startswith("perf.step.")}


@contextlib.contextmanager
def _served_tiny_net():
    """``post_all() -> outputs`` against the tiny MLP behind a real
    ServingHTTPServer: twelve traced /predict requests, rows 1/3/8/2."""
    from deeplearning4j_tpu.serving import InferenceEngine, ServingHTTPServer
    from deeplearning4j_tpu.util.httpjson import HTTPClient
    rng = np.random.default_rng(29)
    batches = [rng.normal(size=(n, 4)).astype(np.float32)
               for n in (1, 3, 8, 2)] * 3
    eng = InferenceEngine(_tiny_net(seed=44), feature_shape=(4,),
                          buckets=(4, 8), batch_window_ms=0.2)
    srv = ServingHTTPServer(engine=eng)
    url = f"http://127.0.0.1:{srv.start()}"
    client = HTTPClient(max_per_host=1, timeout=30.0)

    def post_all():
        outs = []
        for i, x in enumerate(batches):
            status, body = client.request_json(
                "POST", url + "/predict", payload={"features": x.tolist()},
                headers={"X-Trace-Id": f"{i + 1:032x}"})
            assert status == 200
            outs.append(np.asarray(body["output"], np.float32))
        return np.concatenate(outs)

    try:
        yield url, post_all
    finally:
        client.close()
        srv.stop()
        eng.stop(drain=False)


def _request_tracing_and_slo(on, reg, tmp_path):
    from deeplearning4j_tpu.telemetry import (LatencySLO, SLOWatchdog,
                                              set_slo_watchdog)
    reg.enabled = on
    wd = SLOWatchdog([LatencySLO("predict_p99", "serving.default.latency_ms",
                                 threshold_ms=60000.0, target=0.99)],
                     registry=reg, dump_on_breach=False)
    prev = set_slo_watchdog(wd if on else None)
    try:
        with _served_tiny_net() as (_, post_all):
            outs = post_all()
            checked = wd.check() if on else None
    finally:
        set_slo_watchdog(prev)
    if on:
        assert checked["objectives"]["predict_p99"]["good"] == 12
    ids = {f"{i + 1:032x}" for i in range(12)}
    return outs, [e for e in reg.trace_events()
                  if e["args"].get("trace_id") in ids]


def _fleet_collector_and_spool(on, reg, tmp_path):
    from deeplearning4j_tpu.serving.fleet import FleetCollector, FleetRouter
    from deeplearning4j_tpu.telemetry import TraceSpool, read_spool
    spool_path = str(tmp_path / "replica-b0.spool.json")
    router = FleetRouter(policy="round_robin", health_period_s=3600.0)
    collector = spool = None
    try:
        with _served_tiny_net() as (url, post_all):
            router.add_url(url, "b0")
            if on:
                collector = FleetCollector(
                    router, period_s=0.02,
                    registry=MetricsRegistry(enabled=True)).start()
                spool = TraceSpool(spool_path, replica_id="b0",
                                   period_s=0.02).start()
            outs = post_all()
            if not on:
                return outs, None
            collector.pull_once()
            spool.flush(force=True)
            snap = collector.snapshot()
            assert snap["pulls"] > 0 and snap["pull_errors"] == 0
            assert read_spool(spool_path)["events"]
            return outs, snap["events_pulled"]
    finally:
        if collector is not None:
            collector.stop()
        if spool is not None:
            spool.stop()
        router.client.close()


@pytest.mark.parametrize("instrument", [
    _registry_spans, _tracing_and_watch, _perf_accounting,
    _request_tracing_and_slo, _fleet_collector_and_spool],
    ids=lambda f: f.__name__.strip("_"))
def test_instrument_leaves_outputs_bit_equal(instrument, fresh_registry,
                                             tmp_path, monkeypatch):
    """What an instrument may cost is time, and time is read on the chip.
    What it may never do is change the answer: with it on, the workload's
    outputs are bit-equal to those with it off, and the instrument's own
    record is not empty."""
    monkeypatch.setenv("DL4J_TPU_PERF_CAPTURE_AFTER", "1")
    bare, _ = instrument(False, fresh_registry, tmp_path)
    with_it, record = instrument(True, fresh_registry, tmp_path)
    np.testing.assert_array_equal(with_it, bare)
    assert record, "the instrument ran and recorded nothing"
