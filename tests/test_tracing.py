"""Distributed request tracing (ISSUE 13 tentpole): TraceContext
propagation from HTTP ingress through admission, batching, prefill and
every decode step, span/event trace-id stamping, explicit cross-thread
handoff, and the per-request reconstruction tools.

Acceptance pinned here:
- a generation request submitted over HTTP with ``X-Trace-Id`` yields
  spans/events carrying that id across ingress, admission, prefill and
  every decode step it participated in, reconstructable by
  tools/trace2timeline.py (and the header is echoed on the response);
- span-stack integrity: exception unwinding restores the parent span,
  and cross-thread handoff via the context helpers never attributes a
  child to the wrong parent (threaded stress);
- tools/trace2summary.py accepts gzipped traces and --trace-id filters.
"""
import gzip
import json
import threading
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu import telemetry
from deeplearning4j_tpu.telemetry import (MetricsRegistry, adopt,
                                          current_span_path,
                                          current_trace_context, event,
                                          handoff, new_trace_context, span,
                                          use_trace_context)


@pytest.fixture
def fresh_registry():
    reg = MetricsRegistry(enabled=True)
    prev = telemetry.set_registry(reg)
    try:
        yield reg
    finally:
        telemetry.set_registry(prev)


# ------------------------------------------------------------- context core
def test_trace_context_normalizes_and_validates_header_ids():
    ctx = new_trace_context("AABB-CCDD-00112233445566778899aabbcc")
    assert ctx.trace_id == "aabbccdd00112233445566778899aabbcc"
    # junk (non-hex / too short) -> fresh 128-bit id, never echoed junk
    for bad in ("not hex!", "abc", "", None, "<script>"):
        ctx = new_trace_context(bad)
        assert len(ctx.trace_id) == 32
        assert all(c in "0123456789abcdef" for c in ctx.trace_id)
    a, b = new_trace_context(), new_trace_context()
    assert a.trace_id != b.trace_id
    assert a.span_id != b.span_id


def test_use_trace_context_scopes_and_restores():
    assert current_trace_context() is None
    ctx = new_trace_context()
    with use_trace_context(ctx):
        assert current_trace_context() is ctx
        inner = new_trace_context()
        with use_trace_context(inner):
            assert current_trace_context() is inner
        assert current_trace_context() is ctx
        with use_trace_context(None):        # explicit deactivation
            assert current_trace_context() is None
        assert current_trace_context() is ctx
    assert current_trace_context() is None


# ----------------------------------------------------------- span stamping
def test_spans_and_events_stamp_active_trace_id(fresh_registry):
    reg = fresh_registry
    ctx = new_trace_context()
    with use_trace_context(ctx):
        with span("work", k=1):
            event("milestone", n=3)
    with span("untraced"):
        pass
    by_name = {e["name"]: e for e in reg.trace_events()}
    assert by_name["work"]["args"]["trace_id"] == ctx.trace_id
    assert by_name["milestone"]["args"]["trace_id"] == ctx.trace_id
    assert by_name["milestone"]["args"]["path"] == "work"
    assert by_name["milestone"]["ph"] == "i"
    assert "trace_id" not in by_name["untraced"]["args"]


def test_event_explicit_trace_id_override_and_disabled_noop(fresh_registry):
    reg = fresh_registry
    with use_trace_context(new_trace_context()):
        event("multi", trace_id="feedbeef", slot=2)
    assert reg.trace_events()[0]["args"]["trace_id"] == "feedbeef"
    reg.enabled = False
    event("nothing")
    reg.enabled = True
    assert len(reg.trace_events()) == 1


def test_record_external_span_stamps_trace_id(fresh_registry):
    from deeplearning4j_tpu.telemetry import record_external_span
    ctx = new_trace_context()
    with use_trace_context(ctx):
        record_external_span("collective", 1.5, cat="collective", bucket=0)
    ev = fresh_registry.trace_events()[0]
    assert ev["args"]["trace_id"] == ctx.trace_id


# ----------------------------------------------- span-stack integrity (sat)
def test_exception_unwinding_restores_parent_span(fresh_registry):
    with span("outer"):
        try:
            with span("inner"):
                raise RuntimeError("boom")
        except RuntimeError:
            pass
        assert current_span_path() == "outer"
        with span("after"):
            assert current_span_path() == "outer/after"
    assert current_span_path() == ""


def test_handoff_adopt_isolates_consumer_stack(fresh_registry):
    reg = fresh_registry
    with use_trace_context(new_trace_context()) as ctx:
        with span("producer"):
            token = handoff()
    results = {}

    def worker():
        # the worker has its OWN unrelated span open
        with span("worker_idle"):
            with adopt(token):
                assert current_trace_context() is token.ctx
                with span("child"):
                    results["path"] = current_span_path()
            # adopt restored the worker's own stack
            results["after"] = current_span_path()

    t = threading.Thread(target=worker)
    t.start()
    t.join()
    assert results["path"] == "producer/child"
    assert results["after"] == "worker_idle"
    child = [e for e in reg.trace_events() if e["name"] == "child"][0]
    assert child["args"]["path"] == "producer/child"
    assert child["args"]["trace_id"] == ctx.trace_id


def test_threaded_handoff_stress_never_misattributes(fresh_registry):
    """Tier-1 stress (satellite): many producers enqueue work carrying
    handoff tokens; a small worker pool adopts and opens spans. Every
    resulting span event must carry ITS producer's trace id and parent
    path — never a sibling's."""
    import queue
    reg = fresh_registry
    n_producers, n_items, n_workers = 8, 25, 4
    q: "queue.Queue" = queue.Queue()
    expected = {}                     # item id -> trace id

    def producer(pi):
        ctx = new_trace_context()
        with use_trace_context(ctx):
            with span(f"producer{pi}"):
                for j in range(n_items):
                    item = (pi, j)
                    expected[item] = ctx.trace_id
                    q.put((item, handoff()))

    producers = [threading.Thread(target=producer, args=(pi,))
                 for pi in range(n_producers)]
    for t in producers:
        t.start()
    for t in producers:
        t.join()
    for _ in range(n_workers):
        q.put(None)

    def worker():
        while True:
            got = q.get()
            if got is None:
                return
            item, token = got
            with adopt(token):
                with span("consume", pi=item[0], j=item[1]):
                    pass

    workers = [threading.Thread(target=worker) for _ in range(n_workers)]
    for t in workers:
        t.start()
    for t in workers:
        t.join()

    consumed = [e for e in reg.trace_events() if e["name"] == "consume"]
    assert len(consumed) == n_producers * n_items
    for e in consumed:
        item = (e["args"]["pi"], e["args"]["j"])
        assert e["args"]["trace_id"] == expected[item], \
            f"item {item} attributed to the wrong trace"
        assert e["args"]["path"] == f"producer{item[0]}/consume", \
            f"item {item} parented under the wrong span"


# ----------------------------------------------------------- jsonl + tools
def test_write_trace_jsonl_and_trace_id_filter(fresh_registry, tmp_path):
    reg = fresh_registry
    a, b = new_trace_context(), new_trace_context()
    for ctx, name in ((a, "req_a"), (b, "req_b")):
        with use_trace_context(ctx):
            with span(name):
                event("tick")
    full = reg.write_trace_jsonl(str(tmp_path / "all.jsonl"))
    events = [json.loads(ln) for ln in open(full)]
    assert len(events) == 4
    only_a = reg.write_trace_jsonl(str(tmp_path / "a.jsonl"),
                                   trace_id=a.trace_id)
    got = [json.loads(ln) for ln in open(only_a)]
    assert {e["args"]["trace_id"] for e in got} == {a.trace_id}
    assert {e["name"] for e in got} == {"req_a", "tick"}


def test_trace2summary_gzip_and_trace_id_filter(fresh_registry, tmp_path,
                                                capsys):
    """Satellite regression: gzipped trace files load, --trace-id folds
    one request, --top still bounds the table (recorded fixture built
    from a real span run)."""
    import os
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from tools.trace2summary import filter_trace_id, load_events, main
    reg = fresh_registry
    ids = []
    for i in range(3):
        ctx = new_trace_context()
        ids.append(ctx.trace_id)
        with use_trace_context(ctx):
            with span("request", i=i):
                with span("phase"):
                    pass
    # fixture: gzipped JSONL
    gz = tmp_path / "trace.jsonl.gz"
    with gzip.open(gz, "wt") as f:
        for e in reg.trace_events():
            f.write(json.dumps(e) + "\n")
    events = load_events(str(gz))
    assert len(events) == 6
    only = filter_trace_id(events, ids[1])
    assert len(only) == 2
    assert all(e["args"]["trace_id"] == ids[1] for e in only)
    # dashes/case in the CLI-provided id are normalized
    pretty = ids[1][:8] + "-" + ids[1][8:].upper()
    assert len(filter_trace_id(events, pretty)) == 2
    assert main([str(gz), "--trace-id", ids[1], "--top", "5"]) == 0
    out = capsys.readouterr().out
    assert "request" in out and "phase" in out


def test_trace2timeline_reconstruction_and_cli(fresh_registry, tmp_path,
                                               capsys):
    import os
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from tools.trace2timeline import (format_timeline, list_traces, main,
                                      timeline)
    from tools.trace2summary import load_events
    reg = fresh_registry
    ctx = new_trace_context()
    with use_trace_context(ctx):
        event("ingress", route="/generate")
        with span("prefill", rung=32):
            pass
        event("decode_step", token_index=1)
    path = reg.write_trace_jsonl(str(tmp_path / "t.jsonl"))
    events = load_events(path)
    listing = list_traces(events)
    assert listing[0]["trace_id"] == ctx.trace_id
    assert listing[0]["events"] == 3
    rows = timeline(events, ctx.trace_id)
    assert [r["name"] for r in rows] == ["ingress", "prefill",
                                        "decode_step"]
    assert rows[0]["t_ms"] == 0.0                  # relative to first event
    assert rows[1]["dur_ms"] is not None           # spans carry duration
    assert "route=/generate" in rows[0]["detail"]
    assert "prefill" in format_timeline(rows)
    assert main([path, "--list"]) == 0
    assert ctx.trace_id in capsys.readouterr().out
    assert main([path, "--trace-id", ctx.trace_id]) == 0
    assert "decode_step" in capsys.readouterr().out
    assert main([path, "--trace-id", "0" * 32]) == 1   # unknown id


# -------------------------------------------------- serving path (batcher)
def test_predict_under_context_emits_admit_and_batch_events(fresh_registry):
    from deeplearning4j_tpu import MultiLayerNetwork, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers.core import DenseLayer, OutputLayer
    from deeplearning4j_tpu.optimize.updaters import Sgd
    from deeplearning4j_tpu.serving import InferenceEngine
    conf = (NeuralNetConfiguration(seed=31, updater=Sgd(0.1))
            .list(DenseLayer(n_in=4, n_out=8, activation="tanh"),
                  OutputLayer(n_out=3, activation="softmax", loss="mcxent"))
            .build())
    net = MultiLayerNetwork(conf).init()
    eng = InferenceEngine(net, feature_shape=(4,), buckets=(4,),
                          batch_window_ms=0.5)
    try:
        x = np.random.default_rng(1).normal(size=(2, 4)).astype(np.float32)
        ctx = new_trace_context()
        with use_trace_context(ctx):
            eng.predict(x)
        eng.predict(x)                       # untraced: no events
    finally:
        eng.stop()
    evs = [e for e in fresh_registry.trace_events()
           if e["args"].get("trace_id") == ctx.trace_id]
    names = [e["name"] for e in evs]
    assert "serving.admit" in names
    assert "serving.batch" in names          # stamped from dispatch thread
    batch = [e for e in evs if e["name"] == "serving.batch"][0]
    assert batch["args"]["rows"] == 2
    assert "queue_ms" in batch["args"]
    untraced = [e for e in fresh_registry.trace_events()
                if e["name"] == "serving.batch"
                and "trace_id" not in e["args"]]
    assert not untraced                      # untraced caller -> no event


# ------------------------------------------------------------ solver + fit
def test_fit_spans_share_one_trace_id(fresh_registry, rng):
    from deeplearning4j_tpu import MultiLayerNetwork, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers.core import DenseLayer, OutputLayer
    from deeplearning4j_tpu.optimize.updaters import Sgd
    from deeplearning4j_tpu.datasets.dataset import ListDataSetIterator
    conf = (NeuralNetConfiguration(seed=12, updater=Sgd(0.1))
            .list(DenseLayer(n_in=4, n_out=8, activation="tanh"),
                  OutputLayer(n_out=3, activation="softmax", loss="mcxent"))
            .build())
    net = MultiLayerNetwork(conf).init()
    x = rng.normal(size=(16, 4)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, size=16)]
    net.fit(iterator=ListDataSetIterator(features=x, labels=y,
                                         batch_size=8),
            epochs=1, async_prefetch=False)
    spans_ = [e for e in fresh_registry.trace_events()
              if e.get("cat") == "span"]
    ids = {e["args"].get("trace_id") for e in spans_}
    assert len(ids) == 1 and None not in ids    # one fresh id per fit
    # a caller-provided context wins over the per-fit fresh one
    ctx = new_trace_context()
    with use_trace_context(ctx):
        net.fit(iterator=ListDataSetIterator(features=x, labels=y,
                                             batch_size=8),
                epochs=1, async_prefetch=False)
    fit_spans = [e for e in fresh_registry.trace_events()
                 if e["name"] == "fit"]
    assert fit_spans[-1]["args"]["trace_id"] == ctx.trace_id


# --------------------------------------------- HTTP end-to-end (acceptance)
def test_http_generation_trace_end_to_end(fresh_registry, tmp_path):
    """THE acceptance path: X-Trace-Id in -> echoed out, and the id rides
    ingress, admission, prefill and every decode step, reconstructable
    with trace2timeline."""
    import os
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from tools.trace2summary import load_events
    from tools.trace2timeline import timeline
    from deeplearning4j_tpu.models.zoo_extra import transformer_lm
    from deeplearning4j_tpu.serving import (GenerationEngine,
                                            ServingHTTPServer)
    net = transformer_lm(vocab_size=29, d_model=16, n_heads=2, n_blocks=1,
                         max_length=32, seed=7, dtype="float32",
                         token_input=True).init()
    eng = GenerationEngine(net, model_name="lm", block_len=8,
                           max_seq_len=32, decode_slots=2,
                           prefill_batches=(1,), prompt_rungs=(32,))
    srv = ServingHTTPServer(generation=eng)
    base = f"http://127.0.0.1:{srv.start()}"
    wire_id = "AABB-ccdd00112233445566778899aabbcc"
    want_id = "aabbccdd00112233445566778899aabbcc"
    try:
        req = urllib.request.Request(
            base + "/generate",
            json.dumps({"prompt": [3, 5, 7], "max_tokens": 6,
                        "stream": False}).encode(),
            {"Content-Type": "application/json", "X-Trace-Id": wire_id})
        with urllib.request.urlopen(req, timeout=30) as r:
            assert r.headers.get("X-Trace-Id") == want_id   # echoed out
            body = json.loads(r.read())
        assert len(body["tokens"]) == 6
        # a response without an inbound id still carries a generated one
        req2 = urllib.request.Request(
            base + "/generate",
            json.dumps({"prompt": [2, 4], "max_tokens": 2,
                        "stream": False}).encode(),
            {"Content-Type": "application/json"})
        with urllib.request.urlopen(req2, timeout=30) as r:
            gen_id = r.headers.get("X-Trace-Id")
        assert gen_id and len(gen_id) == 32 and gen_id != want_id
    finally:
        srv.stop()
    path = fresh_registry.write_trace_jsonl(str(tmp_path / "t.jsonl"),
                                            trace_id=want_id)
    names = [json.loads(ln)["name"] for ln in open(path)]
    assert names[0] == "http.request"                       # ingress
    assert "generation.submit" in names
    assert "generation.admit" in names                      # admission
    assert "generation.prefill" in names                    # prefill
    # 6 tokens = 1 from prefill + 5 decode steps, every one stamped
    assert names.count("generation.decode_step") == 5
    assert "generation.finish" in names
    # reconstructable per-request view, in causal order
    rows = timeline(load_events(str(tmp_path / "t.jsonl")), want_id)
    order = [r["name"] for r in rows]
    assert order.index("http.request") < order.index("generation.admit") \
        < order.index("generation.prefill") \
        < order.index("generation.decode_step")
