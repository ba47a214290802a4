"""HTTP surface for generation: POST /generate streaming + error taxonomy.

Regression tests alongside the forward-serving 400/429/503/504 suite
(tests/test_serving_engine.py): per-token chunked NDJSON streaming,
block-pool exhaustion -> 429 with a retry hint, mid-stream deadline expiry
terminating the stream cleanly (no hung clients), draining -> 503, and
POST /reload hot-swapping a generation model with the in-flight-on-old,
admissions-on-new cutover rule.
"""
import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np

from deeplearning4j_tpu.models.decode import (TransformerDecodeSpec,
                                              naive_generate)
from deeplearning4j_tpu.models.zoo_extra import transformer_lm
from deeplearning4j_tpu.serving import GenerationEngine, ServingHTTPServer

R = np.random.default_rng(17)


def _lm(seed=7, vocab=29, max_length=32):
    return transformer_lm(vocab_size=vocab, d_model=16, n_heads=2,
                          n_blocks=1, max_length=max_length, seed=seed,
                          dtype="float32", token_input=True).init()


def _engine(net, **kw):
    cfg = dict(model_name="lm", block_len=8, max_seq_len=32, decode_slots=2,
               prefill_batches=(1,), prompt_rungs=(32,))
    cfg.update(kw)
    return GenerationEngine(net, **cfg)


def _post(base, path, payload, timeout=30):
    req = urllib.request.Request(base + path, json.dumps(payload).encode(),
                                 {"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def _stream(base, payload, timeout=30):
    """POST /generate with stream=true; returns the parsed NDJSON lines."""
    req = urllib.request.Request(base + "/generate",
                                 json.dumps(payload).encode(),
                                 {"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return [json.loads(line) for line in r if line.strip()]


def test_http_generate_stream_and_blocking():
    net = _lm()
    spec = TransformerDecodeSpec(net)
    eng = _engine(net)
    srv = ServingHTTPServer(generation=eng)
    base = f"http://127.0.0.1:{srv.start()}"
    try:
        prompt = [3, 5, 7]
        want = naive_generate(net, prompt, 6, pad_to=32, spec=spec)
        # stream: one {"token": id} line per token + a done terminator
        lines = _stream(base, {"prompt": prompt, "max_tokens": 6})
        toks = [l["token"] for l in lines if "token" in l]
        assert toks == want
        assert lines[-1] == {"done": True, "reason": "length", "tokens": 6}
        # blocking: single JSON body
        st, body = _post(base, "/generate",
                         {"prompt": prompt, "max_tokens": 6,
                          "stream": False})
        assert st == 200
        assert body["tokens"] == want
        assert body["reason"] == "length" and body["model"] == "lm"
        # observability routes expose the generation engine
        with urllib.request.urlopen(base + "/metrics", timeout=10) as r:
            m = json.loads(r.read())
        assert m["generation"]["lm"]["tokens_out"] >= 12
        with urllib.request.urlopen(base + "/models", timeout=10) as r:
            models = json.loads(r.read())
        assert models["generation"]["lm"]["adapter"] == "paged"
        with urllib.request.urlopen(base + "/health", timeout=10) as r:
            h = json.loads(r.read())
        assert h["generation_models"] == ["lm"]
    finally:
        srv.stop()


def test_http_generation_error_taxonomy():
    """400 malformed / 404 unknown model / 429 pool exhaustion with retry
    hint / 429 queue+pool saturation — the admission decisions surface as
    the right wire responses."""
    net = _lm(seed=9)
    eng = _engine(net, num_blocks=3, queue_limit=1, decode_slots=2)
    srv = ServingHTTPServer(generation=eng)
    base = f"http://127.0.0.1:{srv.start()}"
    try:
        st, body = _post(base, "/generate", {"prompt": "not-token-ids"})
        assert st == 400
        st, body = _post(base, "/generate", {})
        assert st == 400
        st, body = _post(base, "/generate/ghost", {"prompt": [1]})
        assert st == 404
        # over-capacity prompt+max_tokens -> 400 (shape taxonomy)
        st, body = _post(base, "/generate",
                         {"prompt": [1, 2], "max_tokens": 99,
                          "stream": False})
        assert st == 400
        # within capacity but needs more blocks than the pool HAS -> 429,
        # and since no retry can ever help, NO retry hint
        st, body = _post(base, "/generate",
                         {"prompt": [1, 2], "max_tokens": 28,
                          "stream": False})
        assert st == 429
        assert body["kind"] == "BlockPoolExhaustedError"
        assert "retry_after_ms" not in body
        # saturate: r1 holds both blocks, r2 queues, r3 -> 429. Decode is
        # slowed so r1 deterministically holds its blocks across the
        # submit sequence (the un-slowed window is a few ms — flaky under
        # suite load).
        rt = eng._get("lm")
        orig_decode = rt.active_ps.launch_decode    # the loop's launch

        def slow_decode(*a, **k):
            time.sleep(0.01)
            return orig_decode(*a, **k)

        rt.active_ps.launch_decode = slow_decode
        results = {}

        def bg(i):
            results[i] = _post(base, "/generate",
                               {"prompt": [i, i + 1], "max_tokens": 14,
                                "stream": False, "timeout_ms": 30000})

        t1 = threading.Thread(target=bg, args=(1,))
        t1.start()
        deadline = time.monotonic() + 5.0
        while eng.metrics()["lm"]["prefills"] < 1:
            assert time.monotonic() < deadline
            time.sleep(0.002)
        t2 = threading.Thread(target=bg, args=(2,))
        t2.start()
        deadline = time.monotonic() + 5.0      # wait until r2 is queued
        while eng.queue_depths()["lm"] < 1 and not results.get(2):
            assert time.monotonic() < deadline
            time.sleep(0.002)
        st, body = _post(base, "/generate",
                         {"prompt": [9, 10], "max_tokens": 14,
                          "stream": False})
        t1.join()
        t2.join()
        assert st == 429
        if body["kind"] == "BlockPoolExhaustedError":   # transient flavor
            assert "retry_after_ms" in body             # -> retry hint
        assert results[1][0] == 200 and results[2][0] == 200
        assert len(results[1][1]["tokens"]) == 14
        assert len(results[2][1]["tokens"]) == 14
    finally:
        srv.stop()


def test_http_keepalive_not_desynced_by_preparse_errors():
    """HTTP/1.1 keep-alive: a POST whose error response is written BEFORE
    the body is parsed (unknown route / missing engine) must still drain
    the body, or the unread bytes corrupt the NEXT request on the same
    connection."""
    import http.client
    eng = _engine(_lm(seed=19))
    srv = ServingHTTPServer(generation=eng)
    srv.start()
    try:
        conn = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=10)
        body = json.dumps({"features": [[1.0, 2.0]]})
        # generation-only server: /predict 404s before reading the body
        conn.request("POST", "/predict", body,
                     {"Content-Type": "application/json"})
        r = conn.getresponse()
        assert r.status == 404
        r.read()
        # same connection: must parse as a fresh request, not body residue
        conn.request("GET", "/health")
        r2 = conn.getresponse()
        assert r2.status == 200
        assert json.loads(r2.read())["status"] == "ok"
        # unknown POST route with a body, then reuse again
        conn.request("POST", "/nope", body,
                     {"Content-Type": "application/json"})
        r3 = conn.getresponse()
        assert r3.status == 404
        r3.read()
        conn.request("GET", "/models")
        r4 = conn.getresponse()
        assert r4.status == 200
        r4.read()
        conn.close()
    finally:
        srv.stop()


def test_http_midstream_deadline_terminates_cleanly():
    """A deadline expiring mid-stream ends the chunked response with a
    {"done": true, "reason": "deadline"} line — the client's read loop
    completes on its own, nobody hangs on a half-open stream."""
    net = _lm(seed=11, max_length=64)
    eng = _engine(net, max_seq_len=64, decode_slots=1,
                  prompt_rungs=(64,))
    srv = ServingHTTPServer(generation=eng)
    base = f"http://127.0.0.1:{srv.start()}"
    try:
        t0 = time.monotonic()
        # 5ms: small enough that a warm rig cannot emit all 60 tokens
        # (prefill alone approaches it) — the deadline must land before
        # "length" does, whatever the machine speed
        lines = _stream(base, {"prompt": [1, 2, 3], "max_tokens": 60,
                               "timeout_ms": 5}, timeout=15)
        elapsed = time.monotonic() - t0
        assert lines[-1]["done"] is True
        assert lines[-1]["reason"] == "deadline"
        ntok = len([l for l in lines if "token" in l])
        assert ntok < 60 and lines[-1]["tokens"] == ntok
        assert elapsed < 10.0                  # terminated, not hung
        # blocking flavor with zero output -> 504
        st, body = _post(base, "/generate",
                         {"prompt": [1, 2, 3], "max_tokens": 60,
                          "timeout_ms": 0, "stream": False})
        assert st == 504
    finally:
        srv.stop()


def test_http_draining_503():
    net = _lm(seed=13)
    eng = _engine(net)
    srv = ServingHTTPServer(generation=eng)
    base = f"http://127.0.0.1:{srv.start()}"
    try:
        eng.stop(drain=True, timeout=5.0)      # engine drains, HTTP stays up
        st, body = _post(base, "/generate",
                         {"prompt": [1], "max_tokens": 2, "stream": False})
        assert st == 503
        try:
            with urllib.request.urlopen(base + "/health", timeout=10) as r:
                raise AssertionError(f"expected 503, got {r.status}")
        except urllib.error.HTTPError as e:
            assert e.code == 503
            assert json.loads(e.read())["draining"] is True
    finally:
        srv.stop()


def test_http_reload_hot_swap_under_decode(tmp_path):
    """POST /reload swaps the generation model: the in-flight stream
    finishes on the old params, the next request runs the new ones
    (document cutover rule); unknown names 404."""
    from deeplearning4j_tpu.util.serialization import write_model
    net_a = _lm(seed=7, max_length=64)
    net_b = _lm(seed=8, max_length=64)
    spec_a, spec_b = TransformerDecodeSpec(net_a), TransformerDecodeSpec(net_b)
    prompt = [3, 5, 7, 9]
    want_a = naive_generate(net_a, prompt, 40, pad_to=64, spec=spec_a)
    want_b = naive_generate(net_b, prompt, 40, pad_to=64, spec=spec_b)
    assert want_a != want_b
    zpath = str(tmp_path / "lm_b.zip")
    write_model(net_b, zpath)
    eng = _engine(net_a, max_seq_len=64, prompt_rungs=(64,))
    srv = ServingHTTPServer(generation=eng)
    base = f"http://127.0.0.1:{srv.start()}"
    try:
        got = {}

        def long_client():
            got["a"] = _stream(base, {"prompt": prompt, "max_tokens": 40})

        t = threading.Thread(target=long_client)
        t.start()
        deadline = time.monotonic() + 5.0
        while eng.metrics()["lm"]["prefills"] < 1:
            assert time.monotonic() < deadline
            time.sleep(0.002)
        st, body = _post(base, "/reload", {"model": "lm", "path": zpath})
        assert st == 200 and body["version"] == 2
        st, body = _post(base, "/generate",
                         {"prompt": prompt, "max_tokens": 40,
                          "stream": False})
        t.join()
        toks_a = [l["token"] for l in got["a"] if "token" in l]
        assert toks_a == want_a, "in-flight stream must finish on OLD params"
        assert body["tokens"] == want_b, "post-swap request must be NEW"
        st, _ = _post(base, "/reload", {"model": "ghost", "path": zpath})
        assert st == 404
        st, _ = _post(base, "/reload", {"model": "lm", "path": 7})
        assert st == 400
    finally:
        srv.stop()
