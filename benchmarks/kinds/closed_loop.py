"""Closed-loop serving traffic: a fixed number of callers, each sending its
next request when the last one completes, over a seeded request list that
is stratified block by block so that whichever stretch of it a window
consumes offers the same mix. Served at saturation; tokens per second of
the requests completed inside the window are what is judged.

The rule for the window's edges: callers start a pre-roll before the
window; a request counts (``attempted``) when it completes or fails inside
the window; after the window closes every caller finishes the request it
has in flight, uncounted, and stops.
"""
from __future__ import annotations

import threading
import time
from typing import Dict, List

import numpy as np

from benchmarks.kinds import _serve
from benchmarks.lib import traffic as tlib


def request_list(tr: Dict, rng: np.random.Generator, vocab: int) -> List[Dict]:
    """``blocks`` blocks of ``block`` requests; every block holds the same
    stratified multiset of lengths, in an order the seed draws."""
    out = []
    for _ in range(tr["blocks"]):
        out.extend(tlib.make_requests(tr["lengths"], tr["block"], rng, vocab))
    return out


def run(ctx: Dict) -> Dict:
    from deeplearning4j_tpu import telemetry
    log, cfg, tr = ctx["log"], ctx["config"], ctx["traffic"]
    net, eng = _serve.start_engine(ctx)
    rng = np.random.default_rng(ctx["seed"])
    requests = request_list(tr, rng, cfg["vocab_size"])
    log(f"{len(requests)} requests listed, {tr['callers']} callers, "
        f"pre-roll {tr['preroll_s']}s")
    reg = telemetry.get_registry()
    lock = threading.Lock()
    state = {"next": 0, "stop": False}
    records: List[Dict] = []

    def caller():
        while True:
            with lock:
                if state["stop"] or state["next"] >= len(requests):
                    return
                r = requests[state["next"]]
                state["next"] += 1
            rec = {"prompt": r["prompt"], "start": time.perf_counter(),
                   "tokens": [], "error": None, "reason": None}
            try:
                rec["tokens"], rec["reason"] = eng.generate(
                    r["prompt"], max_tokens=r["max_tokens"], stream=False,
                    timeout=tr["timeout_s"])
            except Exception as e:          # a failed request, counted
                rec["error"] = repr(e)
            rec["end"] = time.perf_counter()
            with lock:
                records.append(rec)

    threads = [threading.Thread(target=caller, name="bench-caller", daemon=True)
               for _ in range(tr["callers"])]
    start = time.perf_counter()
    t0 = start + tr["preroll_s"]
    setup_s = t0 - ctx["t_start"]
    for th in threads:
        th.start()
    time.sleep(max(0.0, t0 - time.perf_counter()))
    compiles0 = telemetry.xla_compile_count()
    seq0 = reg.last_seq
    ctx["tracer"].begin()
    t1 = t0 + ctx["seconds"]
    time.sleep(max(0.0, t1 - time.perf_counter()))
    with lock:
        state["stop"] = True
        drained = state["next"] >= len(requests)
    compiles = telemetry.xla_compile_count() - compiles0
    peak = ctx["memory_peak_bytes"]()
    events = reg.trace_events_since(seq0)
    for th in threads:
        th.join(timeout=tr["timeout_s"] + 30.0)
    if any(th.is_alive() for th in threads):
        raise RuntimeError("a caller did not end after the window")
    info = eng.models()[_serve.MODEL]
    trace = ctx["tracer"].finish(_serve.window_spans(events), blocking=True)
    eng.stop(drain=False, timeout=10.0)
    del eng, net

    inside = [r for r in records if t0 <= r["end"] <= t1]
    failed = [r for r in inside if r["error"] is not None
              or r["reason"] != "length"]
    done = [r for r in inside if r not in failed]
    prompt_tokens = sum(len(r["prompt"]) for r in done)
    out_tokens = sum(len(r["tokens"]) for r in done)
    rate = (prompt_tokens + out_tokens) / ctx["seconds"]
    log(f"window: {len(done)} requests completed ({prompt_tokens} prompt + "
        f"{out_tokens} generated tokens), {len(failed)} failed" +
        ("" if ctx["rehearsal"] else f" -> {rate:.3f} tokens/s") + f"; compiles {compiles}; peak {peak / 1e9:.3f} GB")
    _serve.check_outputs(ctx, done, compiles)
    ctx["checks"].exactly("request_list_outlasted_the_window", drained, False)
    return {
        "attempted": len(inside), "failed": len(failed),
        "memory_peak_bytes": peak,
        "metrics": {"serve_tokens_per_s": rate, "setup_s": setup_s},
        "counts": {"completed": len(done), "failed": len(failed),
                   "compiles_in_window": compiles},
        "obs": {"kind": "closed_loop", "done": done, "events": events,
                "window_perf": (t0, t1), "engine": info, "trace": trace,
                "peak_bytes": peak, "device": ctx["device"], "traffic": tr,
                "config": cfg, "seconds": ctx["seconds"],
                "prompt_tokens": prompt_tokens, "out_tokens": out_tokens},
    }
