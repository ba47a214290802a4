"""Open-loop serving traffic: requests due on a seeded schedule at the rate
fixed in the traffic file, each streamed to a client thread of its own that
does nothing per token but note the clock.

The rule for the window's edges, the same in every run: requests due in
the pre-roll fill the slots and are not counted; requests due inside the
window are ``attempted``; time to first token is sampled from all of them
(the run waits for their first tokens after the window closes), the gap
between tokens from those that finish inside the window; nothing is sent
after the window closes and whatever is still in flight then is cancelled.
"""
from __future__ import annotations

import threading
import time
from typing import Dict, List

import numpy as np

from benchmarks.kinds import _serve
from benchmarks.lib import traffic as tlib
from benchmarks.lib.stats import percentile


def schedule(tr: Dict, seconds: float, rng: np.random.Generator,
             vocab: int, rate: float = None) -> List[Dict]:
    """Pre-roll and window requests with their due times (seconds from the
    window's start; negative in the pre-roll), sorted by due time."""
    rate = tr["rate_per_s"] if rate is None else rate
    out = []
    for span, lo, hi in (("preroll", -tr["preroll_s"], 0.0),
                         ("window", 0.0, seconds)):
        n = max(1, int(round(rate * (hi - lo))))
        reqs = tlib.make_requests(tr["lengths"], n, rng, vocab)
        for r, due in zip(reqs, tlib.conditioned_arrivals(rng, n, lo, hi)):
            out.append(dict(r, due=due, span=span))
    return sorted(out, key=lambda r: r["due"])


def drive(eng, requests: List[Dict], t0: float, seconds: float,
          timeout_s: float, first_token_wait_s: float = 30.0) -> float:
    """Send each request when it is due (``t0 + due`` on
    ``time.perf_counter``); fill each record in place. Returns the clock at
    which the window closed."""
    def client(r, stream):
        try:
            for tok in stream:
                r["stamps"].append(time.perf_counter())
                r["tokens"].append(tok)
            r["reason"] = stream.finish_reason
            if stream.error is not None and r["reason"] != "cancelled":
                r["error"] = repr(stream.error)
        except BaseException as e:          # recorded, judged by the caller
            r["error"] = repr(e)
        r["end"] = time.perf_counter()

    threads = []
    for r in requests:
        r.update(stamps=[], tokens=[], reason=None, error=None, sent=None,
                 end=None, stream=None)
    t1 = t0 + seconds
    for r in requests:
        wait = t0 + r["due"] - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        r["sent"] = time.perf_counter()
        try:
            r["stream"] = eng.generate(r["prompt"], max_tokens=r["max_tokens"],
                                       stream=True, timeout=timeout_s)
        except Exception as e:              # a refusal is a failed request
            r["error"], r["end"] = repr(e), time.perf_counter()
            continue
        th = threading.Thread(target=client, args=(r, r["stream"]),
                              name="bench-client", daemon=True)
        th.start()
        threads.append(th)
    wait = t1 - time.perf_counter()
    if wait > 0:
        time.sleep(wait)
    closed = time.perf_counter()
    limit = closed + first_token_wait_s
    for r in requests:
        while r["span"] == "window" and not r["stamps"] and r["end"] is None \
                and time.perf_counter() < limit:
            time.sleep(0.005)
    for r in requests:
        if r["stream"] is not None and r["end"] is None:
            r["stream"].cancel()
    for th in threads:
        th.join(timeout=60.0)
    if any(th.is_alive() for th in threads):
        raise RuntimeError("a client thread did not end after cancel")
    return closed


def summarize(requests: List[Dict], t0: float, t1: float) -> Dict:
    """Counts and latencies of the window's requests."""
    win = [r for r in requests if r["span"] == "window"]
    failed = [r for r in win if r["error"] is not None
              or r["reason"] in ("deadline",) or
              (r["reason"] not in (None, "length", "cancelled", "stop"))]
    ok = [r for r in win if r not in failed]
    ttft = [(r["stamps"][0] - (t0 + r["due"])) * 1e3 for r in ok if r["stamps"]]
    no_first = [r for r in ok if not r["stamps"]]
    done = [r for r in ok if r["reason"] == "length" and r["end"] is not None
            and r["end"] <= t1]
    tpot = [(r["stamps"][-1] - r["stamps"][0]) * 1e3 / (len(r["stamps"]) - 1)
            for r in done if len(r["stamps"]) >= 2]
    late = [(r["sent"] - (t0 + r["due"])) * 1e3 for r in win
            if r["sent"] is not None]
    return {"attempted": len(win), "failed": len(failed) + len(no_first),
            "ttft_ms": ttft, "tpot_ms": tpot, "lateness_ms": late,
            "done": done, "window": win}


def run(ctx: Dict) -> Dict:
    from deeplearning4j_tpu import telemetry
    log, cfg, tr = ctx["log"], ctx["config"], ctx["traffic"]
    net, eng = _serve.start_engine(ctx)
    rng = np.random.default_rng(ctx["seed"])
    requests = schedule(tr, ctx["seconds"], rng, cfg["vocab_size"])
    log(f"{len(requests)} requests at {tr['rate_per_s']}/s, pre-roll "
        f"{tr['preroll_s']}s; offered in window: "
        f"{tlib.offered([r for r in requests if r['span'] == 'window'])['tokens']} tokens")
    reg = telemetry.get_registry()
    t0 = time.perf_counter() + tr["preroll_s"]
    setup_s = t0 - ctx["t_start"]
    marks = {}

    def at_window_start():
        time.sleep(max(0.0, t0 - time.perf_counter()))
        marks["compiles0"] = telemetry.xla_compile_count()
        marks["seq0"] = reg.last_seq
        ctx["tracer"].begin()

    starter = threading.Thread(target=at_window_start, name="bench-mark",
                               daemon=True)
    starter.start()
    closed = drive(eng, requests, t0, ctx["seconds"], tr["timeout_s"])
    starter.join()
    compiles = telemetry.xla_compile_count() - marks["compiles0"]
    peak = ctx["memory_peak_bytes"]()
    events = [e for e in reg.trace_events_since(marks["seq0"])]
    s = summarize(requests, t0, t0 + ctx["seconds"])
    info = eng.models()[_serve.MODEL]
    trace = ctx["tracer"].finish(_serve.window_spans(events), blocking=True)
    eng.stop(drain=False, timeout=10.0)
    del eng, net
    log(f"window closed {closed - t0:.3f}s after it opened: attempted "
        f"{s['attempted']}, failed {s['failed']}, finished inside "
        f"{len(s['done'])}, ttft n={len(s['ttft_ms'])}, tpot n={len(s['tpot_ms'])}; "
        f"compiles {compiles}; peak {peak / 1e9:.3f} GB")
    metrics = {"setup_s": setup_s}
    if s["tpot_ms"]:       # one sample; BENCHMARK.json says which is judged
        metrics["tpot_p50_ms"] = percentile(s["tpot_ms"], 50)
        metrics["tpot_p90_ms"] = percentile(s["tpot_ms"], 90)
    if s["ttft_ms"]:
        metrics["ttft_p50_ms"] = percentile(s["ttft_ms"], 50)
    if not ctx["rehearsal"]:
        log(f"metrics {metrics}")
    _serve.check_outputs(ctx, s["done"], compiles)
    ctx["checks"].exactly("every_attempted_request_got_a_first_token_or_failed",
                          len(s["ttft_ms"]) + s["failed"], s["attempted"])
    t1_wall = t0 + ctx["seconds"]
    return {
        "attempted": s["attempted"], "failed": s["failed"],
        "memory_peak_bytes": peak, "metrics": metrics,
        "counts": {"attempted": s["attempted"], "failed": s["failed"],
                   "finished_inside": len(s["done"]),
                   "compiles_in_window": compiles},
        "obs": {"kind": "open_loop", "summary": s, "events": events,
                "window_perf": (t0, t1_wall), "engine": info, "trace": trace,
                "peak_bytes": peak, "device": ctx["device"], "traffic": tr,
                "config": cfg, "seconds": ctx["seconds"]},
    }
