#!/usr/bin/env python3
"""The knee sweep of an open-loop cell, once, on the chip: several rates in
one process on one warmed engine; for each a pre-roll and a window of the
cell's traffic. The knee is the highest rate at which the queue does not
grow over the window. One JSON line per rate.

    python benchmarks/tests/sweep_on_chip.py --workload gpt2m-serve-chat --seed 7 --rates 1.5,2,2.5,3 --seconds 30
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    args = ap.parse_args()
    import numpy as np

    from benchmarks import run as harness
    from benchmarks.kinds import _serve, open_loop
    from benchmarks.lib import readers
    from benchmarks.lib.stats import percentile
    from deeplearning4j_tpu import telemetry
    ctx, _, _ = harness.prepare(args.workload, args.seed, args.seconds, False)
    tr, cfg = ctx["traffic"], ctx["config"]
    net, eng = _serve.start_engine(ctx)
    reg = telemetry.get_registry()
    for rate in (float(r) for r in args.rates.split(",")):
        rng = np.random.default_rng(args.seed)
        reqs = open_loop.schedule(tr, args.seconds, rng, cfg["vocab_size"], rate)
        t0 = time.perf_counter() + tr["preroll_s"]
        seq0 = reg.last_seq
        depth = []
        stop = threading.Event()

        def watch():
            while not stop.wait(0.25):
                depth.append((time.perf_counter() - t0,
                              eng.queue_depths()[_serve.MODEL],
                              eng.models()[_serve.MODEL]["in_flight"]))
        th = threading.Thread(target=watch, daemon=True)
        th.start()
        open_loop.drive(eng, reqs, t0, args.seconds, tr["timeout_s"])
        stop.set()
        th.join()
        s = open_loop.summarize(reqs, t0, t0 + args.seconds)
        obs = {"events": reg.trace_events_since(seq0),
               "window_perf": (t0, t0 + args.seconds),
               "epoch_ns": ctx["epoch_ns"]}
        steps = readers.spans(obs, "generation.decode_step")
        half = args.seconds / 2
        first = [q for t, q, _ in depth if 0 <= t < half]
        second = [q for t, q, _ in depth if half <= t < args.seconds]
        def p50(xs):
            return percentile(xs, 50) if xs else None
        ttft_by_half = [
            p50([(r["stamps"][0] - (t0 + r["due"])) * 1e3
                 for r in s["window"] if r["stamps"] and lo <= r["due"] < hi])
            for lo, hi in ((0, half), (half, args.seconds))]
        print(json.dumps({
            "rate_per_s": rate, "attempted": s["attempted"], "failed": s["failed"],
            "finished_inside": len(s["done"]),
            "tpot_p50_ms": percentile(s["tpot_ms"], 50) if s["tpot_ms"] else None,
            "tpot_p90_ms": percentile(s["tpot_ms"], 90) if s["tpot_ms"] else None,
            "ttft_p50_ms": percentile(s["ttft_ms"], 50) if s["ttft_ms"] else None,
            "ttft_p90_ms": percentile(s["ttft_ms"], 90) if s["ttft_ms"] else None,
            "ttft_p50_ms_by_half": ttft_by_half,
            "queue_depth_mean_by_half": [sum(first) / max(1, len(first)),
                                         sum(second) / max(1, len(second))],
            "queue_depth_max": max((q for _, q, _ in depth), default=0),
            "occupancy_pct": 100.0 * sum(x["args"].get("slots", 0) for x in steps)
            / max(1, len(steps)) / tr["engine"]["decode_slots"],
            "decode_step_p50_ms": percentile([x["dur"] * 1e3 for x in steps], 50)
            if steps else None,
        }), flush=True)
    eng.stop(drain=False, timeout=10.0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
