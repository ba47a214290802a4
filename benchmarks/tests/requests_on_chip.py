#!/usr/bin/env python3
"""One untraced run of an open-loop cell as ``run.py`` makes it, with the
window's requests and the program's decode and prefill spans written out
for a look at where a tail's noise comes from (hand script, on the chip;
PR 37's refusal, PERF.md section 6).

    python benchmarks/tests/requests_on_chip.py --workload gpt2m-serve-chat --seed 7 --seconds 51 --tag a_7

Writes ``chiprun_out/requests_<tag>.json`` and prints the run's metrics
with a few more statistics of the same sample as one JSON line.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--tag", required=True)
    args = ap.parse_args()
    from benchmarks import run as harness
    from benchmarks.lib import readers
    from benchmarks.lib.stats import percentile
    ctx, _, kind = harness.prepare(args.workload, args.seed, args.seconds, False)
    res = kind.run(ctx)
    obs = dict(res["obs"], epoch_ns=ctx["epoch_ns"])
    t0, t1 = obs["window_perf"]
    s = obs["summary"]
    done = {id(r) for r in s["done"]}
    rows = [{"prompt": len(r["prompt"]), "max_tokens": r["max_tokens"],
             "due": r["due"], "sent": None if r["sent"] is None else r["sent"] - t0,
             "first": r["stamps"][0] - t0 if r["stamps"] else None,
             "last": r["stamps"][-1] - t0 if r["stamps"] else None,
             "n": len(r["stamps"]), "reason": r["reason"],
             "done": id(r) in done} for r in s["window"]]
    spans = {name: [[x["start"] - t0, x["dur"], x["args"].get("slots"),
                     x["args"].get("rows"), x["args"].get("padded_tokens")]
                    for x in readers.spans(obs, "generation." + name)]
             for name in ("decode_step", "prefill")}
    tp = s["tpot_ms"]
    gaps_ms = sum((r["last"] - r["first"]) for r in rows if r["done"] and r["n"] >= 2) * 1e3
    gaps_n = sum(r["n"] - 1 for r in rows if r["done"] and r["n"] >= 2)
    steps = [x[1] * 1e3 for x in spans["decode_step"]]
    line = {"tag": args.tag, "seed": args.seed, "correct": ctx["checks"].correct,
            "metrics": res["metrics"], "n": len(tp),
            "tpot_p10_ms": percentile(tp, 10), "tpot_p50_ms": percentile(tp, 50),
            "tpot_p75_ms": percentile(tp, 75), "tpot_p90_ms": percentile(tp, 90),
            "tpot_p95_ms": percentile(tp, 95), "tpot_max_ms": max(tp),
            "tpot_mean_ms": sum(tp) / len(tp),
            "gap_mean_ms": gaps_ms / gaps_n,
            "decode_step_p50_ms": percentile(steps, 50),
            "decode_step_mean_ms": sum(steps) / len(steps),
            "decode_steps": len(steps), "prefills": len(spans["prefill"]),
            "rate_per_s": ctx["traffic"].get("rate_per_s")}
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"requests_{args.tag}.json"), "w") as f:
        json.dump({"line": line, "requests": rows, "spans": spans}, f)
    print("REQUESTS " + json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
