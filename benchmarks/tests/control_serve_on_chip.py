#!/usr/bin/env python3
"""The control of a serving cell, on the chip at the cell's own size and
load: a short window of the cell's traffic on each seed, then the reference
over the sampled prompts and served tokens and, at the same positions, the
token that the int8 reference (the next precision below bfloat16) puts
first. Prints, per seed, the program's widest gap and the control's.

    python benchmarks/tests/control_serve_on_chip.py --workload gpt2m-serve-chat --seeds 1,2,3 --seconds 20
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args()
    from benchmarks import run as harness
    for seed in (int(s) for s in args.seeds.split(",")):
        ctx, _, kind = harness.prepare(args.workload, seed, args.seconds, False)
        ctx["control"] = True
        res = kind.run(ctx)
        c = ctx["control_result"]
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "program_widest_gap": c["widest_gap"],
            "control_widest_gap": c["control_widest_gap"],
            "served_tokens": c["tokens"], "argmax_tokens": c["argmax_tokens"],
            "metrics": res["metrics"], "attempted": res["attempted"],
            "failed": res["failed"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
