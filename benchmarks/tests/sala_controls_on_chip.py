#!/usr/bin/env python3
"""Both controls of a MiniCPM-SALA serving cell, on the chip at the cell's
own size and load and THROUGH the harness: a window of the cell's traffic
on each seed (``kinds/closed_loop.py`` ``run``, with ``control`` set as
``control_serve_on_chip.py`` sets it), then ``check_outputs``' one call of
the reference over the sampled prompts and served tokens with BOTH of the
family's controls in the program's place (``reference.CONTROLS``: the
float8 reference, and the full-precision reference reading each selecting
position's forced blocks alone). Prints, per seed, the program's widest
gap at the positions the cell's selection margin keeps, the share it
leaves out, and each control's widest gap at the same positions; every
control has to pass the cell's ``widest_logit_gap``. With ``--dump`` the
per-position gaps and margins go to ``chiprun_out/<dump>_<seed>.npz``, for
whoever sets the limits.

    python benchmarks/tests/sala_controls_on_chip.py --workload minicpm-sala-serve-longctx --seeds 1,2 --seconds 20
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="minicpm-sala-serve-longctx")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--dump", default="")
    args = ap.parse_args()
    import numpy as np
    from benchmarks import run as harness
    failed = 0
    for seed in (int(s) for s in args.seeds.split(",")):
        ctx, _, kind = harness.prepare(args.workload, seed, args.seconds, False)
        ref = ctx["family"]["reference"]
        ctx["control"] = True
        ctx["family"] = dict(ctx["family"], reference=types.SimpleNamespace(
            token_gaps=ref.token_gaps, CONTROL=ref.CONTROLS))
        res = kind.run(ctx)
        c = ctx["control_result"]
        limit = ctx["limits"]["widest_logit_gap"]
        stood_off = {k: v > limit for k, v in c["control_widest_gaps"].items()}
        failed += not (ctx["checks"].correct and all(stood_off.values()))
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "program_correct": ctx["checks"].correct,
            "program_widest_gap": c["widest_gap"],
            "program_widest_gap_every_position": c["all_widest_gap"],
            "close_margin_share": c["close_margin_share"],
            "control_widest_gaps": c["control_widest_gaps"],
            "controls_not_correct": stood_off, "limit": limit,
            "served_tokens": c["tokens"], "argmax_tokens": c["argmax_tokens"],
            "metrics": res["metrics"], "attempted": res["attempted"],
            "failed": res["failed"]}), flush=True)
        if args.dump:
            out = os.path.join(ROOT, "chiprun_out")
            os.makedirs(out, exist_ok=True)
            np.savez(os.path.join(out, f"{args.dump}_{seed}.npz"),
                     gaps=c["gaps"], margins=c["margins"],
                     layer_margins=c["layer_margins"],
                     **{"control_" + k: v
                        for k, v in c["control_gaps"].items()})
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
