#!/usr/bin/env python3
"""The control of a training cell, on the chip at the cell's own size: the
reference put in the program's place at the next lower precision (float8 e4m3
fake-quantised matmul operands), read against the float32 reference on
several seeds. Needs no measured window. Prints one JSON line per seed.

    python benchmarks/tests/control_on_chip.py --workload gpt2m-train-1k --seeds 11,12,13
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args()
    import numpy as np

    from benchmarks import run as harness
    from benchmarks.lib.correct import worst_leaf_gap
    cell, _, _ = harness.find_cell(args.workload)
    cfg = harness.load_json(HERE, "configs", cell["config"], "config.json")
    tr = harness.load_json(HERE, "traffic", cell["traffic"] + ".json")
    harness.setup_compile_cache()
    fam = {k: importlib.import_module(f"benchmarks.families.{cfg['family']}.{k}")
           for k in ("build", "weights", "reference")}
    ref_mod = fam["reference"]
    for seed in (int(s) for s in args.seeds.split(",")):
        batches = fam["build"].make_batches(cfg, tr, np.random.default_rng(seed))[:3]
        hp = cfg["hyperparameters"]
        rows = tr.get("reference_rows", 1)
        ref = ref_mod.train_reference(fam["weights"].make(cfg, seed, "train"),
                                      batches, cfg, hp, rows=rows)
        ctl = ref_mod.train_reference(fam["weights"].make(cfg, seed, "train"),
                                      batches, cfg, hp, quant=ref_mod.CONTROL,
                                      rows=rows)
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "control_loss_rel_gap": [abs(a - b) / abs(b) for a, b in
                                     zip(ctl["losses"], ref["losses"])],
            "control_grad_norm_gap": worst_leaf_gap(ctl["grad_norms"],
                                                    ref["grad_norms"]),
            "control_change_norm_gap": worst_leaf_gap(ctl["change_norms"],
                                                      ref["change_norms"]),
            "control_grad_vectors_rel_error": ref_mod.vectors_rel_error(
                ctl["grad_small"], ref["grad_small"]),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
