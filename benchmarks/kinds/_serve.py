"""What the two serving kinds share: the engine built from the traffic
file, the per-request record, the post-window output check."""
from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

MODEL = "lm"


def start_engine(ctx: Dict):
    """The net with the benchmark's weights behind a warmed
    ``GenerationEngine``; only the cell's own rungs and batches."""
    from deeplearning4j_tpu.serving import GenerationEngine
    cfg, fam = ctx["config"], ctx["family"]
    net = fam["build"].build(cfg, cfg["hyperparameters"], "serve")
    fam["build"].install(net, fam["weights"].make(cfg, ctx["seed"], "serve"))
    e = ctx["traffic"]["engine"]
    t = time.perf_counter()
    eng = GenerationEngine(
        net, model_name=MODEL, block_len=e["block_len"],
        max_seq_len=e["max_seq_len"], decode_slots=e["decode_slots"],
        prompt_rungs=tuple(e["prompt_rungs"]),
        prefill_batches=tuple(e["prefill_batches"]))
    ctx["log"](f"engine warm in {time.perf_counter() - t:.1f}s: "
               f"{eng.models()[MODEL]}")
    return net, eng


def window_spans(events: List[Dict]):
    """The program's spans as (name, start wall ns, end wall ns)."""
    return [(e["name"], e["ts"] * 1000, (e["ts"] + e["dur"]) * 1000)
            for e in events if e.get("ph") == "X" and e.get("cat") == "span"]


def sample_served(done: List[Dict], rng: np.random.Generator,
                  min_tokens: int, max_requests: int) -> List[Dict]:
    """A seeded sample of finished requests, the longest always in it,
    until it holds ``min_tokens`` served tokens or ``max_requests``."""
    if not done:
        return []
    longest = max(done, key=lambda r: len(r["prompt"]) + len(r["tokens"]))
    rest = [r for r in done if r is not longest]
    picked, n = [longest], len(longest["tokens"])
    for i in rng.permutation(len(rest)):
        if n >= min_tokens or len(picked) >= max_requests:
            break
        picked.append(rest[i])
        n += len(rest[i]["tokens"])
    return picked


def check_outputs(ctx: Dict, done: List[Dict], compiles: int) -> Dict:
    """Once the window has closed and the program is freed: the reference
    runs once over each sampled prompt with its served tokens; the widest
    gap by which a served token's logit lies below the reference's best is
    held to the cell's limit."""
    cfg, fam, tr, limits = (ctx["config"], ctx["family"], ctx["traffic"],
                            ctx["limits"])
    rng = np.random.default_rng(ctx["seed"] + 1)
    picked = sample_served(done, rng, tr["check"]["min_tokens"],
                           tr["check"]["max_requests"])
    t = time.perf_counter()
    w = fam["weights"].make(cfg, ctx["seed"], "serve")
    res = fam["reference"].token_gaps(
        w, cfg, [(r["prompt"], r["tokens"]) for r in picked],
        quant=fam["reference"].CONTROL if ctx.get("control") else None)
    ctx["log"](f"reference over {len(picked)} requests, {res['tokens']} served "
               f"tokens ({res['argmax_tokens']} its argmax) in "
               f"{time.perf_counter() - t:.1f}s")
    if ctx.get("control"):
        ctx["log"](f"control (float8 reference in the program's place): widest "
                   f"gap {res['control_widest_gap']}")
        ctx["control_result"] = res
    checks = ctx["checks"]
    checks.at_most("served_token_widest_logit_gap", res["widest_gap"],
                   limits["widest_logit_gap"])
    checks.exactly("compiles_in_window", compiles, 0)
    checks.exactly("served_sample_is_not_empty", res["tokens"] > 0, True)
    return res
