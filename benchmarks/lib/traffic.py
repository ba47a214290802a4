"""Seed-stratified traffic: every seed offers the same work.

A traffic file fixes a rate (or a caller count) and the length
distributions. The generator takes the N mid-quantiles of each
distribution, pairs prompt and output quantiles by a permutation that the
FILE seeds (``pairing_seed``), so that the multiset of (prompt, output)
pairs -- and with it any cap on their sum -- is the same for every run
seed, and orders the requests by a permutation the RUN seed draws.
Arrivals are a Poisson process conditioned on its count: N due times
uniform over the span, sorted. Only order, spacing and token ids differ
from seed to seed.
"""
from __future__ import annotations

import math
from statistics import NormalDist
from typing import Dict, List, Sequence

import numpy as np


def mid_quantiles(dist: Dict, n: int) -> List[int]:
    """The n mid-quantiles ((i + 0.5) / n) of a length distribution, as
    whole numbers cut to [lo, hi]."""
    kind, lo, hi = dist["kind"], int(dist["lo"]), int(dist["hi"])
    qs = [(i + 0.5) / n for i in range(n)]
    if kind == "lognormal":
        mu, sigma = math.log(dist["median"]), float(dist["sigma"])
        nd = NormalDist()
        xs = [math.exp(mu + sigma * nd.inv_cdf(q)) for q in qs]
    elif kind == "uniform":
        xs = [lo + q * (hi + 1 - lo) - 0.5 for q in qs]
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return [min(max(int(round(x)), lo), hi) for x in xs]


def stratified_pairs(spec: Dict, n: int) -> List[tuple]:
    """n (prompt_len, output_len) pairs: mid-quantiles of both
    distributions, paired by the file's own permutation, outputs cut so
    that prompt + output <= max_total. Independent of the run seed."""
    prompts = mid_quantiles(spec["prompt"], n)
    outputs = mid_quantiles(spec["output"], n)
    perm = np.random.default_rng(int(spec["pairing_seed"])).permutation(n)
    cap = int(spec["max_total"])
    return [(p, max(1, min(outputs[j], cap - p)))
            for p, j in zip(prompts, perm)]


def conditioned_arrivals(rng: np.random.Generator, n: int, start: float,
                         end: float) -> List[float]:
    """Due times of a Poisson process on [start, end) given its count n."""
    return sorted(float(t) for t in rng.uniform(start, end, n))


def make_requests(spec: Dict, n: int, rng: np.random.Generator,
                  vocab: int) -> List[Dict]:
    """n requests in seeded order, token ids unshared and random."""
    pairs = stratified_pairs(spec, n)
    order = rng.permutation(n)
    return [{"prompt": rng.integers(0, vocab, pairs[i][0]).astype(np.int32),
             "max_tokens": int(pairs[i][1])} for i in order]


def offered(requests: Sequence[Dict]) -> Dict:
    """What a request list offers: count, sorted lengths, token total."""
    lens = sorted((len(r["prompt"]), r["max_tokens"]) for r in requests)
    return {"count": len(lens), "pairs": lens,
            "tokens": sum(p + o for p, o in lens)}
