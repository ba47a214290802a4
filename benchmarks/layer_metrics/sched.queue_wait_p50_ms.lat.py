"""Median wait between a request's submission and the start of the prefill
that produced its first token: the ``generation.prefill`` span that ended
last before the client's first stamp, minus the generator's send time."""
from bisect import bisect_right

from benchmarks.lib import readers


def read(obs):
    if obs.get("kind") != "open_loop":
        return None
    fills = sorted(readers.spans(obs, "generation.prefill"), key=lambda s: s["end"])
    ends = [s["end"] for s in fills]
    waits = []
    for r in obs["summary"]["window"]:
        if not r["stamps"] or r["sent"] is None:
            continue
        i = bisect_right(ends, r["stamps"][0]) - 1
        if i >= 0 and fills[i]["start"] >= r["sent"] - 1e-4:
            waits.append((fills[i]["start"] - r["sent"]) * 1e3)
    return readers.percentile(waits, 50) if len(waits) >= 10 else None
