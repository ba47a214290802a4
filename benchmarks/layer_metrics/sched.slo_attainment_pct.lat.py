"""Share of attempted requests inside both limits of the traffic file
(time to first token from due; gap between tokens, so far for a request
still in flight when the window closed). A failed request misses."""
from benchmarks.lib import readers


def read(obs):
    if obs.get("kind") != "open_loop" or "slo" not in obs["traffic"]:
        return None
    slo, (t0, _) = obs["traffic"]["slo"], obs["window_perf"]
    win = obs["summary"]["window"]
    met = 0
    for r in win:
        if r["error"] is not None or not r["stamps"]:
            continue
        ttft = (r["stamps"][0] - (t0 + r["due"])) * 1e3
        gap = readers.tpot_so_far(r)
        met += ttft <= slo["ttft_ms"] and (gap is None or gap <= slo["tpot_ms"])
    return 100.0 * met / len(win) if win else None
