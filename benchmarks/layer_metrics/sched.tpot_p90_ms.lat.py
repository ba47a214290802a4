"""The tail of the gap between tokens: per request (last token - first
token) / (tokens - 1) as the client stamped them, 90th percentile over the
requests that finished inside the window. The end-to-end metric of the
chat cell until PR 37: its runs spread by more than half of the widest
bound there is (PERF.md, sections 2 and 6), so the median of the same
sample is judged and the tail is read here."""
from benchmarks.lib import readers


def read(obs):
    if obs.get("kind") != "open_loop" or len(obs["summary"]["tpot_ms"]) < 10:
        return None
    return readers.percentile(obs["summary"]["tpot_ms"], 90)
