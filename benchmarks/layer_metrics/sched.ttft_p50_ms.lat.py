"""Median time to first token, from when each request was due (sent or
not) to its first token at the client. An end-to-end quantity by nature;
it stands here because its runs spread too widely for a bound of 10%
(PERF.md, section 2), so the arrow to ``tpot_p50_ms`` is nominal."""
from benchmarks.lib import readers


def read(obs):
    if obs.get("kind") != "open_loop" or len(obs["summary"]["ttft_ms"]) < 10:
        return None
    return readers.percentile(obs["summary"]["ttft_ms"], 50)
