"""The tail of time to first token, from when each request was due."""
from benchmarks.lib import readers


def read(obs):
    if obs.get("kind") != "open_loop" or len(obs["summary"]["ttft_ms"]) < 10:
        return None
    return readers.percentile(obs["summary"]["ttft_ms"], 90)
