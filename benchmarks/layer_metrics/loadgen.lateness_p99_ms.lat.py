"""How late the generator sent: sent minus due, 99th percentile."""
from benchmarks.lib import readers


def read(obs):
    if obs.get("kind") != "open_loop" or len(obs["summary"]["lateness_ms"]) < 10:
        return None
    return readers.percentile(obs["summary"]["lateness_ms"], 99)
