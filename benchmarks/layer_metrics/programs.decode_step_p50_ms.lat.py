"""Median duration of the ``generation.decode_step`` spans of the window
(host clock around a decode dispatch that blocks on its result)."""
from benchmarks.lib import readers


def read(obs):
    steps = readers.spans(obs, "generation.decode_step")
    if len(steps) < 10 or obs.get("kind") != "open_loop":
        return None
    return readers.percentile([s["dur"] * 1e3 for s in steps], 50)
