"""Live slots per decode step (the ``slots`` attribute of each
``generation.decode_step`` span) over ``decode_slots``, mean over steps."""
from benchmarks.lib import readers


def read(obs):
    steps = readers.spans(obs, "generation.decode_step")
    if not steps or obs.get("kind") != "open_loop":
        return None
    slots = obs["engine"]["decode_slots"]
    return 100.0 * sum(s["args"].get("slots", 0) for s in steps) / (len(steps) * slots)
