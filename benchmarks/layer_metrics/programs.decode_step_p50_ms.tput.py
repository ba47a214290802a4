"""Median duration of the ``generation.decode_step`` spans of the window
(host clock around a decode dispatch that blocks on its result), in a
closed-loop cell: at saturation it is the price of a step for every slot."""
from benchmarks.lib import program_events


def read(obs):
    if obs.get("kind") != "closed_loop":
        return None
    return program_events.median_span_ms(obs, "generation.decode_step")
