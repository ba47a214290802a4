"""Tokens a decode step's gather reads per layer (``gathered_tokens``:
every slot's whole table) over the tokens the step attends to
(``live_tokens``: the valid positions of its live slots), both on the
``generation.decode_step`` span; mean over the window's steps. Its outside
twin, which reckoned the gather from the engine's shapes, was retired in
PR 26: it would go on reading 5-7 for a step that gathers nothing."""
from benchmarks.lib import program_events


def read(obs):
    if obs.get("kind") != "open_loop":
        return None
    return program_events.mean_ratio(obs, "generation.decode_step",
                                     "gathered_tokens", "live_tokens")
