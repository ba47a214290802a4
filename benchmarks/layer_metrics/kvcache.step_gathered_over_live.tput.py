"""Tokens a decode step's gather reads per layer (``gathered_tokens``:
every slot's whole table) over the tokens the step attends to
(``live_tokens``: the valid positions of its live slots), both on the
``generation.decode_step`` span; mean over the window's steps."""
from benchmarks.lib import program_events


def read(obs):
    if obs.get("kind") != "closed_loop":
        return None
    return program_events.mean_ratio(obs, "generation.decode_step",
                                     "gathered_tokens", "live_tokens")
