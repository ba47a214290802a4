"""Tokens the decode step gathers (``decode_slots`` x cache capacity, from
the engine's shapes: computed, not measured) over the tokens live in the
cache at that step (from the clients' stamps), mean over the steps."""
from benchmarks.lib import readers


def read(obs):
    steps = readers.spans(obs, "generation.decode_step")
    if len(steps) < 10 or obs.get("kind") != "open_loop":
        return None
    gathered = obs["engine"]["decode_slots"] * obs["engine"]["capacity"]
    reqs = obs["all_requests"]
    ratios = []
    for s in steps:
        live = readers.live_tokens_at(reqs, s["start"])
        if live:
            ratios.append(gathered / live)
    return sum(ratios) / len(ratios) if ratios else None
