"""Prompt tokens of the requests completed inside the window over the self
time of the window's ``generation.prefill`` spans."""
from benchmarks.lib import readers


def read(obs):
    sec = readers.span_seconds(obs, "generation.prefill")
    if not sec or obs.get("kind") != "closed_loop":
        return None
    return obs["prompt_tokens"] / sec
