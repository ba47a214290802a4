"""Share of the prefill programs' token positions that are padding: 1 -
real prompt tokens / (rows x rung) over the window's
``generation.prefill`` spans (``tokens`` and ``padded_tokens``, host
integers the scheduler has when it pads the batch)."""
from benchmarks.lib import program_events


def read(obs):
    if obs.get("kind") != "closed_loop":
        return None
    return program_events.one_minus_ratio_pct(
        obs, "generation.prefill", "tokens", "padded_tokens")
