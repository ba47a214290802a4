"""Share of a sequence's context a sliding-window layer reads in a decode
step: 100 x ``window_tokens`` (sum over live slots of min(pos + 1, window))
over ``live_tokens`` (their whole contexts, what a full-attention layer
reads), both on the ``generation.decode_step`` span; mean over the
window's steps."""
from benchmarks.lib import program_events


def read(obs):
    if obs.get("kind") != "closed_loop":
        return None
    ratio = program_events.mean_ratio(obs, "generation.decode_step",
                                      "window_tokens", "live_tokens")
    return None if ratio is None else 100.0 * ratio
