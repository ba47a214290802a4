"""Share of a sequence's context a block-sparse layer reads in a decode
step: 100 x ``selected_tokens`` (sum over live slots of the rows in their
chosen pages up to their position) over ``live_tokens`` (their whole
contexts, what a layer without a selection reads), both on the
``generation.decode_step`` span; mean over the window's steps."""
from benchmarks.lib import program_events


def read(obs):
    if obs.get("kind") != "closed_loop":
        return None
    ratio = program_events.mean_ratio(obs, "generation.decode_step",
                                      "selected_tokens", "live_tokens")
    return None if ratio is None else 100.0 * ratio
