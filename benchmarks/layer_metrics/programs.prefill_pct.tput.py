"""Share of the window inside ``generation.prefill`` spans (self time)."""
from benchmarks.lib import readers


def read(obs):
    sec = readers.span_seconds(obs, "generation.prefill")
    if sec is None or obs.get("kind") != "closed_loop":
        return None
    return 100.0 * sec / readers.window_seconds(obs)
