"""The longest ``generation.decode_step`` pass of the window: it names a
slow run."""
from benchmarks.lib import pass_events


def read(obs):
    return pass_events.of_kind(obs, "open_loop", pass_events.longest_pass_ms)
