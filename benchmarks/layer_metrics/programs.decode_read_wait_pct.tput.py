"""Share of the wall of the window's ``generation.decode_step`` passes spent
inside their blocking read (``read_wait_ms`` of each pass over its own
duration): whether the device or the host sets the pass."""
from benchmarks.lib import pass_events


def read(obs):
    return pass_events.of_kind(obs, "closed_loop", pass_events.read_wait_pct)
