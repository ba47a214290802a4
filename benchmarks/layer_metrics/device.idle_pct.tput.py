"""Share of the traced window in which no operation ran on the device:
1 - union of the device's operation intervals over the window."""
from benchmarks.lib import readers


def read(obs):
    return readers.idle_pct(obs) if obs.get("kind") == "closed_loop" else None
