"""Involuntary context switches of the loop's thread a second of the
window (``nivcsw`` of the window's last decode pass that sampled it less
the first's): whether the shared host took the loop's core."""
from benchmarks.lib import pass_events


def read(obs):
    return pass_events.of_kind(obs, "closed_loop", pass_events.preemptions_per_s)
