"""Share of the window the trace ring had dropped when its events were
handed over (0 while fewer than ``trace_capacity`` came): whether the
other readers saw the whole window."""
from benchmarks.lib import pass_events


def read(obs):
    return pass_events.of_kind(obs, "closed_loop", pass_events.events_lost_pct)
