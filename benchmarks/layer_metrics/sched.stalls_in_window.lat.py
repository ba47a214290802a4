"""``generation.stall`` events inside the window: passes far longer than
their kind's recent median, each with its cause on the event."""
from benchmarks.lib import pass_events


def read(obs):
    return pass_events.of_kind(obs, "open_loop", pass_events.stalls)
