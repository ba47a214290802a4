"""Share of the window the loop spends in its host phases between program
calls, with the device waiting: the admission passes
(``generation.admit_batch``) and the emission after each step
(``generation.emit``), complete events the scheduler records itself."""
from benchmarks.lib import program_events


def read(obs):
    if obs.get("kind") != "open_loop":
        return None
    return program_events.phase_share_pct(obs)
