"""Median duration of the window's ``generation.dispatch`` spans of the
decode program: the host-to-device transfer of the step's arguments and
the launch, the first half of each ``generation.decode_step``."""
from benchmarks.lib import program_events


def read(obs):
    if obs.get("kind") != "open_loop":
        return None
    return program_events.median_span_ms(obs, "generation.dispatch",
                                         program="decode")
