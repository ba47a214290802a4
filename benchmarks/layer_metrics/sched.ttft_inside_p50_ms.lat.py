"""Median ``ttft_ms`` of the ``generation.request`` records of the requests
submitted inside the window: submission to the emission of the first
token, one clock, one thread. The outside twin is
``sched.ttft_p50_ms.lat``."""
from benchmarks.lib import pass_events


def read(obs):
    return pass_events.of_kind(obs, "open_loop", pass_events.ttft_inside_p50_ms)
