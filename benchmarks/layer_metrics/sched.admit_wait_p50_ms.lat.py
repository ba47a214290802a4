"""Median wait from a request's submission to its slot, as the scheduler
takes it at admission (``queue_ms`` of the window's ``generation.admit``
events: one clock, one thread). The outside twin,
``sched.queue_wait_p50_ms.lat``, rebuilds the same wait from the client's
stamps and the prefill spans."""
from benchmarks.lib import program_events


def read(obs):
    if obs.get("kind") != "open_loop":
        return None
    return program_events.median_attr(
        program_events.instants(obs, "generation.admit"), "queue_ms")
