"""Share of the loop's time in which its thread neither ran, nor waited
for a result, nor idled for want of work: between the window's first and
last decode pass that sampled the thread's usage, wall - (``loop_cpu_ms``
of the last - of the first) - the ``generation.readback`` spans and
``generation.idle_wait`` phases between. The interpreter lock behind the
client threads, preemption, launches that block."""
from benchmarks.lib import pass_events


def read(obs):
    return pass_events.of_kind(obs, "closed_loop", pass_events.offcpu_pct)
