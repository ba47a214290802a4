"""Share of the window ``fit`` spent waiting for its feed: the sum of the
program's ``perf.step.input_wait_ms`` over the window's steps (host clock)."""
from benchmarks.lib import readers


def read(obs):
    d = readers.hist_sum_delta(obs, "perf.step.input_wait_ms")
    if d is None or obs.get("kind") != "fit_cycle":
        return None
    return 100.0 * d[0] / (obs["elapsed_s"] * 1e3)
