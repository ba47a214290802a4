"""The judged ``tpot_p50_ms`` taken inside: per ``generation.request``
record (``last_token_us`` - ``first_token_us``) / (``tokens`` - 1), median
over the same sample. What lies between the two is not the loop's."""
from benchmarks.lib import pass_events


def read(obs):
    return pass_events.of_kind(obs, "open_loop", pass_events.tpot_inside_p50_ms)
