"""Median of a client's first stamp less the loop's emission of that
token (``first_token_us`` of the request's record, paired by the
stream's ``request_id``): handing a token to its client thread."""
from benchmarks.lib import pass_events


def read(obs):
    return pass_events.of_kind(obs, "open_loop", pass_events.stream_handoff_p50_ms)
