"""The allocator's peak_bytes_in_use when the window closed, before
the reference runs."""
from benchmarks.lib import readers


def read(obs):
    return readers.peak_hbm_gb(obs) if obs.get("kind") == "fit_cycle" else None
