"""Rows of the cache pool that hold a live token (``live_tokens`` on the
``generation.decode_step`` span: the valid positions of the step's live
slots) over the rows the pool has (``num_blocks`` x ``block_len`` of the
engine's record), mean over the window's decode steps: how full the
memory the cell reserves for its cache runs."""
from benchmarks.lib import readers


def read(obs):
    eng = obs.get("engine") or {}
    if obs.get("kind") != "closed_loop" or not eng.get("num_blocks") \
            or not eng.get("block_len") or not eng.get("cache_kind"):
        return None
    rows = eng["num_blocks"] * eng["block_len"]
    shares = [100.0 * s["args"]["live_tokens"] / rows
              for s in readers.spans(obs, "generation.decode_step")
              if "live_tokens" in s["args"]]
    return sum(shares) / len(shares) if len(shares) >= 10 else None
