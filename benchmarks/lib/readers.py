"""Arithmetic the per-layer metric readers share: spans of the window, self
time, request latencies. A reader that finds nothing to read returns None."""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from benchmarks.lib.stats import percentile


def spans(obs: Dict, name: str) -> List[Dict]:
    """The program's spans called ``name`` recorded since the window opened,
    on the ``time.perf_counter`` clock in seconds, clipped to the window."""
    if "events" not in obs:
        return []
    t0, t1 = obs["window_perf"]
    off = obs["epoch_ns"]
    out = []
    for e in obs["events"]:
        if e.get("ph") != "X" or e.get("name") != name:
            continue
        s = (e["ts"] * 1000 - off) / 1e9
        d = e["dur"] / 1e6
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            out.append({"start": s, "end": s + d, "dur": d, "clipped": b - a,
                        "args": e.get("args", {})})
    return out


def span_seconds(obs: Dict, name: str) -> Optional[float]:
    """Seconds of the window inside spans called ``name`` (they have no
    child spans today, so this is their self time)."""
    found = spans(obs, name)
    return sum(s["clipped"] for s in found) if found else None


def window_seconds(obs: Dict) -> float:
    t0, t1 = obs["window_perf"]
    return t1 - t0


def tpot_so_far(r: Dict) -> Optional[float]:
    st = r["stamps"]
    return (st[-1] - st[0]) * 1e3 / (len(st) - 1) if len(st) >= 2 else None


def hist_sum_delta(obs: Dict, name: str) -> Optional[Tuple[float, int]]:
    after = obs.get("reg_after", {}).get("histograms", {}).get(name)
    if after is None:
        return None
    before = obs["reg_before"].get("histograms", {}).get(
        name, {"sum": 0.0, "count": 0})
    return after["sum"] - before["sum"], after["count"] - before["count"]


def idle_pct(obs: Dict) -> Optional[float]:
    tr = obs.get("trace")
    if not tr:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def peak_hbm_gb(obs: Dict) -> Optional[float]:
    return obs["peak_bytes"] / 1e9 if obs.get("peak_bytes") else None


__all__ = ["percentile", "spans", "span_seconds", "window_seconds",
           "tpot_so_far", "hist_sum_delta", "idle_pct", "peak_hbm_gb"]
