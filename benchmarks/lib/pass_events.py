"""Arithmetic shared by the readers of the serving loop's own account
(ISSUE 42): what a ``generation.decode_step`` pass says it waited for
(``step``, ``read_wait_ms``; every sixteenth also ``loop_cpu_ms`` and
``nivcsw``, the loop thread's usage so far), the
``generation.stall`` instant events, the one ``generation.request`` record
a request leaves, and what the trace ring kept of the window. A program
that records none of it (the commit before) leaves every function here
with nothing to read: None."""
from __future__ import annotations

from typing import Dict, List, Optional

from benchmarks.lib import program_events, readers
from benchmarks.lib.stats import percentile

PASS = "generation.decode_step"


def passes(obs: Dict, *need: str) -> List[Dict]:
    """The window's decode passes that carry ``step`` and every attribute
    of ``need``, in order of their start; ``wall_ms`` is the span's own
    duration."""
    found = [dict(s, wall_ms=s["dur"] * 1e3)
             for s in readers.spans(obs, PASS)
             if "step" in s["args"] and all(k in s["args"] for k in need)]
    return sorted(found, key=lambda s: s["start"])


def read_wait_pct(obs: Dict) -> Optional[float]:
    """Share of the passes' wall inside their blocking read: near 100 where
    the device sets the pass, near 0 where the host does."""
    found = passes(obs, "read_wait_ms")
    wall = sum(s["wall_ms"] for s in found)
    if not wall:
        return None
    return 100.0 * sum(s["args"]["read_wait_ms"] for s in found) / wall


def offcpu_pct(obs: Dict) -> Optional[float]:
    """Share of the loop's time in which its thread neither ran, nor
    waited for a result, nor idled for want of work. Between the ends of
    the window's first and last decode pass that sampled the thread's
    usage (``loop_cpu_ms``, cumulative): the wall, less the CPU time, less
    what of the ``generation.readback`` spans and ``generation.idle_wait``
    phases lies between. What is left is the interpreter lock, preemption
    and launches that block; under 0, CPU time overlapped the waits (a
    read that spins)."""
    found = passes(obs, "loop_cpu_ms")
    if len(found) < 2 or found[-1]["end"] <= found[0]["end"]:
        return None
    a, b = found[0]["end"], found[-1]["end"]
    cpu_s = (found[-1]["args"]["loop_cpu_ms"]
             - found[0]["args"]["loop_cpu_ms"]) / 1e3
    waits = sum(max(0.0, min(s["end"], b) - max(s["start"], a))
                for name in ("generation.readback", "generation.idle_wait")
                for s in readers.spans(obs, name))
    return 100.0 * (b - a - cpu_s - waits) / (b - a)


def preemptions_per_s(obs: Dict) -> Optional[float]:
    """Involuntary context switches of the loop's thread (``nivcsw``,
    cumulative, on the passes that sampled it) between the window's first
    and last such pass, a second of the window."""
    found = passes(obs, "nivcsw")
    if len(found) < 2:
        return None
    return (found[-1]["args"]["nivcsw"] - found[0]["args"]["nivcsw"]) \
        / readers.window_seconds(obs)


def longest_pass_ms(obs: Dict) -> Optional[float]:
    found = passes(obs)
    return max(s["wall_ms"] for s in found) if found else None


def stalls(obs: Dict) -> Optional[float]:
    """``generation.stall`` events stamped inside the window; None for a
    program whose passes carry no ``step`` (it records no stall either)."""
    if not passes(obs):
        return None
    return float(len(program_events.instants(obs, "generation.stall")))


def requests(obs: Dict) -> List[Dict]:
    """The ``generation.request`` records handed over, as their ``args``
    with ``start`` and ``end`` on the ``time.perf_counter`` clock."""
    out = []
    for e in obs.get("events", ()):
        if e.get("name") == "generation.request" and e.get("ph") == "X":
            start = _perf_s(obs, e["ts"])
            out.append(dict(e.get("args", {}), start=start,
                            end=start + e["dur"] / 1e6))
    return out


def _perf_s(obs: Dict, wall_us: float) -> float:
    """An event's wall-clock microseconds on ``time.perf_counter``."""
    return (wall_us * 1000 - obs["epoch_ns"]) / 1e9


def ttft_inside_p50_ms(obs: Dict, least: int = 10) -> Optional[float]:
    """Median ``ttft_ms`` (submission to the emission of the first token,
    on the loop's clock) of the requests submitted inside the window."""
    t0, t1 = obs["window_perf"]
    found = [r["ttft_ms"] for r in requests(obs)
             if "ttft_ms" in r and t0 <= r["start"] <= t1]
    return percentile(found, 50) if len(found) >= least else None


def tpot_inside_p50_ms(obs: Dict, least: int = 10) -> Optional[float]:
    """The judged gap's inside twin: per request (last emission - first
    emission) / (tokens - 1), median over the requests submitted inside
    the window that finished by count inside it (the sample of
    ``tpot_p50_ms``)."""
    t0, t1 = obs["window_perf"]
    found = [(r["last_token_us"] - r["first_token_us"]) / 1e3
             / (r["tokens"] - 1) for r in requests(obs)
             if r.get("reason") == "length" and r.get("tokens", 0) >= 2
             and "first_token_us" in r and t0 <= r["start"]
             and r["end"] <= t1]
    return percentile(found, 50) if len(found) >= least else None


def stream_handoff_p50_ms(obs: Dict, least: int = 10) -> Optional[float]:
    """Median, over the window's requests, of the client's first stamp
    less the loop's emission of that token: what handing a token to its
    client thread costs. A client record is paired with its request's
    record by the ``request_id`` of the stream it holds."""
    first = {r["request"]: _perf_s(obs, r["first_token_us"])
             for r in requests(obs) if "first_token_us" in r}
    found = []
    for c in obs.get("summary", {}).get("window", ()):
        rid = getattr(c.get("stream"), "request_id", None)
        if rid in first and c["stamps"]:
            found.append((c["stamps"][0] - first[rid]) * 1e3)
    return percentile(found, 50) if len(found) >= least else None


def events_lost_pct(obs: Dict, capacity: Optional[int] = None
                    ) -> Optional[float]:
    """Share of the window the trace ring no longer held when its events
    were handed over: 0 while fewer than the ring's capacity came; else
    the ring may have wrapped, and what it lost is the window up to the
    first event it kept."""
    events = obs.get("events")
    if not events:
        return None
    if capacity is None:
        from deeplearning4j_tpu.telemetry import get_registry
        capacity = get_registry().trace_capacity
    if len(events) < capacity:
        return 0.0
    t0, t1 = obs["window_perf"]
    kept_from = min(_perf_s(obs, e["ts"]) for e in events)
    return 100.0 * min(max(kept_from - t0, 0.0), t1 - t0) / (t1 - t0)


def of_kind(obs: Dict, kind: str, fn, *args):
    """``fn(obs)`` in a cell of the reader's own kind, else None."""
    return fn(obs, *args) if obs.get("kind") == kind else None
