"""Reduction from a profiler trace (``.xplane.pb``) to busy and idle
share, time by operation, and idle gaps named by the host span they fall
in. Reads with nothing but ``jax.profiler.ProfileData``.

Times in an xplane are nanoseconds from the start of the profile. The
harness writes one ``bench.sync`` annotation into the trace and notes the
wall clock beside it; that pair puts the program's spans (wall clock) on
the trace's clock.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
import time
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

SYNC_NAME = "bench.sync"
_OPS_LINE = "XLA Ops"
_INSTANCE = re.compile(r"[.\-_]?\d+$")
REACH_NS = 3e6        # how far the device's clock may be shifted onto the host's


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path: str) -> Dict:
    """{"devices": {plane name: [(op name, start_ns, dur_ns)]},
    "sync_ns": start of the bench.sync annotation or None}."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[str, List[Tuple[str, float, float]]] = {}
    lines: Dict[str, Dict] = {}
    sync = None
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            for line in plane.lines:
                evs = [(e.name, float(e.start_ns), float(e.duration_ns))
                       for e in line.events]
                if evs:
                    lines[f"{plane.name}/{line.name}"] = {
                        "events": len(evs), "first_ns": min(e[1] for e in evs),
                        "last_ns": max(e[1] + e[2] for e in evs),
                        "sum_s": sum(e[2] for e in evs) / 1e9}
                if line.name == _OPS_LINE:
                    devices[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == SYNC_NAME and sync is None:
                        sync = float(e.start_ns)
    return {"devices": devices, "sync_ns": sync, "lines": lines}


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merged, sorted [start, end) intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def op_family(name: str) -> str:
    """An operation's name without its instance number: ``fusion.123`` ->
    ``fusion``, ``copy.4`` -> ``copy``; ``%`` and a module prefix dropped."""
    name = name.lstrip("%").split(" ")[0].split("(")[0]
    prev = None
    while prev != name:
        prev, name = name, _INSTANCE.sub("", name)
    return name or prev


def op_category(text: str) -> str:
    """``mosaic`` for a Pallas/Mosaic custom call (alone or inside a
    fusion of kind kCustom), else ``xla``: read off the operation's HLO
    text, which is the event's name in the ``XLA Ops`` line."""
    if " custom-call(" in text or "kind=kCustom" in text \
            or "tpu_custom_call" in text:
        return "mosaic"
    return "xla"


def _overlap(xs: List[Tuple[float, float]], ys: List[Tuple[float, float]]) -> float:
    """Length of the intersection of two merged, sorted interval lists."""
    i = j = 0
    total = 0.0
    while i < len(xs) and j < len(ys):
        lo, hi = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if hi > lo:
            total += hi - lo
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return total


def device_clock_shift(busy: List[Tuple[float, float]],
                       blocking: List[Tuple[float, float]],
                       reach_ns: float = REACH_NS, step_ns: float = 1e5) -> float:
    """The device plane's clock runs a millisecond or so off the host
    plane's (in the recorded trace a module starts 1.1 ms before the host
    dispatches it). Where the host's spans block on the device's result,
    device work lies inside them: the shift of the device's intervals,
    within +-``reach_ns``, that puts most of them inside the spans."""
    if not busy or not blocking:
        return 0.0
    best, best_cover = 0.0, -1.0
    n = int(reach_ns / step_ns)
    for k in sorted(range(-n, n + 1), key=abs):
        d = k * step_ns
        cover = _overlap([(a + d, b + d) for a, b in busy], blocking)
        if cover > best_cover + 1.0:          # ties go to the smaller shift
            best, best_cover = d, cover
    return best


def _split_gaps(gaps: List[Tuple[float, float]],
                spans: List[Tuple[str, float, float]],
                into: Dict[str, float]) -> None:
    """Add to ``into`` the length of every piece of the ``gaps`` (sorted,
    disjoint) under the name of the first span of ``spans`` (innermost
    first) that overlaps it, and what no span covers under
    ``outside-spans``. One sweep: the gaps in time order against the spans
    by start, so that a gap is split over the few spans open in it and
    never meets the others."""
    by_start = sorted(range(len(spans)), key=lambda i: spans[i][1])
    nxt = 0
    open_ = []                        # ranks in ``spans`` of the spans open
    for a, b in gaps:
        while nxt < len(by_start) and spans[by_start[nxt]][1] < b:
            bisect.insort(open_, by_start[nxt])
            nxt += 1
        open_ = [i for i in open_ if spans[i][2] > a]    # ended: never again
        left = [(a, b)]
        for i in open_:
            if not left:
                break
            name, s, e = spans[i]
            rest = []
            for a_, b_ in left:
                lo, hi = max(a_, s), min(b_, e)
                if hi > lo:
                    into[name] = into.get(name, 0.0) + (hi - lo)
                    if lo > a_:
                        rest.append((a_, lo))
                    if b_ > hi:
                        rest.append((hi, b_))
                else:
                    rest.append((a_, b_))
            left = rest
        for a_, b_ in left:
            into["outside-spans"] = into.get("outside-spans", 0.0) + (b_ - a_)


def reduce(trace: Dict, window: Tuple[float, float],
           host_spans: Sequence[Tuple[str, float, float]] = (),
           top: int = 10, blocking: bool = False) -> Dict:
    """Busy seconds (averaged over the device planes), the window's
    length, the ``top`` operation families by device time and the idle
    gaps split over the host spans they overlap (innermost span first; what
    no span covers is ``outside-spans``). ``window`` and ``host_spans``
    (name, start_ns, end_ns) are on the trace's clock. ``blocking`` says
    that the spans block on the device's results, which lets the device's
    clock be lined up with the host's (``device_clock_shift``).

    Only the spans within ``REACH_NS`` of the window can meet a gap or
    move the clock's fit (the shifted gaps lie inside that), so the others
    are dropped first. ``cost`` says what the reduction itself took."""
    w0, w1 = window
    if not trace["devices"]:
        raise ValueError("the trace holds no TPU device plane with an "
                         f"{_OPS_LINE!r} line")
    busy_total = 0.0
    by_op: Dict[str, float] = {}
    by_cat: Dict[str, float] = {}
    gaps: Dict[str, float] = {}
    spans = sorted((s for s in host_spans
                    if s[2] > w0 - REACH_NS and s[1] < w1 + REACH_NS),
                   key=lambda s: s[2] - s[1])               # innermost first
    blocking_union = union((s, e) for _, s, e in spans) if blocking else []
    shifts = []
    cost = {"clock_fit_s": 0.0, "split_s": 0.0, "device_ops": 0, "gaps": 0,
            "spans_given": len(host_spans), "spans_kept": len(spans)}
    for events in trace["devices"].values():
        clipped = []
        for name, s, d in events:
            a, b = max(s, w0), min(s + d, w1)
            if b > a:
                clipped.append((a, b))
                fam = op_family(name)
                by_op[fam] = by_op.get(fam, 0.0) + (b - a)
                cat = op_category(name)
                by_cat[cat] = by_cat.get(cat, 0.0) + (b - a)
        merged = union(clipped)
        busy_total += sum(b - a for a, b in merged)
        began = time.perf_counter()
        shift = device_clock_shift(merged, blocking_union) if blocking else 0.0
        shifts.append(shift)
        fitted = time.perf_counter()
        edges = [w0] + [t + shift for ab in merged for t in ab] + [w1]
        idle = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        _split_gaps(idle, spans, gaps)
        cost["clock_fit_s"] += fitted - began
        cost["split_s"] += time.perf_counter() - fitted
        cost["device_ops"] += len(clipped)
        cost["gaps"] += len(idle)
    n = len(trace["devices"])
    rank = lambda d: [[k, v / n / 1e9] for k, v in
                      sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {"busy_s": busy_total / n / 1e9, "window_s": (w1 - w0) / 1e9,
            "device_ops": rank(by_op), "idle_gaps": rank(gaps),
            "by_op_s": {k: v / n / 1e9 for k, v in by_op.items()},
            "by_category_s": {k: v / n / 1e9 for k, v in by_cat.items()},
            "device_clock_shift_ms": [x / 1e6 for x in shifts], "cost": cost}
