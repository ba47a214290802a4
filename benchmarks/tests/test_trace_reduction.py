"""The trace reduction gives the readings it gave before PR 37 and holds its
time. Pure Python, no JAX device:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_trace_reduction.py -q -p no:cacheprovider

``_reduce_as_before`` is ``xplane.reduce`` as it stood before PR 37, copied
verbatim but for its name; the harness never imports it. It walked every
span of the whole window for every idle gap of the traced 4 s, which took a
traced serving run past the driver's 1,200 s (PERF.md section 6, PR 37).
``xplane.reduce`` splits the same gaps by the same rule in one sweep and
adds its pieces in the same order, so its readings are held equal to the
oracle's to the last digit, not to a tolerance.
"""
from __future__ import annotations

import importlib
import os
import sys
import time
from typing import Dict, Sequence, Tuple

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmarks.lib import xplane  # noqa: E402
from benchmarks.lib.xplane import (_OPS_LINE, device_clock_shift,  # noqa: E402
                                   op_category, op_family, union)

READINGS = ("busy_s", "window_s", "device_ops", "idle_gaps", "by_op_s",
            "by_category_s", "device_clock_shift_ms")
EVERY = 10 ** 9                      # no cut to the top ten


# --------------------------------------------------------------- the oracle
def _reduce_as_before(trace: Dict, window: Tuple[float, float],
                      host_spans: Sequence[Tuple[str, float, float]] = (),
                      top: int = 10, blocking: bool = False) -> Dict:
    """Busy seconds (averaged over the device planes), the window's
    length, the ``top`` operation families by device time and the idle
    gaps split over the host spans they overlap (innermost span first; what
    no span covers is ``outside-spans``). ``window`` and ``host_spans``
    (name, start_ns, end_ns) are on the trace's clock. ``blocking`` says
    that the spans block on the device's results, which lets the device's
    clock be lined up with the host's (``device_clock_shift``)."""
    w0, w1 = window
    if not trace["devices"]:
        raise ValueError("the trace holds no TPU device plane with an "
                         f"{_OPS_LINE!r} line")
    busy_total = 0.0
    by_op: Dict[str, float] = {}
    by_cat: Dict[str, float] = {}
    gaps: Dict[str, float] = {}
    spans = sorted(host_spans, key=lambda s: s[2] - s[1])   # innermost first
    shifts = []
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
        shift = device_clock_shift(
            merged, union((s, e) for _, s, e in spans)) if blocking else 0.0
        shifts.append(shift)
        edges = [w0] + [t + shift for ab in merged for t in ab] + [w1]
        for i in range(0, len(edges), 2):
            left = [(edges[i], edges[i + 1])] if edges[i + 1] > edges[i] else []
            for name, s, e in spans:
                if not left:
                    break
                rest = []
                for a, b in left:
                    lo, hi = max(a, s), min(b, e)
                    if hi > lo:
                        gaps[name] = gaps.get(name, 0.0) + (hi - lo)
                        if lo > a:
                            rest.append((a, lo))
                        if b > hi:
                            rest.append((hi, b))
                    else:
                        rest.append((a, b))
                left = rest
            for a, b in left:
                gaps["outside-spans"] = gaps.get("outside-spans", 0.0) + (b - a)
    n = len(trace["devices"])
    rank = lambda d: [[k, v / n / 1e9] for k, v in
                      sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {"busy_s": busy_total / n / 1e9, "window_s": (w1 - w0) / 1e9,
            "device_ops": rank(by_op), "idle_gaps": rank(gaps),
            "by_op_s": {k: v / n / 1e9 for k, v in by_op.items()},
            "by_category_s": {k: v / n / 1e9 for k, v in by_cat.items()},
            "device_clock_shift_ms": [x / 1e6 for x in shifts]}


def both(trace, window, spans=(), blocking=False):
    """(new, old) readings of one trace, every idle gap and operation kept."""
    new = xplane.reduce(trace, window, spans, top=EVERY, blocking=blocking)
    old = _reduce_as_before(trace, window, spans, top=EVERY, blocking=blocking)
    return {k: new[k] for k in READINGS}, old


# ----------------------------------------------------------- seeded traces
OPS = ["%fusion.12 = bf16[16,1024] fusion(%p0), kind=kLoop", "copy.4", "sort.3",
       "%paged = bf16[16,16,64] custom-call(%q), custom_call_target=\"tpu_custom_call\"",
       "convert_reduce_fusion.7", "%attn.2 = fusion(%a), kind=kCustom",
       "slice-done.1", "iota_reduce_fusion"]


def random_trace(seed: int):
    """A serving-like trace on whole nanoseconds (so that edges coincide):
    steps of a decode span with a dispatch and a read-back inside (three
    deep under a span that starts before ``w0 - 3 ms`` and ends after
    ``w1``, in one seed of three), spans of equal length, twin spans, spans far outside the
    window, operations back to back, clipped by both edges of the window
    and, on the device's clock, up to 2 ms off the host's. The seed picks
    one or two device planes, ``blocking`` and, in some, no span at all."""
    rng = np.random.default_rng(seed)
    planes, blocking = 1 + seed % 2, bool(seed // 2 % 2)
    w0 = float(rng.integers(0, 5_000_000_000))
    w1 = w0 + float(rng.integers(20_000_000, 60_000_000))
    step = int(rng.integers(1_200_000, 3_000_000))
    skew = float(rng.integers(-2_000_000, 2_000_000))
    spans = []
    if seed % 8 != 7:
        if seed % 3 == 1:     # under it every shift covers alike: the fit reads 0
            spans.append(("fit", w0 - 40_000_000.0, w1 + 40_000_000.0))
        t = w0 - 30_000_000.0
        while t < w1 + 30_000_000.0:
            length = float(int(step * rng.uniform(0.5, 0.9)))
            d0 = t + float(rng.integers(0, 50_000))
            d1 = d0 + float(rng.choice([200_000, 300_000, 400_000]))
            r1 = t + length - float(rng.integers(0, 20_000))
            spans.append(("generation.decode_step", t, t + length))
            spans.append(("generation.dispatch", d0, d1))
            spans.append(("generation.readback", d1 + float(rng.integers(0, 30_000)), r1))
            if rng.random() < 0.1:          # a twin: the order given decides
                spans.append(("generation.verify", d0, d1))
            if rng.random() < 0.05:         # an instant: covers nothing
                spans.append(("generation.emit", d0, d0))
            t += step
        order = rng.permutation(len(spans))
        spans = [spans[i] for i in order]
    devices = {}
    for p in range(planes):
        events = []
        t = w0 - 30_000_000.0 + skew          # in step with the spans
        while t < w1 + 5_000_000.0:
            at = t + float(rng.integers(50_000, 400_000))
            for _ in range(int(rng.integers(3, 12)) if t > w0 - 6_000_000.0 else 0):
                dur = float(rng.integers(1_000, 60_000))
                events.append((OPS[int(rng.integers(len(OPS)))], at, dur))
                at += dur + float(rng.choice([0, 0, 500, 3_000, 20_000]))
            t += step
        events.append(("copy.9", w0 - 70_000.0, 100_000.0))    # clipped at w0
        events.append(("sort.1", w1 - 40_000.0, 90_000.0))     # clipped at w1
        events.append(("copy.8", w0 + 1_000_000.0, 0.0))       # nothing of it
        devices[f"/device:TPU:{p}"] = events
    return {"devices": devices, "sync_ns": 0.0}, (w0, w1), spans, blocking


@pytest.mark.parametrize("seed", range(48))
def test_the_sweep_reads_what_the_walk_over_every_span_read(seed):
    trace, window, spans, blocking = random_trace(seed)
    new, old = both(trace, window, spans, blocking)
    assert new == old
    assert [k for k, _ in new["device_ops"]] == [k for k, _ in old["device_ops"]]
    gaps = dict(new["idle_gaps"])
    assert gaps and all(v > 0 for v in gaps.values())
    if spans:
        assert {"generation.dispatch", "generation.readback"} <= set(gaps)
    else:
        assert set(gaps) == {"outside-spans"}
    if blocking and spans:     # the fit is exercised: not every seed reads 0
        assert all(abs(s) <= 3.0 for s in new["device_clock_shift_ms"])


def test_the_seeded_traces_cover_what_they_are_for():
    """Across the seeds: both plane counts, ``blocking`` on and off, traces
    with no spans, fits that chose a shift other than nought, spans dropped
    and spans kept that straddle the window's edges."""
    seen = {"planes": set(), "blocking": set(), "no_spans": 0, "shifted": 0,
            "dropped": 0}
    for seed in range(48):
        trace, window, spans, blocking = random_trace(seed)
        out = xplane.reduce(trace, window, spans, blocking=blocking)
        seen["planes"].add(len(trace["devices"]))
        seen["blocking"].add(blocking)
        seen["no_spans"] += not spans
        seen["shifted"] += any(s != 0.0 for s in out["device_clock_shift_ms"])
        seen["dropped"] += out["cost"]["spans_kept"] < out["cost"]["spans_given"]
        assert out["cost"]["spans_given"] == len(spans)
    assert seen["planes"] == {1, 2} and seen["blocking"] == {True, False}
    assert seen["no_spans"] >= 4 and seen["shifted"] >= 4 and seen["dropped"] >= 30


# ------------------------------------- the cases test_lib.py already holds
@pytest.mark.parametrize("case", [
    "test_reduce_on_a_synthetic_trace",
    "test_idle_gaps_are_split_over_the_spans_they_overlap",
    "test_device_clock_is_lined_up_with_blocking_spans",
    "test_reduce_the_recorded_trace"])
@pytest.mark.parametrize("which", ["sweep", "as_before"])
def test_the_cases_of_test_lib_hold_for_both(case, which, monkeypatch):
    test_lib = importlib.import_module("test_lib")
    assert test_lib.xplane is xplane
    if which == "as_before":
        monkeypatch.setattr(xplane, "reduce", _reduce_as_before)
    getattr(test_lib, case)()


# ----------------------------------------------------------------- the scale
def chat_like_trace(steps_per_s: float, ops_per_step: int,
                    window_s: float = 51.0, traced_s: float = 4.0):
    """The chat cell's shape: a decode step with a dispatch and a read-back
    inside, ``steps_per_s`` a second over the whole window, and the device's
    operations of the steps inside the traced part, 1.1 ms early."""
    step = 1e9 / steps_per_s
    spans, events = [], []
    for k in range(int(window_s * steps_per_s)):
        t = k * step
        spans.append(("generation.decode_step", t, t + 0.95 * step))
        spans.append(("generation.dispatch", t + 0.02 * step, t + 0.42 * step))
        spans.append(("generation.readback", t + 0.44 * step, t + 0.94 * step))
        if t < traced_s * 1e9:
            each = 0.35 * step / ops_per_step
            for j in range(ops_per_step):
                events.append((OPS[j % len(OPS)],
                               t + 0.05 * step - 1.1e6 + j * each, 0.7 * each))
    return ({"devices": {"/device:TPU:0": events}, "sync_ns": 0.0},
            (0.0, traced_s * 1e9), spans)


@pytest.mark.parametrize("steps_per_s,each,blocking,spans,ops", [
    pytest.param(170.0, 150, True, 26_010, 102_000, id="chat-170-steps-a-second"),
    pytest.param(680.0, 150, False, 104_040, 408_000, id="four-times-the-step-rate"),
    pytest.param(170.0, 1800, False, 26_010, 1_224_000, id="as-many-operations-as-the-chip-showed"),
])
def test_a_chat_sized_trace_is_reduced_in_seconds(steps_per_s, each, blocking, spans, ops):
    """A guard on the order of the cost, not a CPU timing ratio of the kind
    PR 31 took out: at these sizes the sweep takes 4 s, about 2 s and 6-8 s
    here (most of the first is the clock's fit, which the others leave out
    so that the split is what is timed), where the walk over every span
    took more than five minutes, more than an hour and, on the chip, more
    than the 1,300 s its call had left. 30 s leaves four times and more on
    the sweep's side and ten times and more on the walk's. The third is
    what the chip showed in PR 37: 1.25 million operations in chat's
    traced 4 s, 1,800 a step and not the few hundred the issue reckoned;
    there the clock's fit, left as it was, takes 40-50 s on its own."""
    trace, window, host_spans = chat_like_trace(steps_per_s, each)
    assert len(host_spans) == spans
    assert len(trace["devices"]["/device:TPU:0"]) == ops
    began = time.perf_counter()
    out = xplane.reduce(trace, window, host_spans, blocking=blocking)
    took = time.perf_counter() - began
    assert took < 30.0, out["cost"]
    cost = out["cost"]
    assert cost["spans_given"] == spans and cost["spans_kept"] < spans / 10
    assert cost["device_ops"] >= ops - each and cost["gaps"] >= ops - each
    gaps = dict(out["idle_gaps"])
    if blocking:
        assert out["device_clock_shift_ms"] == [pytest.approx(1.1, abs=0.31)]
    else:        # unshifted, busy and idle make up the window
        assert out["busy_s"] + sum(gaps.values()) == pytest.approx(out["window_s"])
    assert set(gaps) <= {"generation.decode_step", "generation.dispatch",
                         "generation.readback", "outside-spans"}


# ------------------------------------------------------ one real trace, cut
def test_a_cut_of_a_recorded_chat_trace_reads_the_same():
    """100 ms of ``gpt2m-serve-chat``'s traced window as ``run.py`` handed it
    to ``reduce`` on a TPU v5e (seed 3700000011; 34,480 of its 801,417
    device operations and 74 of its 19,173 spans):
    ``reduce_input_on_chip.py`` ``record`` there, ``cut`` here. The whole
    input is 5 MB packed and is not kept; 200 ms of it would be 500 KB."""
    from reduce_input_on_chip import read, unpack
    trace, window, spans, blocking = unpack(
        read(os.path.join(HERE, "data", "chat_cut.json.gz")))
    assert blocking and window[1] - window[0] <= 100e6
    assert sum(len(v) for v in trace["devices"].values()) > 1000
    assert {"generation.decode_step", "generation.dispatch",
            "generation.readback"} <= {n for n, _, _ in spans}
    new, old = both(trace, window, spans, blocking)
    assert new == old
    assert dict(new["idle_gaps"])["generation.readback"] > 0
    assert xplane.reduce(trace, window, spans, blocking=True)["cost"][
        "spans_kept"] < len(spans)
