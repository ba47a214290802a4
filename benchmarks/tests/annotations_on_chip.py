#!/usr/bin/env python3
"""One run of a cell as ``benchmarks/run.py`` makes it, plus what the harness
does not keep and PR 24 had to report by hand. Run on the chip; writes
``chiprun_out/annotations/<cell>-<seed>-t<trace>.json`` and prints the
result line last, as ``run.py`` does (on the chip with every metric the kind
computed under ``e2e``, traced or not; a CPU rehearsal of a toy cell prints
counts only).

    python benchmarks/tests/annotations_on_chip.py --workload gpt2m-serve-chat --seed 7 --seconds 51 --trace 1

What it adds to the result:

- ``window_events``: how many trace events the window recorded, by name
  (the registry's ring holds 65,536);
- traced, before ``Tracer.finish`` deletes the raw ``.xplane.pb``: the
  program's span paths found as events of the host plane
  (``host_span_events``), the registry's stamps against the annotations
  through ``bench.sync`` (``annotation_vs_registry_clock_ms``), and bounds
  on the device plane's clock offset (``offset_bounds_ms``, below);
- ``seconds_inside_traced_window``: the time of the program's spans and
  phase events inside the traced 4 s, for the split of the idle;
- open loop: which requests missed the first-token limit and when they
  were due, the admissions that waited over 150 ms, and the decode steps
  that ran with every slot taken.

The offset bounds. With device stamp = true time + o: the device is done
before the read-back returns, and cannot start before the next dispatch
begins. For the idle gap between two decode steps, seen at [g0, g1] on the
device plane, t the annotated ``generation.readback`` end before it and d
the annotated start of the next ``generation.dispatch``:
``g0 - t <= o <= g1 - d``; the largest lower and the smallest upper bound
over the steps are reported (``low_max``, ``high_min``).
"""
from __future__ import annotations

import argparse
import bisect
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from benchmarks import run as harness  # noqa: E402  (T_START is taken here)

READBACK = "generation.decode_step/generation.readback"
DISPATCH = "/generation.dispatch"


def _summary(values):
    v = sorted(values)
    return {"n": len(v), "min": v[0], "p50": v[len(v) // 2], "max": v[-1]}


def analyse_xplane(path, tracer, events):
    from jax.profiler import ProfileData

    from benchmarks.lib import xplane
    pd = ProfileData.from_file(path)
    host_names, readback_ends, dispatch_starts, ops, sync = {}, [], [], [], None
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == xplane.SYNC_NAME and sync is None:
                        sync = float(e.start_ns)
                    if not e.name.startswith(("generation.", "fit")):
                        continue
                    host_names[e.name] = host_names.get(e.name, 0) + 1
                    if e.name.endswith(READBACK):
                        readback_ends.append(float(e.start_ns + e.duration_ns))
                    if e.name.endswith(DISPATCH):
                        dispatch_starts.append(float(e.start_ns))
        elif plane.name.startswith("/device:") and "TPU" in plane.name:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = [(float(e.start_ns), float(e.start_ns + e.duration_ns))
                           for e in line.events]
    out = {"xplane_bytes": os.path.getsize(path),
           "host_span_events": host_names, "device_ops": len(ops)}
    # the device's idle gaps of a millisecond or more: between two steps
    busy = xplane.union((a, b + 2e4) for a, b in ops)
    gaps = [(busy[i][1] - 2e4, busy[i + 1][0]) for i in range(len(busy) - 1)
            if busy[i + 1][0] - busy[i][1] + 2e4 >= 1e6]
    dispatch_starts.sort()
    lows, highs, lengths = [], [], []
    for t in readback_ends:
        near = [g for g in gaps if abs(g[0] - t) < 8e6]
        j = bisect.bisect_left(dispatch_starts, t - 1e6)
        if near and j < len(dispatch_starts) and dispatch_starts[j] - t < 5e6:
            g0, g1 = min(near, key=lambda g: abs(g[0] - t))
            lows.append((g0 - t) / 1e6)
            highs.append((g1 - dispatch_starts[j]) / 1e6)
            lengths.append((g1 - g0) / 1e6)
    if lows:
        out["offset_bounds_ms"] = {
            "low_max": max(lows), "high_min": min(highs),
            "gap_start_minus_readback_end": _summary(lows),
            "gap_end_minus_next_dispatch_start": _summary(highs),
            "gap_ms": _summary(lengths)}
    if sync is not None and readback_ends:
        off = tracer.sync_wall_ns - sync               # wall = trace + off
        wall = sorted((e["ts"] + e["dur"]) * 1000 for e in events
                      if e.get("name") == "generation.readback"
                      and e.get("args", {}).get("program") == "decode")
        apart = []
        for t in readback_ends:
            i = bisect.bisect_left(wall, t + off)
            apart += [min(abs(wall[j] - t - off) for j in (i - 1, i)
                          if 0 <= j < len(wall)) / 1e6] if wall else []
        if apart:
            out["annotation_vs_registry_clock_ms"] = _summary(apart)
    return out


def open_loop_tail(ctx, obs, events, tracer):
    """Who missed the first-token limit, and what the scheduler saw then."""
    t0 = obs["window_perf"][0]
    at = lambda e: round((e["ts"] * 1000 - ctx["epoch_ns"]) / 1e9 - t0, 2)
    limit = ctx["traffic"].get("slo", {}).get("ttft_ms", 250.0)
    late = [(round(r["due"], 3), round((r["stamps"][0] - (t0 + r["due"])) * 1e3, 1))
            for r in obs["summary"]["window"] if r["stamps"]
            and (r["stamps"][0] - (t0 + r["due"])) * 1e3 > limit]
    full = [at(e) for e in events if e.get("name") == "generation.decode_step"
            and e.get("ph") == "X"
            and e["args"].get("slots") == obs["engine"]["decode_slots"]]
    out = {"limit_ms": limit, "over_limit_due_s_and_ttft_ms": late,
           "admit_waits_over_150ms_at_s_and_queue_ms": [
               (at(e), e["args"]["queue_ms"]) for e in events
               if e.get("name") == "generation.admit"
               and e["args"].get("queue_ms", 0) > 150.0],
           "steps_with_every_slot_taken": {"n": len(full), "first_s": full[:1],
                                           "last_s": full[-1:]}}
    if tracer.window_wall_ns:
        out["traced_part_s"] = [(w - ctx["epoch_ns"]) / 1e9 - t0
                                for w in tracer.window_wall_ns]
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    ctx, bench, kind = harness.prepare(args.workload, args.seed, args.seconds,
                                       bool(args.trace))
    from benchmarks.lib import xplane
    from deeplearning4j_tpu import telemetry
    tracer, reg = ctx["tracer"], telemetry.get_registry()
    extra = {"cell": args.workload, "seed": args.seed, "trace": args.trace}
    load = xplane.load

    def load_and_look(path):
        # Tracer.finish() deletes the raw trace once it is reduced: look at
        # it here, when finish() loads it; the run's own result stands
        try:
            extra["xplane"] = analyse_xplane(path, tracer, reg.trace_events())
        except Exception as e:
            extra["xplane_error"] = repr(e)
        return load(path)
    xplane.load = load_and_look

    res = kind.run(ctx)
    obs = dict(res["obs"], epoch_ns=ctx["epoch_ns"])
    events = obs.get("events")
    if events is None:              # a fit_cycle run keeps no window mark
        events = reg.trace_events()
    by_name = {}
    for e in events:
        key = f"{e.get('name')}|{e.get('ph')}|{e.get('cat')}"
        by_name[key] = by_name.get(key, 0) + 1
    extra["window_events"] = len(events)
    extra["window_events_by_name"] = dict(sorted(by_name.items(),
                                                 key=lambda kv: -kv[1]))
    extra["ring"] = {"capacity": reg.trace_capacity,
                     "dropped": reg.trace_dropped}
    if tracer.window_wall_ns:
        w0, w1 = tracer.window_wall_ns
        inside = {}
        for e in events:
            if e.get("ph") == "X" and e.get("cat") in ("span", "phase"):
                a = max(e["ts"] * 1000, w0)
                b = min((e["ts"] + e["dur"]) * 1000, w1)
                if b > a:
                    inside[e["name"]] = inside.get(e["name"], 0.0) + (b - a) / 1e9
        extra["seconds_inside_traced_window"] = inside
    if obs.get("kind") == "open_loop":
        extra["first_tokens"] = open_loop_tail(ctx, obs, events, tracer)

    cell, checks = ctx["cell"], ctx["checks"]
    dev = dict(ctx["device"], memory_peak_bytes=res["memory_peak_bytes"])
    line = {"correct": checks.correct, "attempted": res["attempted"],
            "failed": res["failed"], "device": dev}
    if ctx["rehearsal"]:
        # a CPU run reports counts only, never under a device metric's name
        line["metrics"] = {"rehearsal." + k: {"value": v, "unit": "count"}
                           for k, v in res["counts"].items()}
        if args.trace:
            line["rehearsal_layer_metrics_read"] = sorted(
                harness.read_layer_metrics(bench, harness._stands_for(cell),
                                           obs, True))
    else:
        line["e2e"] = {k: float(v) for k, v in res["metrics"].items()}
    if args.trace and not ctx["rehearsal"]:
        reduced = obs["trace"]
        dev["busy_s"], dev["window_s"] = reduced["busy_s"], reduced["window_s"]
        line["metrics"] = harness.read_layer_metrics(bench, cell["name"], obs)
        line["breakdown"] = {"device_ops": reduced["device_ops"],
                             "idle_gaps": reduced["idle_gaps"]}
        extra["device_clock_shift_ms"] = reduced["device_clock_shift_ms"]
    line["checks"] = checks.rows
    extra["line"] = line
    out = os.path.join(ROOT, "chiprun_out", "annotations")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"{args.workload}-{args.seed}-t{args.trace}.json"),
              "w") as f:
        json.dump(extra, f, indent=1)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
