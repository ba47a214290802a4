#!/usr/bin/env python3
"""One run of a cell exactly as ``run.py`` makes it (``main()`` untouched,
the result line printed as ever), and after it a summary of the program's
spans, written as JSON: for each span of the window its count, sum, median,
p90 and longest; every span of the whole run (warm-up and pre-roll too)
that took over 250 ms and four times its median; requests completed per
5 s; the collector's full passes; the process's CPU seconds in the window.

It is how PR 26 found what a slow run is (PERF.md §5): every median as in
the other runs and one ``generation.decode_step`` of 2.9 s. A --trace 0
run records the same spans as a traced one, so this costs no profiler.

    python benchmarks/tests/spans_on_chip.py chiprun_out/long_1.spans.json \\
        --workload gpt2m-serve-longprompt --seed 7 --seconds 51 --trace 0
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks import run as bench_run     # T_START as in a plain run  # noqa: E402

import collections  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402

from benchmarks.lib.stats import percentile  # noqa: E402


def span_key(e: dict) -> str:
    a = e.get("args", {})
    if e["name"] == "generation.prefill":
        return f"generation.prefill[b{a.get('batch')},r{a.get('rung')}]"
    if e["name"] in ("generation.dispatch", "generation.readback"):
        return f"{e['name']}[{a.get('program')}]"
    return e["name"]


def summarize(obs: dict, all_events: list, collections_seen: list,
              cpu_marks: list) -> dict:
    t0, t1 = obs.get("window_perf", (bench_run.T_START, time.perf_counter()))
    off = obs["epoch_ns"]

    def at(e):                      # the event's start on perf_counter
        return (e["ts"] * 1000 - off) / 1e9

    found = collections.defaultdict(list)
    for e in obs.get("events", []):
        if e.get("ph") == "X" and t0 <= at(e) <= t1:
            found[span_key(e)].append(e["dur"] / 1e3)
    out = {"window_from_process_start_s": [t0 - bench_run.T_START,
                                           t1 - bench_run.T_START],
           "spans_ms": {k: {"n": len(v), "sum": sum(v),
                            "p50": percentile(v, 50), "p90": percentile(v, 90),
                            "max": max(v)} for k, v in sorted(found.items())}}
    by_name = collections.defaultdict(list)
    for e in all_events:
        if e.get("ph") == "X":
            by_name[e["name"]].append(e["dur"] / 1e3)
    median = {k: percentile(v, 50) for k, v in by_name.items()}
    out["long_spans"] = [
        {"name": span_key(e), "ms": e["dur"] / 1e3,
         "median_ms": median[e["name"]],
         "from_window_start_s": round(at(e) - t0, 3)}
        for e in all_events if e.get("ph") == "X"
        and e["dur"] / 1e3 > max(250.0, 4 * median[e["name"]])]
    if "done" in obs:
        ends = [r["end"] for r in obs["done"]]
        out["completed_by_5s"] = [
            sum(1 for x in ends if t0 + 5 * i <= x < t0 + 5 * (i + 1))
            for i in range(int((t1 - t0) // 5) + 1)]
    out["full_collections_in_window"] = [
        {"from_window_start_s": round(s - t0, 2), "ms": round(d * 1e3, 2)}
        for s, d, gen in collections_seen if gen == 2 and t0 <= s <= t1]
    inside = [c for t, c in cpu_marks if t0 <= t <= t1]
    if len(inside) >= 2:
        out["process_cpu_s_in_window"] = inside[-1] - inside[0]
    return out


def main() -> int:
    out_path, argv = sys.argv[1], sys.argv[2:]
    stash, collections_seen, cpu_marks, started = {}, [], [], []

    def on_collection(phase, info):
        # every collection also marks the process's CPU time: a few a
        # second, no thread of the observer's own
        now = time.perf_counter()
        if phase == "start":
            started.append(now)
        else:
            begun = started.pop()
            collections_seen.append((begun, now - begun, info["generation"]))
            cpu_marks.append((now, time.process_time()))

    def keep_obs(run):
        def inner(ctx):
            res = run(ctx)
            stash["obs"] = dict(res["obs"], epoch_ns=ctx["epoch_ns"])
            return res
        return inner

    from benchmarks.kinds import closed_loop, fit_cycle, open_loop
    for kind in (closed_loop, fit_cycle, open_loop):
        kind.run = keep_obs(kind.run)
    gc.callbacks.append(on_collection)
    rc = bench_run.main(argv)
    gc.callbacks.remove(on_collection)

    from deeplearning4j_tpu import telemetry
    summary = summarize(stash["obs"],
                        telemetry.get_registry().trace_events_since(0),
                        collections_seen, cpu_marks)
    summary["argv"] = argv
    os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(summary, f, indent=1)
    return rc


if __name__ == "__main__":
    sys.exit(main())
