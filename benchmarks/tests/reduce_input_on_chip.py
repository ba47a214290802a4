#!/usr/bin/env python3
"""What ``xplane.reduce`` is handed in one traced run of a cell, kept, so
that the reduction can be run again off the chip: by the walk over every
span that stood before PR 37 and by the sweep that replaced it.

``record`` makes one run exactly as ``run.py`` makes it (``main()``
untouched, the result line printed as ever) and writes the device planes,
the traced window, the spans and ``blocking`` as ``reduce`` received them,
with what ``reduce`` returned and the seconds it took. It needs nothing of
PR 37: copied into a checkout of an older tree it records that tree's run.

    python benchmarks/tests/reduce_input_on_chip.py record \\
        chiprun_out/chat.reduce_input.json.gz \\
        --workload gpt2m-serve-chat --seed 7 --seconds 51 --trace 1

``compare`` (no chip, no JAX) runs both reductions on a recorded input and
prints their seconds, their readings and whether every reading is equal;
``cut`` writes the operations inside ``--ms`` milliseconds of the traced
window with the spans near them, small enough to commit: the last case of
``test_trace_reduction.py``.

    python benchmarks/tests/reduce_input_on_chip.py compare chat.reduce_input.json.gz
    python benchmarks/tests/reduce_input_on_chip.py cut chat.reduce_input.json.gz \\
        benchmarks/tests/data/chat_cut.json.gz --from-ms 1000 --ms 100
"""
from __future__ import annotations

import argparse
import gzip
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))


def pack(devices, window, spans, blocking, **more) -> dict:
    """Names once, rows as [name's index, start_ns, duration or end_ns]."""
    op_names, span_names = {}, {}
    return dict(more, window=list(window), blocking=bool(blocking),
                devices={plane: [[op_names.setdefault(n, len(op_names)), s, d]
                                 for n, s, d in events]
                         for plane, events in devices.items()},
                spans=[[span_names.setdefault(n, len(span_names)), s, e]
                       for n, s, e in spans],
                op_names=list(op_names), span_names=list(span_names))


def unpack(rec: dict):
    """(trace, window, spans, blocking) as ``reduce`` takes them."""
    ops, names = rec["op_names"], rec["span_names"]
    devices = {plane: [(ops[i], s, d) for i, s, d in rows]
               for plane, rows in rec["devices"].items()}
    spans = [(names[i], s, e) for i, s, e in rec["spans"]]
    return ({"devices": devices, "sync_ns": 0.0}, tuple(rec["window"]), spans,
            rec["blocking"])


def read(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def write(path: str, rec: dict) -> None:
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with gzip.open(path, "wt") as f:
        json.dump(rec, f, separators=(",", ":"))


def record(out_path: str, argv) -> int:
    from benchmarks import run as bench_run
    from benchmarks.lib import xplane
    real = xplane.reduce

    def kept(trace, window, host_spans=(), top=10, blocking=False):
        rec = pack(trace["devices"], window, host_spans, blocking,
                   argv=list(argv))
        write(out_path, rec)      # first: the walk may outlast the call
        began = time.perf_counter()
        out = real(trace, window, host_spans, top=top, blocking=blocking)
        took = time.perf_counter() - began
        bench_run.log(f"reduce took {took:.1f}s over {len(host_spans)} spans")
        write(out_path, dict(rec, reduce_s=took, out=out))
        return out

    xplane.reduce = kept
    try:
        return bench_run.main(argv)
    finally:
        xplane.reduce = real


def compare(path: str) -> int:
    from benchmarks.lib import xplane
    sys.path.insert(0, HERE)
    from test_trace_reduction import EVERY, READINGS, _reduce_as_before
    rec = read(path)
    trace, window, spans, blocking = unpack(rec)
    result = {"spans": len(spans), "blocking": blocking, "device_operations":
              sum(len(v) for v in trace["devices"].values()),
              "on_the_chip": {"reduce_s": rec.get("reduce_s"),
                              "idle_gaps": rec.get("out", {}).get("idle_gaps")}}
    outs = {}
    for name, fn in (("sweep", xplane.reduce), ("as_before", _reduce_as_before)):
        began = time.perf_counter()
        out = outs[name] = fn(trace, window, spans, top=EVERY, blocking=blocking)
        result[name] = {"seconds": time.perf_counter() - began,
                        "cost": out.get("cost"),
                        **{k: out[k] for k in READINGS if k != "by_op_s"}}
        print(f"{name}: {result[name]['seconds']:.1f}s", file=sys.stderr,
              flush=True)
    result["every_reading_equal"] = all(
        outs["sweep"][k] == outs["as_before"][k] for k in READINGS)
    print(json.dumps(result, indent=1))
    return 0 if result["every_reading_equal"] else 1


def cut(path: str, out_path: str, from_ms: float, ms: float,
        margin_ns: float = 10e6) -> int:
    """The operations that touch ``ms`` milliseconds of the traced window,
    ``from_ms`` after its start, and the spans within 10 ms of that."""
    trace, (w0, _), spans, blocking = unpack(read(path))
    c0 = w0 + from_ms * 1e6
    c1 = c0 + ms * 1e6
    devices = {plane: [(n, s, d) for n, s, d in events if s + d > c0 and s < c1]
               for plane, events in trace["devices"].items()}
    near = [(n, s, e) for n, s, e in spans
            if e > c0 - margin_ns and s < c1 + margin_ns]
    write(out_path, pack(devices, (c0, c1), near, blocking,
                         source=os.path.basename(path)))
    print(f"{sum(len(v) for v in devices.values())} operations, {len(near)} "
          f"spans, {os.path.getsize(out_path)} bytes")
    return 0


def main() -> int:
    if len(sys.argv) > 2 and sys.argv[1] == "record":
        return record(sys.argv[2], sys.argv[3:])
    ap = argparse.ArgumentParser()
    sub = ap.add_subparsers(dest="mode", required=True)
    sub.add_parser("compare").add_argument("path")
    c = sub.add_parser("cut")
    c.add_argument("path")
    c.add_argument("out")
    c.add_argument("--from-ms", type=float, default=1000.0)
    c.add_argument("--ms", type=float, default=100.0)
    args = ap.parse_args()
    if args.mode == "compare":
        return compare(args.path)
    return cut(args.path, args.out, args.from_ms, args.ms)


if __name__ == "__main__":
    sys.exit(main())
