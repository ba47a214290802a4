#!/usr/bin/env python
"""One-page offline performance report from a perf dump.

    python tools/perf_report.py perf_dump.json[.gz]
    python tools/perf_report.py flightrec_*.json      # black boxes work too
    python tools/perf_report.py dump.json --json

Reads the file written by ``telemetry.write_perf_dump`` (the
``{"perf_dump": 1, ...}`` shape) OR a flight-recorder dump (which embeds
the same ``perf`` block), gzipped or not, and renders:

  - **Roofline table** — one row per captured program (span-path keyed):
    FLOPs/step, bytes/step, arithmetic intensity, compute- vs
    memory-bound, measured step time, achieved TFLOP/s and MFU.
    MFU here is recomputed IN THIS TOOL from the dumped flops + step
    time + peak (not just echoed), so the report cross-checks the live
    gauges; a row whose recomputation disagrees with the dumped gauge
    is flagged.
  - **Step-time decomposition** — compute / input-wait / host ms per
    step with shares: "why is steps/sec down" at a glance.
  - **Memory top-K** — live-array groups by (shape, dtype, owner) and
    per-device totals.

Like the other tools/ CLIs, this file must stay importable without the
package (no jax): stdlib only. Peak TFLOP/s for the MFU recomputation
comes from the dump (which stamps the peak of the device it was folded
on), else the BENCH_PEAK_TFLOPS override; a dump from a device with no
known peak gets no MFU column, never another chip's.
"""
from __future__ import annotations

import argparse
import gzip
import json
import os
import sys
from typing import List, Optional


def _read_text(path: str) -> str:
    with open(path, "rb") as f:
        magic = f.read(2)
    if path.endswith(".gz") or magic == b"\x1f\x8b":
        with gzip.open(path, "rt") as f:
            return f.read()
    with open(path) as f:
        return f.read()


def load_dump(path: str) -> dict:
    """Normalize a perf dump / flight-recorder dump / bare registry
    snapshot into {perf, metrics, trigger?}."""
    data = json.loads(_read_text(path))
    if not isinstance(data, dict):
        raise ValueError(f"{path}: not a JSON object")
    if "perf_dump" in data or "flightrec" in data:
        out = {"perf": data.get("perf", {}),
               "metrics": data.get("metrics", {})}
        if "trigger" in data:
            out["trigger"] = data["trigger"]
        return out
    if "counters" in data or "gauges" in data:   # bare snapshot
        return {"perf": {}, "metrics": data}
    raise ValueError(f"{path}: neither a perf dump, a flight-recorder "
                     "dump, nor a registry snapshot")


def _peak_tflops(dump: dict) -> Optional[float]:
    # the dump stamps the peak it was folded against (perf_snapshot);
    # the env override serves older or hand-built dumps
    v = dump.get("perf", {}).get("peak_tflops") \
        or os.environ.get("BENCH_PEAK_TFLOPS")
    return float(v) if v else None


def _fmt_bytes(n) -> str:
    if n is None:
        return "-"
    n = float(n)
    for unit in ("B", "KB", "MB", "GB", "TB"):
        if abs(n) < 1024 or unit == "TB":
            return f"{n:.1f}{unit}" if unit != "B" else f"{int(n)}B"
        n /= 1024
    return "-"


def _fmt(v, nd=3) -> str:
    if v is None:
        return "-"
    if isinstance(v, float):
        return f"{v:.{nd}g}" if abs(v) < 1e-3 or abs(v) >= 1e6 \
            else f"{round(v, nd)}"
    return str(v)


def roofline_rows(dump: dict) -> List[dict]:
    """The roofline table with MFU RECOMPUTED from flops + step time —
    an independent pass over the dumped inputs that cross-checks the
    live gauge values (``mfu_gauge`` is what the fold published)."""
    peak = _peak_tflops(dump)
    rows = []
    for row in dump.get("perf", {}).get("programs", []) or []:
        flops, step_ms = row.get("flops_per_step"), row.get("step_ms")
        mfu = achieved = None
        if flops and step_ms:
            achieved = float(flops) / (float(step_ms) / 1e3) / 1e12
            mfu = achieved / peak if peak else None
        rows.append({
            "path": row.get("path", "?"),
            "flops_per_step": flops,
            "bytes_per_step": row.get("bytes_per_step"),
            "intensity": row.get("intensity"),
            "roofline": row.get("roofline", "?"),
            "step_ms": step_ms,
            "achieved_tflops": achieved,       # full precision: renderers
            "mfu": mfu,                        # format, comparisons don't
            "mfu_gauge": row.get("mfu"),
            "source": row.get("source", "?"),
            "implausible": bool(row.get("implausible")),
            # only meaningful MFUs can disagree: sub-0.1% values round to
            # zero in the gauges (toy CPU programs) — flagging those would
            # cry wolf on every small-model dump
            "gauge_disagrees": (
                mfu is not None and row.get("mfu") is not None
                and max(mfu, row["mfu"]) > 1e-3
                and abs(mfu - row["mfu"]) > 0.05 * max(mfu, row["mfu"])),
        })
    rows.sort(key=lambda r: -(r["flops_per_step"] or 0))
    return rows


def format_roofline(rows: List[dict]) -> str:
    if not rows:
        return "(no captured programs — did the run fold the cost index?)"
    wp = max(max(len(r["path"]) for r in rows), len("program"))
    head = (f"{'program':<{wp}}  {'flops/step':>12}  {'bytes/step':>10}  "
            f"{'int.':>7}  {'bound':<7}  {'step_ms':>9}  {'TFLOP/s':>8}  "
            f"{'MFU':>8}  src")
    lines = [head, "-" * len(head)]
    for r in rows:
        mfu = f"{r['mfu']:.2%}" if r["mfu"] is not None else "-"
        flags = ""
        if r["implausible"]:
            flags += " !implausible"
        if r["gauge_disagrees"]:
            flags += " !gauge-mismatch"
        lines.append(
            f"{r['path']:<{wp}}  {_fmt(r['flops_per_step']):>12}  "
            f"{_fmt_bytes(r['bytes_per_step']):>10}  "
            f"{_fmt(r['intensity']):>7}  {r['roofline']:<7}  "
            f"{_fmt(r['step_ms'], 4):>9}  "
            f"{_fmt(r['achieved_tflops']):>8}  {mfu:>8}  "
            f"{r['source']}{flags}")
    return "\n".join(lines)


def format_decomposition(dump: dict) -> str:
    d = dump.get("perf", {}).get("step_decomposition") or {}
    parts = [(k, v) for k, v in d.items()
             if isinstance(v, dict) and "p50" in v]
    if not parts:
        return "(no step decomposition recorded)"
    shares = d.get("shares", {})
    head = (f"{'component':<16}  {'p50_ms':>8}  {'p95_ms':>8}  "
            f"{'mean_ms':>8}  {'samples':>7}  share")
    lines = [head, "-" * len(head)]
    for name, v in parts:
        share = shares.get(name)
        lines.append(f"{name:<16}  {_fmt(v['p50'], 4):>8}  "
                     f"{_fmt(v['p95'], 4):>8}  {_fmt(v['mean'], 4):>8}  "
                     f"{v.get('count', '-'):>7}  "
                     f"{f'{share:.1%}' if share is not None else '-'}")
    if "collective_ms" in d:
        lines.append(f"{'collective_ms':<16}  (gauge) "
                     f"{_fmt(d['collective_ms'], 4)}")
    return "\n".join(lines)


def format_memory(dump: dict) -> str:
    m = dump.get("perf", {}).get("memory") or {}
    if not m:
        return "(no memory profile in dump)"
    lines = [f"live arrays: {m.get('live_arrays', '-')}   total: "
             f"{_fmt_bytes(m.get('total_live_bytes'))}"]
    per_dev = m.get("live_bytes_by_device") or {}
    if per_dev:
        lines.append("per device: " + "  ".join(
            f"{d}={_fmt_bytes(v)}" for d, v in sorted(per_dev.items())))
    top = m.get("top") or []
    if top:
        head = (f"{'shape':<26}  {'dtype':<10}  {'owner':<24}  "
                f"{'count':>6}  bytes")
        lines += [head, "-" * len(head)]
        for r in top:
            shape = "x".join(str(d) for d in r.get("shape", [])) or "()"
            lines.append(f"{shape:<26}  {r.get('dtype', '?'):<10}  "
                         f"{str(r.get('owner', '?')):<24}  "
                         f"{r.get('count', 0):>6}  "
                         f"{_fmt_bytes(r.get('total_bytes'))}")
    return "\n".join(lines)


def render(dump: dict) -> str:
    sections = []
    if "trigger" in dump:
        sections.append(f"(from flight-recorder dump, trigger="
                        f"{dump['trigger']})")
    sections.append("== Roofline: per-program cost & utilization ==\n"
                    + format_roofline(roofline_rows(dump)))
    sections.append("== Step-time decomposition (per step) ==\n"
                    + format_decomposition(dump))
    sections.append("== Memory: live arrays ==\n" + format_memory(dump))
    return "\n\n".join(sections)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Offline performance report from a perf/flightrec "
                    "dump")
    ap.add_argument("dump", help="perf dump, flight-recorder dump, or "
                                 "registry snapshot (.gz ok)")
    ap.add_argument("--json", action="store_true",
                    help="emit the computed report data as JSON")
    args = ap.parse_args(argv)
    dump = load_dump(args.dump)
    if args.json:
        print(json.dumps({"roofline": roofline_rows(dump),
                          "decomposition":
                              dump.get("perf", {}).get(
                                  "step_decomposition") or {},
                          "memory": dump.get("perf", {}).get("memory")},
                         indent=2))
        return 0
    print(render(dump))
    return 0


if __name__ == "__main__":
    sys.exit(main())
