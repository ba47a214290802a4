#!/usr/bin/env python
"""Kernel-library report: registered kernels, active impls, autotune
decisions, measured-vs-roofline flags.

    python tools/kernels_report.py perf_dump.json          # from a dump
    python tools/kernels_report.py --autotune-cache ~/.cache/deeplearning4j_tpu/autotune.json
    python tools/kernels_report.py perf_dump.json --json

Reads the ``kernels`` block that ``telemetry.perf.perf_snapshot()``
embeds in every perf dump / flight-recorder black box (written by
``ops/kernels/registry.kernels_snapshot()``), the live
``perf.kernels.<name>.*`` gauges riding the dump's metrics snapshot, and
the autotune decision cache JSON (``DL4J_TPU_AUTOTUNE_CACHE``). Renders:

  - **Kernel table** — impl active on the dumping rig (fused /
    interpret / fallback), kill switch + legacy aliases, parity-pin
    presence, hand-tuned default block choice;
  - **Autotune decisions** — per (kernel, shape-sig, backend): the
    chosen blocks, whether measurement CHANGED the default (or the
    recorded reason defaults stand), replay count (proof the cache
    short-circuits re-measurement), best measured candidate times;
  - **Roofline check** — measured vs roofline ms per kernel from the
    gauges, flagging anything > 2x over its bound.

Like the other tools/ CLIs this must stay importable WITHOUT the
package (no jax import): stdlib only.
"""
from __future__ import annotations

import argparse
import gzip
import json
import os
import sys
from typing import Dict, List, Optional

ROOFLINE_FLAG_RATIO = 2.0


def _read_text(path: str) -> str:
    with open(path, "rb") as f:
        magic = f.read(2)
    if path.endswith(".gz") or magic == b"\x1f\x8b":
        with gzip.open(path, "rt") as f:
            return f.read()
    with open(path) as f:
        return f.read()


def default_cache_path() -> str:
    p = os.environ.get("DL4J_TPU_AUTOTUNE_CACHE")
    if p:
        return p
    return os.path.join(os.path.expanduser("~"), ".cache",
                        "deeplearning4j_tpu", "autotune.json")


def load_dump(path: str) -> dict:
    """{kernels, gauges} from a perf dump / flight-recorder dump."""
    data = json.loads(_read_text(path))
    if not isinstance(data, dict):
        raise ValueError(f"{path}: not a JSON object")
    perf = data.get("perf", data) or {}
    metrics = data.get("metrics", {}) or {}
    gauges = metrics.get("gauges", {}) or {}
    return {"kernels": perf.get("kernels", {}) or {}, "gauges": gauges}


def load_autotune(path: str) -> Dict[str, dict]:
    """decisions dict from the autotune cache file ({} when absent)."""
    try:
        data = json.loads(_read_text(path))
    except (OSError, ValueError):
        return {}
    if isinstance(data, dict) and data.get("autotune_cache") == 1:
        dec = data.get("decisions")
        if isinstance(dec, dict):
            return dec
    return {}


def _gauge(gauges: dict, name: str) -> Optional[float]:
    v = gauges.get(name)
    if isinstance(v, dict):
        v = v.get("value")
    try:
        return float(v)
    except (TypeError, ValueError):
        return None


def roofline_rows(kernels: dict, gauges: dict) -> List[dict]:
    rows = []
    names = set(kernels)
    for g in gauges:
        if g.startswith("perf.kernels.") and g.endswith(".measured_ms"):
            names.add(g[len("perf.kernels."):-len(".measured_ms")])
    for name in sorted(names):
        base = f"perf.kernels.{name}"
        measured = _gauge(gauges, f"{base}.measured_ms")
        if measured is None:
            continue
        rows.append({
            "kernel": name,
            "measured_ms": measured,
            "roofline_ms": _gauge(gauges, f"{base}.roofline_ms"),
            "vs_roofline": _gauge(gauges, f"{base}.vs_roofline"),
            "below_roofline": bool(
                _gauge(gauges, f"{base}.below_roofline") or 0.0),
        })
    return rows


def _fmt_choice(c) -> str:
    if not c:
        return "-"
    return "x".join(str(v) for v in c)


def _best_measured(rec: dict) -> str:
    ms = rec.get("measured_ms") or {}
    vals = [(v, k) for k, v in ms.items()
            if isinstance(v, (int, float)) and v == v]   # drop NaN
    if not vals:
        return "-"
    v, k = min(vals)
    return f"{v:.3f} ms @ {k}"


def render(kernels: dict, decisions: Dict[str, dict],
           gauges: dict) -> str:
    out = []
    w = out.append
    w("KERNEL LIBRARY")
    w("=" * 78)
    if kernels:
        w(f"{'kernel':<20} {'impl':<10} {'on':<3} {'pin':<4} "
          f"{'default':<10} kill switch")
        w("-" * 78)
        for name in sorted(kernels):
            row = kernels[name]
            kill = row.get("kill_env", "-")
            aliases = row.get("kill_aliases") or []
            if aliases:
                kill += " (legacy: " + ", ".join(aliases) + ")"
            w(f"{name:<20} {row.get('impl', '?'):<10} "
              f"{'y' if row.get('enabled', True) else 'N':<3} "
              f"{'yes' if row.get('has_parity_pin') else 'NO':<4} "
              f"{_fmt_choice(row.get('default_choice')):<10} {kill}")
    else:
        w("  (no kernels block in the dump — pass a perf dump written "
          "by telemetry.write_perf_dump)")
    w("")
    w("AUTOTUNE DECISIONS")
    w("=" * 78)
    if decisions:
        for key in sorted(decisions):
            rec = decisions[key]
            parts = key.split("|")
            kern, sig, backend = (parts + ["?", "?", "?"])[:3]
            chose = _fmt_choice(rec.get("choice"))
            dflt = _fmt_choice(rec.get("default"))
            tag = ("CHANGED default " + dflt
                   if rec.get("changed_default") else f"default {dflt}")
            w(f"  {kern} [{sig} @ {backend}] -> {chose}  ({tag}, "
              f"replays={rec.get('replays', 0)})")
            why = rec.get("why")
            if why:
                w(f"      why: {why}")
            best = _best_measured(rec)
            if best != "-":
                w(f"      best measured: {best}")
    else:
        w("  (no cached decisions)")
    w("")
    w("MEASURED VS ROOFLINE")
    w("=" * 78)
    rows = roofline_rows(kernels, gauges)
    if rows:
        for r in rows:
            flag = "  << BELOW ROOFLINE (>2x over bound)" \
                if r["below_roofline"] else ""
            roof = (f"{r['roofline_ms']:.4f}"
                    if r["roofline_ms"] is not None else "?")
            ratio = (f"{r['vs_roofline']:.2f}x"
                     if r["vs_roofline"] is not None else "?")
            w(f"  {r['kernel']:<20} measured {r['measured_ms']:.4f} ms  "
              f"roofline {roof} ms  ({ratio}){flag}")
    else:
        w("  (no perf.kernels.* timing gauges in the dump)")
    return "\n".join(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("dump", nargs="?", default=None,
                    help="perf dump / flight-recorder JSON (optional)")
    ap.add_argument("--autotune-cache", default=None,
                    help="autotune cache JSON (default: "
                         "$DL4J_TPU_AUTOTUNE_CACHE or "
                         "~/.cache/deeplearning4j_tpu/autotune.json)")
    ap.add_argument("--json", action="store_true",
                    help="emit the merged report as JSON")
    args = ap.parse_args(argv)

    kernels, gauges = {}, {}
    if args.dump:
        try:
            d = load_dump(args.dump)
        except (OSError, ValueError) as e:
            print(f"error: {e}", file=sys.stderr)
            return 2
        kernels, gauges = d["kernels"], d["gauges"]
    decisions = load_autotune(args.autotune_cache or default_cache_path())

    if args.json:
        print(json.dumps({"kernels": kernels, "autotune": decisions,
                          "roofline": roofline_rows(kernels, gauges)},
                         indent=1, sort_keys=True))
    else:
        print(render(kernels, decisions, gauges))
    return 0


if __name__ == "__main__":
    sys.exit(main())
