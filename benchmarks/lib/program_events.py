"""Arithmetic shared by the readers of what the program says about itself
(ISSUE 24): the split of its blocking spans (``generation.dispatch``,
``generation.readback``), the loop's host phases (complete events of
category ``phase``), the per-request ``generation.admit`` instant events
and the token counts on the ``generation.decode_step`` and
``generation.prefill`` spans. A program that records none of these (the
commit before) leaves every function here with nothing to read: None."""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

from benchmarks.lib import readers
from benchmarks.lib.stats import percentile

HOST_PHASES = ("generation.admit_batch", "generation.emit")


def instants(obs: Dict, name: str) -> List[Dict]:
    """The ``args`` of the instant events called ``name`` stamped inside
    the window."""
    if "events" not in obs:
        return []
    t0, t1 = obs["window_perf"]
    off = obs["epoch_ns"]
    return [e.get("args", {}) for e in obs["events"]
            if e.get("ph") == "i" and e.get("name") == name
            and t0 <= (e["ts"] * 1000 - off) / 1e9 <= t1]


def median_span_ms(obs: Dict, name: str, least: int = 10,
                   **attrs) -> Optional[float]:
    """Median duration of the window's spans called ``name`` whose
    attributes match ``attrs``."""
    found = [s["dur"] * 1e3 for s in readers.spans(obs, name)
             if all(s["args"].get(k) == v for k, v in attrs.items())]
    return percentile(found, 50) if len(found) >= least else None


def phase_share_pct(obs: Dict, names: Sequence[str] = HOST_PHASES
                    ) -> Optional[float]:
    """Share of the window inside the complete events called ``names``:
    the loop's host phases, during which no program call is in flight."""
    seconds = [readers.span_seconds(obs, n) for n in names]
    if all(s is None for s in seconds):
        return None
    return 100.0 * sum(s or 0.0 for s in seconds) / readers.window_seconds(obs)


def median_attr(args: Sequence[Dict], key: str,
                least: int = 10) -> Optional[float]:
    values = [a[key] for a in args if key in a]
    return percentile(values, 50) if len(values) >= least else None


def one_minus_ratio_pct(obs: Dict, name: str, part: str,
                        whole: str) -> Optional[float]:
    """100 x (1 - sum of ``part`` / sum of ``whole``) over the window's
    spans called ``name`` that carry both attributes."""
    have = [s["args"] for s in readers.spans(obs, name)
            if part in s["args"] and whole in s["args"]]
    total = sum(a[whole] for a in have)
    if not total:
        return None
    return 100.0 * (1.0 - sum(a[part] for a in have) / total)


def mean_ratio(obs: Dict, name: str, over: str, under: str,
               least: int = 10) -> Optional[float]:
    """Mean over the window's spans called ``name`` of ``over`` /
    ``under``, where both attributes are there and ``under`` is not 0."""
    ratios = [s["args"][over] / s["args"][under]
              for s in readers.spans(obs, name)
              if s["args"].get(under) and over in s["args"]]
    return sum(ratios) / len(ratios) if len(ratios) >= least else None
