"""The comparisons that decide ``correct``. Every number compared is
printed beside its limit; a run is correct when every check holds."""
from __future__ import annotations

import math
from typing import Dict, List


class Checks:
    def __init__(self) -> None:
        self.rows: List[Dict] = []

    def at_most(self, name: str, value: float, limit: float) -> None:
        ok = (value is not None and not math.isnan(float(value))
              and float(value) <= limit)
        self.rows.append({"check": name, "value": value, "limit": limit,
                          "ok": bool(ok)})
        print(f"[check] {name}: {value!r} <= {limit!r} -> "
              f"{'ok' if ok else 'FAIL'}", flush=True)

    def exactly(self, name: str, value, want) -> None:
        ok = value == want
        self.rows.append({"check": name, "value": value, "limit": want,
                          "ok": bool(ok)})
        print(f"[check] {name}: {value!r} == {want!r} -> "
              f"{'ok' if ok else 'FAIL'}", flush=True)

    @property
    def correct(self) -> bool:
        return bool(self.rows) and all(r["ok"] for r in self.rows)


def worst_leaf_gap(program: Dict[str, float], reference: Dict[str, float]) -> float:
    """Worst leaf by |program norm - reference norm| over the larger of the
    reference's norm of that leaf and of the median leaf."""
    if set(program) != set(reference):
        raise ValueError("leaf names differ: "
                         f"{sorted(set(program) ^ set(reference))[:6]}")
    ref_sorted = sorted(reference.values())
    median = ref_sorted[len(ref_sorted) // 2]
    gaps = {k: abs(program[k] - reference[k]) / max(reference[k], median)
            for k in reference}
    worst = sorted(gaps, key=gaps.get, reverse=True)[:3]
    print("[check] worst leaves: " + ", ".join(
        f"{k} {gaps[k]:.4g} (program {program[k]:.4g}, reference "
        f"{reference[k]:.4g})" for k in worst), flush=True)
    return gaps[worst[0]]
