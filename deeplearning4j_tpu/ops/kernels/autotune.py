"""Autotuning harness: measured block-size decisions, cached per
(kernel, shape-sig, backend).

The decision loop is deliberately dumb — measure each candidate with the
caller-supplied thunk, keep the argmin — because the interesting part is
the *discipline* around it:

  - decisions persist in a JSON cache (``DL4J_TPU_AUTOTUNE_CACHE``,
    default ``~/.cache/deeplearning4j_tpu/autotune.json``) keyed
    ``kernel|shape_sig|backend`` so the next process REPLAYS the choice
    without re-measuring (each replay is counted — the acceptance
    criterion that caching actually short-circuits measurement is
    testable from the record itself). The file lives OUTSIDE the
    checkout and changes kernel block sizes, so ``loaded_from_file``
    says whether one was read (``chip_smoke.py`` prints it), and the
    trace-time replay (``cached_decision``) only reads: tracing a kernel
    never writes outside the checkout;
  - when no trustworthy measurement is possible (no measure thunk — e.g.
    a CPU run, where interpret-mode timings say nothing about the TPU) the
    harness records the default WITH the reason in ``why``, so "defaults
    stand" is an auditable decision, not a silent skip;
  - every record carries the measured times, whether the winner differs
    from the hand-tuned default (``changed_default``), and the reason —
    ``tools/kernels_report.py`` renders them.

Consumers: ``pallas_attention._blocks`` resolves env override → cached
decision → preference defaults; ``tools/autotune_attention.py`` remains
the sweep driver that can populate the cache on a real rig.
"""
from __future__ import annotations

import json
import os
import tempfile
import threading
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

_LOCK = threading.Lock()
_CACHE: Optional["AutotuneCache"] = None


def cache_path() -> str:
    p = os.environ.get("DL4J_TPU_AUTOTUNE_CACHE")
    if p:
        return p
    return os.path.join(os.path.expanduser("~"), ".cache",
                        "deeplearning4j_tpu", "autotune.json")


class AutotuneCache:
    """JSON-file-backed decision store. Atomic writes (tmp + rename, the
    repo's checkpoint discipline); a corrupt/absent file is an empty
    cache, never an error."""

    def __init__(self, path: Optional[str] = None):
        self.path = path or cache_path()
        self._decisions: Dict[str, Dict[str, Any]] = {}
        self.loaded_from_file = False     # a decision file was read
        self._load()

    def _load(self) -> None:
        try:
            with open(self.path) as f:
                data = json.load(f)
            if isinstance(data, dict) and data.get("autotune_cache") == 1:
                dec = data.get("decisions")
                if isinstance(dec, dict):
                    self._decisions = dec
                    self.loaded_from_file = True
        except (OSError, ValueError):
            self._decisions = {}

    def _save(self) -> None:
        try:
            d = os.path.dirname(self.path)
            if d:
                os.makedirs(d, exist_ok=True)
            fd, tmp = tempfile.mkstemp(dir=d or ".", suffix=".tmp")
            with os.fdopen(fd, "w") as f:
                json.dump({"autotune_cache": 1,
                           "decisions": self._decisions}, f, indent=1,
                          sort_keys=True)
            os.replace(tmp, self.path)
        except OSError:
            pass    # a read-only FS degrades to per-process decisions

    @staticmethod
    def key(kernel: str, shape_sig: str, backend: str) -> str:
        return f"{kernel}|{shape_sig}|{backend}"

    def lookup(self, kernel: str, shape_sig: str,
               backend: str) -> Optional[Dict[str, Any]]:
        return self._decisions.get(self.key(kernel, shape_sig, backend))

    def decisions_for(self, kernel: str) -> Dict[str, Dict[str, Any]]:
        pre = kernel + "|"
        return {k: v for k, v in self._decisions.items()
                if k.startswith(pre)}

    def store(self, kernel: str, shape_sig: str, backend: str,
              record: Dict[str, Any]) -> None:
        self._decisions[self.key(kernel, shape_sig, backend)] = record
        self._save()


def get_cache() -> AutotuneCache:
    global _CACHE
    with _LOCK:
        if _CACHE is None or _CACHE.path != cache_path():
            _CACHE = AutotuneCache()
        return _CACHE


def _backend() -> str:
    try:
        import jax
        return jax.default_backend()
    except Exception:
        return "unknown"


def cached_decision(kernel: str, shape_sig: str,
                    backend: Optional[str] = None) -> Optional[Sequence]:
    """Replay path (called at kernel TRACE time): the cached choice for
    this rig, or None. Counts the replay on the in-memory record (proof
    no re-measurement happened) and writes nothing."""
    rec = get_cache().lookup(kernel, shape_sig, backend or _backend())
    if rec is None or "choice" not in rec:
        return None
    rec["replays"] = int(rec.get("replays", 0)) + 1
    return rec["choice"]


def decisions_for(kernel: str) -> Dict[str, Dict[str, Any]]:
    return get_cache().decisions_for(kernel)


def decide(kernel: str, shape_sig: str,
           candidates: Sequence[Tuple],
           measure: Optional[Callable[[Tuple], float]],
           default: Tuple, *, force: bool = False) -> Dict[str, Any]:
    """Choose a block config for (kernel, shape_sig) on this backend.

    ``candidates`` — tuples to try; ``measure(candidate) -> seconds`` (or
    None when measurement is meaningless here, e.g. off-TPU); ``default``
    — the hand-tuned choice measurements must beat. Returns the decision
    record (and persists it). A cached record short-circuits everything
    unless ``force``.
    """
    backend = _backend()
    cache = get_cache()
    rec = None if force else cache.lookup(kernel, shape_sig, backend)
    if rec is not None and "choice" in rec:
        rec["replays"] = int(rec.get("replays", 0)) + 1
        cache._save()
        return rec

    default = tuple(default)
    if measure is None:
        rec = {"choice": list(default), "default": list(default),
               "changed_default": False, "replays": 0, "measured_ms": {},
               "why": (f"defaults stand: no measurement available on "
                       f"backend {backend!r} (interpret-mode timings do "
                       f"not predict TPU block behavior)")}
        cache.store(kernel, shape_sig, backend, rec)
        return rec

    timings: Dict[str, float] = {}
    best, best_t = None, float("inf")
    for cand in candidates:
        cand = tuple(cand)
        try:
            t = float(measure(cand))
        except Exception:               # a failing-to-compile candidate
            timings[str(list(cand))] = float("nan")
            continue
        timings[str(list(cand))] = t * 1e3
        if t < best_t:
            best, best_t = cand, t
    if best is None:
        best = default
        why = "defaults stand: every candidate failed to measure"
        changed = False
    else:
        changed = best != default
        why = (f"measured argmin over {len(candidates)} candidates"
               + ("" if changed else " — default already optimal"))
    rec = {"choice": list(best), "default": list(default),
           "changed_default": changed, "replays": 0,
           "measured_ms": timings, "why": why}
    cache.store(kernel, shape_sig, backend, rec)
    return rec
