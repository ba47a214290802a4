"""Performance observability: the live cost-model accounting layer.

ONE cost model (FLOPs, bytes, implied MFU, roofline class) for every
program the system compiles, turned into *live* gauges on the
fit/serving/generation paths:

- **Shared cost model** — :func:`normalize_cost_analysis` (the one place
  that knows ``compiled.cost_analysis()`` returns a list-of-dict on some
  backends and a dict on others), :func:`implied_mfu`,
  :func:`roofline_dt` and :func:`classify_roofline` (compute- vs
  memory-bound from arithmetic intensity against the ridge point). Peak
  numbers come from ONE table keyed by the running device's
  ``device_kind`` (:data:`DEVICE_PEAKS`); ``BENCH_PEAK_TFLOPS`` /
  ``BENCH_HBM_GBPS`` are explicit overrides. A device the table does not
  know, with no override, has NO peak: MFU and roofline gauges are then
  absent, never computed against another chip's numbers.

- **:class:`ProgramCostIndex`** — captures the XLA cost analysis of
  every program the system compiles, keyed by the program's span path:
  train-step programs (Solver per-step and scan-window, via a one-time
  ``jit(...).lower()`` — an abstract trace, NO extra backend compile,
  nothing touches a device buffer — deferred until the program has
  dispatched ``DL4J_TPU_PERF_CAPTURE_AFTER`` steps, default 256 —
  seconds into any real training run, never reached by a short
  exploratory fit, whose retrace would cost more than it informs), serving bucket
  programs and generation prefill/decode/verify programs (registered
  from their AOT ``Compiled`` objects at warm-up). Each entry pairs the
  per-step FLOP/byte counts with a *timing metric* (an existing
  registry histogram observed by the hot loop), and :meth:`fold` — run
  OFF the hot loop at window/epoch boundaries or scrape time — turns
  the delta of that histogram into ``perf.<path>.mfu`` /
  ``.achieved_tflops`` / ``.step_ms`` / ``.roofline_compute_bound``
  gauges. A ``lax.scan``/``fori_loop`` body is counted ONCE by XLA's
  analysis (verified on this stack), so a K-step window program's cost
  IS the per-step cost; only the timing is divided by K.

- **:class:`StepAccounting`** — per-step time decomposition
  (``perf.step.compute_ms`` / ``input_wait_ms`` / ``host_ms``
  histograms): the fit loop appends plain floats and the buffers flush
  at window boundaries, same zero-host-sync discipline as TrainingWatch.

Kill switch: ``DL4J_TPU_PERF_ACCOUNTING=0`` disables capture and fold
(a disabled registry disables them too).
"""
from __future__ import annotations

import json
import logging
import os
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

from .registry import MetricsRegistry, get_registry

log = logging.getLogger("deeplearning4j_tpu")

__all__ = ["normalize_cost_analysis", "cost_analysis_of", "implied_mfu",
           "roofline_dt", "classify_roofline", "peak_tflops", "hbm_gbps",
           "max_plausible_mfu", "accounting_enabled",
           "ProgramCost", "ProgramCostIndex", "get_cost_index",
           "set_cost_index", "StepAccounting",
           "decomposition_summary", "write_perf_dump", "perf_snapshot"]

_ENV_KILL = "DL4J_TPU_PERF_ACCOUNTING"


def accounting_enabled() -> bool:
    """Cost capture + fold master switch (default on; the registry's
    ``enabled`` flag gates it too)."""
    return os.environ.get(_ENV_KILL, "1").lower() not in ("0", "false",
                                                          "off")


# ------------------------------------------------------------ chip model
# Published per-chip peaks keyed by ``jax.devices()[0].device_kind``:
# (bf16 MXU TFLOP/s, HBM GB/s). Source: Google Cloud TPU documentation,
# "TPU v5e" (197 TFLOP/s bf16, 819 GB/s HBM per chip). f32 matmuls/convs
# at JAX's default precision also run as bf16 MXU passes, so the bf16
# peak is the denominator for both dtypes.
DEVICE_PEAKS: Dict[str, Tuple[float, float]] = {
    "TPU v5 lite": (197.0, 819.0),
}


def _resolve_peak(override: Optional[float], env: str,
                  column: int) -> Optional[float]:
    """Explicit argument, else the env override, else the table row of
    the device this process runs on; None when that device is unknown."""
    if override is not None:
        return float(override)
    v = os.environ.get(env)
    if v:
        return float(v)
    import jax
    row = DEVICE_PEAKS.get(jax.devices()[0].device_kind)
    return row[column] if row else None


def peak_tflops(override: Optional[float] = None) -> Optional[float]:
    return _resolve_peak(override, "BENCH_PEAK_TFLOPS", 0)


def hbm_gbps(override: Optional[float] = None) -> Optional[float]:
    return _resolve_peak(override, "BENCH_HBM_GBPS", 1)


def max_plausible_mfu(override: Optional[float] = None) -> float:
    if override is not None:
        return float(override)
    return float(os.environ.get("BENCH_MAX_PLAUSIBLE_MFU", "0.6"))


def normalize_cost_analysis(ca) -> dict:
    """Normalize a raw ``cost_analysis()`` result across backends
    (list-of-dict on some, dict on others, occasionally neither) — THE
    one place that knows the quirk."""
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return ca if hasattr(ca, "get") else {}


def cost_analysis_of(program) -> dict:
    """Normalized cost analysis of a jax ``Compiled`` OR ``Lowered``
    stage ({} when the backend can't provide one). ``Lowered`` works on
    this stack WITHOUT a backend compile — its flop count matches the
    compiled analysis (bytes run higher pre-optimization)."""
    try:
        return normalize_cost_analysis(program.cost_analysis())
    except Exception:
        return {}


def implied_mfu(flops_per_step, dt_s, *, peak: Optional[float] = None
                ) -> Optional[float]:
    """MFU implied by a measured per-step time (None if the flops or the
    device's peak are unknown)."""
    pk = peak_tflops(peak)
    if not flops_per_step or not dt_s or dt_s <= 0 or not pk:
        return None
    return flops_per_step / dt_s / 1e12 / pk


def roofline_dt(flops_per_step, *, peak: Optional[float] = None,
                mfu_ceiling: Optional[float] = None) -> float:
    """Fastest physically plausible per-step time at the MFU ceiling."""
    pk = peak_tflops(peak)
    if not pk:
        raise ValueError("no peak FLOP/s known for this device kind "
                         "(not in DEVICE_PEAKS, BENCH_PEAK_TFLOPS unset)")
    return flops_per_step / (pk * 1e12 * max_plausible_mfu(mfu_ceiling))


def classify_roofline(flops, bytes_accessed, *,
                      peak: Optional[float] = None,
                      gbps: Optional[float] = None) -> dict:
    """Compute- vs memory-bound classification from arithmetic intensity
    (flops/byte) against the ridge point (peak_flops / bandwidth).
    ``attainable_tflops`` is the roofline ceiling for this intensity —
    the honest denominator for "how close to the roof are we"."""
    pk, bw = peak_tflops(peak), hbm_gbps(gbps)
    intensity = (float(flops) / float(bytes_accessed)
                 if flops and bytes_accessed else None)
    if not pk or not bw:        # unknown device: nothing to classify against
        return {"bound": "unknown",
                "intensity": round(intensity, 3) if intensity else None,
                "ridge": None, "attainable_tflops": None}
    ridge = pk * 1e12 / (bw * 1e9)
    if intensity is None:
        return {"bound": "unknown", "intensity": None, "ridge": round(ridge, 2),
                "attainable_tflops": None}
    attainable = min(pk, intensity * bw / 1e3)
    return {"bound": "compute" if intensity >= ridge else "memory",
            "intensity": round(intensity, 3), "ridge": round(ridge, 2),
            "attainable_tflops": round(attainable, 3)}


# ----------------------------------------------------------- cost index
@dataclass
class ProgramCost:
    """One program's captured cost + fold state. ``flops_per_step`` /
    ``bytes_per_step`` are PER STEP (a scan-window body is counted once
    by XLA's analysis, so the program cost is already per-step);
    ``steps_per_call`` divides the TIMING metric only."""
    path: str
    flops_per_step: Optional[float] = None
    bytes_per_step: Optional[float] = None
    peak_memory_bytes: Optional[float] = None
    steps_per_call: int = 1
    items_per_step: Optional[float] = None
    model_axis_size: int = 1         # tensor-parallel ways (ISSUE 20)
    source: str = "unknown"          # compiled | lowered | analytic
    timing_metric: Optional[str] = None
    # fold state: last (count, sum) seen on the timing histogram
    _last_count: int = field(default=0, repr=False)
    _last_sum: float = field(default=0.0, repr=False)
    last_row: Optional[dict] = field(default=None, repr=False)


def _memory_analysis_bytes(program) -> Optional[float]:
    """Best-effort peak working-set estimate from ``memory_analysis()``
    (AOT ``Compiled`` only; None elsewhere)."""
    try:
        ma = program.memory_analysis()
    except Exception:
        return None
    if ma is None:
        return None
    total = 0.0
    got = False
    for attr in ("temp_size_in_bytes", "argument_size_in_bytes",
                 "output_size_in_bytes"):
        v = getattr(ma, attr, None)
        if v is not None:
            total += float(v)
            got = True
    return total if got else None


class ProgramCostIndex:
    """Process-wide registry of per-program cost entries keyed by span
    path. Thread-safe; capture is once per (path, signature); fold runs
    off the hot loop.

    Keying caveat: the span path is the identity, LAST writer wins — two
    different models training in one process share the ``fit/...`` paths,
    so the entry (and the gauges folded from it) always describes the
    most recently captured program. Between a new program's first
    dispatch and its own capture-threshold crossing, its timings are
    paired with the previous program's cost — transient, and bounded by
    the capture threshold."""

    def __init__(self):
        self._lock = threading.Lock()
        self._entries: Dict[str, ProgramCost] = {}
        self._seen: set = set()
        self._train_path: Optional[str] = None

    # ------------------------------------------------------------ register
    def register(self, path: str, *, program=None,
                 flops_per_step: Optional[float] = None,
                 bytes_per_step: Optional[float] = None,
                 peak_memory_bytes: Optional[float] = None,
                 steps_per_call: int = 1,
                 items_per_step: Optional[float] = None,
                 model_axis_size: int = 1,
                 timing_metric: Optional[str] = None,
                 source: Optional[str] = None) -> Optional[ProgramCost]:
        """Register (or refresh — last write wins per path) one program's
        cost. ``program`` may be a jax ``Compiled`` or ``Lowered``;
        explicit ``flops_per_step``/``bytes_per_step`` override it
        (mandatory for Pallas programs — XLA cannot see inside custom
        calls). ``model_axis_size`` divides the captured flops/bytes: a
        tensor-parallel program's cost analysis counts the WHOLE model's
        work, but each chip executes 1/m of it, so the per-chip MFU/
        roofline gauges (peak numbers are per chip) must fold the
        per-chip share. Returns None when no cost could be extracted."""
        if program is not None:
            ca = cost_analysis_of(program)
            if flops_per_step is None and ca.get("flops"):
                flops_per_step = float(ca["flops"])
            if bytes_per_step is None and ca.get("bytes accessed"):
                bytes_per_step = float(ca["bytes accessed"])
            if peak_memory_bytes is None:
                peak_memory_bytes = _memory_analysis_bytes(program)
            if source is None:
                source = ("compiled"
                          if type(program).__name__ == "Compiled"
                          else "lowered")
        if flops_per_step is None and bytes_per_step is None:
            reg = get_registry()
            if reg.enabled:
                reg.counter("perf.cost_capture_failures").inc()
            return None
        m = max(1, int(model_axis_size))
        if m > 1:
            if flops_per_step is not None:
                flops_per_step /= m
            if bytes_per_step is not None:
                bytes_per_step /= m
        entry = ProgramCost(
            path=path, flops_per_step=flops_per_step,
            bytes_per_step=bytes_per_step,
            peak_memory_bytes=peak_memory_bytes,
            steps_per_call=max(1, int(steps_per_call)),
            items_per_step=items_per_step, model_axis_size=m,
            source=source or "analytic", timing_metric=timing_metric)
        with self._lock:
            prev = self._entries.get(path)
            if prev is not None:         # keep fold continuity on refresh
                entry._last_count = prev._last_count
                entry._last_sum = prev._last_sum
            self._entries[path] = entry
            if path.startswith("fit"):
                self._train_path = path
        return entry

    def maybe_capture(self, path: str, sig, jitted, args, kwargs=None, *,
                      steps_per_call: int = 1,
                      model_axis_size: int = 1,
                      timing_metric: Optional[str] = None
                      ) -> Optional[ProgramCost]:
        """One-time cost capture for a ``jax.jit`` program: lower
        (abstract trace — no backend compile, no execution, no device
        reads) and register the cost analysis. De-duplicated on
        ``(path, sig)`` — callers pass a cheap shape signature; a failed
        capture is remembered too (it will not retry per-iteration)."""
        key = (path, sig)
        with self._lock:
            if key in self._seen:
                return None
            self._seen.add(key)
        try:
            lowered = jitted.lower(*args, **(kwargs or {}))
        except Exception as e:        # capture must never break the loop
            log.debug("perf: cost capture lower() failed for %s: %s",
                      path, e)
            return None
        return self.register(path, program=lowered, source="lowered",
                             steps_per_call=steps_per_call,
                             model_axis_size=model_axis_size,
                             timing_metric=timing_metric)

    # ------------------------------------------------------------- queries
    def get(self, path: str) -> Optional[ProgramCost]:
        with self._lock:
            return self._entries.get(path)

    def paths(self) -> List[str]:
        with self._lock:
            return sorted(self._entries)

    def train_cost(self) -> Optional[ProgramCost]:
        """The most recently registered train-step program (path under
        ``fit``) — what PerformanceListener's mfu history keys read."""
        with self._lock:
            return (self._entries.get(self._train_path)
                    if self._train_path else None)

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._seen.clear()
            self._train_path = None

    # ---------------------------------------------------------------- fold
    def fold(self, registry: Optional[MetricsRegistry] = None
             ) -> List[dict]:
        """Resolve every entry against its timing histogram's NEW
        observations since the last fold and publish the
        ``perf.<path>.*`` gauges. Pure host arithmetic over metrics the
        hot loop already recorded — call from window/epoch boundaries,
        scrape handlers, or dump time, never from the dispatch loop.
        Returns the full cost table (entries without fresh timing keep
        their last row; entries without a timing metric report cost
        only)."""
        reg = registry or get_registry()
        rows: List[dict] = []
        if not accounting_enabled():
            return rows
        ceiling = max_plausible_mfu()
        peak = peak_tflops()        # None: unknown device, no MFU gauges
        # the whole fold runs under the index lock: concurrent folds
        # (epoch boundary vs /metrics scrape vs flight dump) must not
        # consume the same timing delta twice or tear _last_count/_sum.
        # Gauge/histogram accesses take their own (leaf) locks; nothing
        # acquires this lock while holding one of those.
        with self._lock:
            entries = list(self._entries.values())
            for e in entries:
                dt_step_ms = None
                if e.timing_metric:
                    h = reg.histogram_if_exists(e.timing_metric)
                    if h is not None:
                        count, total = h.count_and_sum()
                        if count < e._last_count:     # registry was reset:
                            e._last_count, e._last_sum = 0, 0.0   # resync
                        dc, ds = count - e._last_count, total - e._last_sum
                        if dc > 0 and ds >= 0:
                            e._last_count, e._last_sum = count, total
                            dt_step_ms = ds / dc / e.steps_per_call
                if dt_step_ms is None and e.last_row is not None:
                    rows.append(e.last_row)
                    continue
                rf = classify_roofline(e.flops_per_step, e.bytes_per_step)
                row = {"path": e.path, "flops_per_step": e.flops_per_step,
                       "bytes_per_step": e.bytes_per_step,
                       "peak_memory_bytes": e.peak_memory_bytes,
                       "steps_per_call": e.steps_per_call,
                       "items_per_step": e.items_per_step,
                       "model_axis_size": e.model_axis_size,
                       "source": e.source, "timing_metric": e.timing_metric,
                       "roofline": rf["bound"], "intensity": rf["intensity"],
                       "attainable_tflops": rf["attainable_tflops"],
                       "step_ms": None, "achieved_tflops": None, "mfu": None,
                       "implausible": False}
                if dt_step_ms is not None and dt_step_ms > 0:
                    row["step_ms"] = dt_step_ms
                    if e.flops_per_step:
                        achieved = e.flops_per_step / (dt_step_ms / 1e3) / 1e12
                        # full precision: a toy CPU program's MFU is ~1e-8 —
                        # rounding here would zero it (renderers format)
                        row["achieved_tflops"] = achieved
                        if peak:
                            row["mfu"] = achieved / peak
                            # an MFU past the plausibility ceiling means
                            # the timing under-measured (async dispatch
                            # slack), not a fast chip — published, flagged
                            row["implausible"] = row["mfu"] > ceiling
                    if reg.enabled:
                        p = f"perf.{e.path}"
                        reg.gauge(f"{p}.step_ms").set(round(dt_step_ms, 6))
                        if row["achieved_tflops"] is not None:
                            reg.gauge(f"{p}.achieved_tflops").set(
                                row["achieved_tflops"])
                        if row["mfu"] is not None:
                            reg.gauge(f"{p}.mfu").set(row["mfu"])
                            reg.gauge(f"{p}.implausible").set(
                                1.0 if row["implausible"] else 0.0)
                        if rf["bound"] != "unknown":
                            reg.gauge(f"{p}.roofline_compute_bound").set(
                                1.0 if rf["bound"] == "compute" else 0.0)
                e.last_row = row
                rows.append(row)
        return rows


_index = ProgramCostIndex()
_index_lock = threading.Lock()


def get_cost_index() -> ProgramCostIndex:
    """THE process-wide cost index every capture site registers into."""
    return _index


def set_cost_index(index: ProgramCostIndex) -> ProgramCostIndex:
    global _index
    with _index_lock:
        prev, _index = _index, index
    return prev


# ----------------------------------------------------- step decomposition
class StepAccounting:
    """Per-step time decomposition with deferred flush.

    The fit loop calls :meth:`on_step` with host-measured millisecond
    walls (values it already computes — nothing here reads a device
    buffer); the samples buffer in plain lists and flush into
    ``<prefix>.compute_ms`` / ``input_wait_ms`` / ``host_ms`` histograms
    every ``flush_every`` steps and at epoch end — "why is steps/sec
    down" becomes answerable from ``/metrics``: a fat ``input_wait_ms``
    is the feed, a fat ``host_ms`` is listener/dispatch overhead, a fat
    ``compute_ms`` is the program itself (pair with ``perf.<path>.mfu``
    to see whether the program got slower or bigger)."""

    def __init__(self, registry: Optional[MetricsRegistry] = None,
                 prefix: str = "perf.step", flush_every: int = 32):
        self._registry = registry
        self.prefix = prefix
        self.flush_every = max(1, int(flush_every))
        self._buf: List[Tuple[float, float, float, int]] = []
        self._steps = 0

    @property
    def registry(self) -> MetricsRegistry:
        return self._registry if self._registry is not None \
            else get_registry()

    def on_step(self, *, input_wait_ms: float, compute_ms: float,
                host_ms: float = 0.0, steps: int = 1) -> None:
        """Record one dispatch's wall decomposition (a K-window passes
        its TOTALS and ``steps=K``; flush divides)."""
        self._buf.append((input_wait_ms, compute_ms, host_ms, steps))
        self._steps += steps
        if self._steps >= self.flush_every:
            self.flush()

    def flush(self) -> None:
        if not self._buf:
            return
        buf, self._buf = self._buf, []
        self._steps = 0
        reg = self.registry
        if not reg.enabled:
            return
        h_wait = reg.histogram(f"{self.prefix}.input_wait_ms")
        h_comp = reg.histogram(f"{self.prefix}.compute_ms")
        h_host = reg.histogram(f"{self.prefix}.host_ms")
        for wait, comp, host, k in buf:
            k = max(1, k)
            h_wait.observe(wait / k)
            h_comp.observe(comp / k)
            h_host.observe(max(host, 0.0) / k)


def decomposition_summary(registry: Optional[MetricsRegistry] = None
                          ) -> dict:
    """The step-time decomposition as one JSON-ready dict (perf.step.*
    histograms + the collective time the parallel layer publishes)."""
    reg = registry or get_registry()
    out: Dict[str, Any] = {}
    for part in ("compute_ms", "input_wait_ms", "host_ms"):
        h = reg.histogram_if_exists(f"perf.step.{part}")
        if h is not None and h.count:
            st = h.stats()
            out[part] = {"p50": round(st["p50"], 4),
                         "p95": round(st["p95"], 4),
                         "mean": round(st["mean"], 4),
                         "count": st["count"]}
    g = reg.gauge_if_exists("parallel.collective_ms")
    if g is not None:
        out["collective_ms"] = g.value
    means = {k: v["mean"] for k, v in out.items() if isinstance(v, dict)}
    total = sum(means.values())
    if total > 0:
        out["shares"] = {k: round(v / total, 4) for k, v in means.items()}
    return out


# -------------------------------------------------------------- snapshots
def perf_snapshot(registry: Optional[MetricsRegistry] = None,
                  index: Optional[ProgramCostIndex] = None,
                  top_k: int = 8, fresh_memory: bool = False) -> dict:
    """The ``"perf"`` block for ``/metrics``, the dashboard card and the
    flight recorder: cost table (freshly folded), step decomposition and
    the memory top-K. Never raises — an observability read must not add
    a second failure to whatever triggered it."""
    out: dict = {}
    try:                     # a malformed BENCH_PEAK_TFLOPS env value
        out["peak_tflops"] = peak_tflops()    # must not cost a flight
        out["hbm_gbps"] = hbm_gbps()          # dump its black box
    except (TypeError, ValueError) as e:
        log.debug("perf snapshot: bad chip-model env: %s", e)
    try:
        reg = registry or get_registry()
        idx = index or get_cost_index()
        out["programs"] = idx.fold(reg)
        out["step_decomposition"] = decomposition_summary(reg)
    except Exception as e:          # pragma: no cover - defensive
        log.debug("perf snapshot failed: %s", e)
    try:
        # kernel library (ISSUE 17): registered kernels, active impl,
        # autotune decisions — lazy import so telemetry never forces the
        # ops package (and a broken kernel module never costs a dump)
        from ..ops.kernels import kernels_snapshot
        out["kernels"] = kernels_snapshot()
    except Exception as e:          # pragma: no cover - defensive
        log.debug("kernels snapshot failed: %s", e)
    try:
        # cached walk (~2 s max staleness) by default: /metrics scrapes
        # and repeat-fire dump triggers must not pay a fresh
        # O(live-arrays) walk each. ``fresh_memory=True`` forces the
        # walk (deliberate one-shot artifacts: write_perf_dump);
        # POST /debug/memprof calls memprof.snapshot directly.
        from . import memprof
        out["memory"] = (memprof.snapshot(top_k=top_k) if fresh_memory
                         else memprof.snapshot_cached(top_k=top_k))
    except Exception as e:          # pragma: no cover - defensive
        log.debug("memprof snapshot failed: %s", e)
    return out


def write_perf_dump(path: str, *,
                    registry: Optional[MetricsRegistry] = None,
                    index: Optional[ProgramCostIndex] = None,
                    top_k: int = 10) -> str:
    """Write the offline-report input file: folded cost table, step
    decomposition, memory profile and full metrics snapshot.
    ``tools/perf_report.py`` renders it; a flight-recorder dump is an
    acceptable substitute (it carries the same ``perf`` block)."""
    reg = registry or get_registry()
    idx = index or get_cost_index()
    record = {"perf_dump": 1, "wall_time": time.time(),
              "perf": perf_snapshot(reg, idx, top_k=top_k,
                                    fresh_memory=True),
              "metrics": reg.snapshot()}
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(record, f, default=repr)
    os.replace(tmp, path)
    return path
