"""JAX-native telemetry signals: recompiles, host syncs, device memory.

Three runtime betrayals the compiler never announces loudly enough:

- **Silent retraces/recompiles.** Every XLA backend compile emits a
  ``/jax/core/compile/backend_compile_duration`` event on
  ``jax.monitoring``. ONE process-wide fan-out listener (jax 0.4.x has no
  unregister, so it is installed once and dispatches to subscribers)
  counts them, lands a Chrome-trace event attributed to the compiling
  thread's active span path, and feeds any live ``RecompileDetector`` —
  which turns "training got slow" into "iteration 14 recompiled inside
  fit/epoch/window/dispatch".

- **Accidental host syncs.** A ``float(loss)`` in the wrong place
  serializes the whole async dispatch pipeline. ``HostSyncDetector``
  wraps the jax array host-materialization funnel
  (``ArrayImpl._value`` — the path ``float()``/``bool()``/``str()``/
  ``.tolist()``/printing take on EVERY backend, including the CPU test
  platform where XLA's transfer guard is a no-op because host arrays are
  zero-copy) and flags each first materialization inside the armed scope
  with the offending span path. On real device backends pass
  ``transfer_guard="disallow"`` to additionally arm
  ``jax.transfer_guard_device_to_host`` for the copies the Python funnel
  cannot see (``np.asarray``/``device_get`` go through C).

- **Device memory.** ``device_memory_gauges`` snapshots
  ``Device.memory_stats()`` into ``device<i>.bytes_in_use`` /
  ``peak_bytes_in_use`` gauges (watermark kept by the Gauge itself).
  CPU backends report no stats; the gauges simply stay absent there.
"""
from __future__ import annotations

import logging
import threading
import time
from typing import Callable, Dict, List, Optional

from .registry import MetricsRegistry, get_registry
from .spans import _EPOCH_NS, current_span, current_span_path

log = logging.getLogger("deeplearning4j_tpu")

__all__ = ["xla_compile_count", "xla_cache_hit_count",
           "ensure_monitoring_hook",
           "RecompileDetector", "HostSyncDetector", "HostSyncError",
           "device_memory_gauges"]

_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

_hook_lock = threading.Lock()
_hook_installed = False
_compile_count = 0
_cache_hit_count = 0
_compile_subscribers: List[Callable[[str, float], None]] = []


def ensure_monitoring_hook() -> None:
    """Install the process-wide jax.monitoring fan-out (idempotent)."""
    global _hook_installed
    if _hook_installed:
        return
    with _hook_lock:
        if _hook_installed:
            return
        import jax.monitoring

        def _on_duration(name, secs, **kw):
            global _compile_count
            if name != _BACKEND_COMPILE_EVENT:
                return
            _compile_count += 1
            path = current_span_path()
            reg = get_registry()
            if reg.enabled:
                reg.counter("jax.compiles").inc()
                reg.histogram("jax.compile_ms").observe(secs * 1e3)
                # synthesized complete event: the listener fires when the
                # compile FINISHES, so backdate the start by its duration
                now_ns = time.perf_counter_ns()
                reg.record_event({
                    "name": "backend_compile", "ph": "X", "cat": "compile",
                    "ts": (now_ns + _EPOCH_NS) // 1000 - int(secs * 1e6),
                    "dur": int(secs * 1e6), "pid": 1,
                    "tid": threading.get_ident() & 0xFFFFFFFF,
                    "args": {"path": path, "duration_s": round(secs, 6)}})
            for cb in list(_compile_subscribers):
                cb(path, secs)

        def _on_event(name, **kw):
            # persistent-compilation-cache hits: on this jax line the
            # backend_compile duration event fires even when the
            # executable was LOADED from the cache, so "how many programs
            # did this process freshly compile" is compiles MINUS hits —
            # the cold-start pin (serving/fleet/coldstart.py) reads both
            global _cache_hit_count
            if name == _CACHE_HIT_EVENT:
                _cache_hit_count += 1
                reg = get_registry()
                if reg.enabled:
                    reg.counter("jax.compile_cache_hits").inc()

        # jax 0.4.x registers but cannot unregister a listener; one
        # fan-out installed once per process dispatches to subscribers.
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_listener(_on_event)
        _hook_installed = True


def xla_compile_count() -> int:
    """Process-wide XLA backend-compile count (the zero-recompile
    assertions in serving ride this — snapshot after warm-up, any later
    increase means something recompiled)."""
    ensure_monitoring_hook()
    return _compile_count


def xla_cache_hit_count() -> int:
    """Process-wide persistent-compilation-cache hit count. A program
    answered from the cache still fires the backend-compile duration
    event on this jax line, so ``xla_compile_count() -
    xla_cache_hit_count()`` is the number of FRESH compiles — the
    cold-start acceptance pin."""
    ensure_monitoring_hook()
    return _cache_hit_count


_STDLIB_DIR = None
_THIS_PKG_DIR = None


def _source_hint() -> str:
    """Best-effort 'file.py:line in func' for the user code driving the
    current compile: the innermost stack frame that is neither installed
    jax internals, the stdlib (contextlib/threading wrappers around the
    compile call), nor this telemetry package. Filters are anchored to
    site-packages / this package's own directory so a user file that
    merely CONTAINS 'jax' or 'telemetry' in its path is never skipped.
    Only computed when a flagged warning is being emitted — the stack
    walk is microseconds next to the multi-ms compile it annotates."""
    import os as _os
    import traceback
    global _STDLIB_DIR, _THIS_PKG_DIR
    if _STDLIB_DIR is None:
        import sysconfig
        _STDLIB_DIR = sysconfig.get_paths()["stdlib"].replace("\\", "/")
        _THIS_PKG_DIR = _os.path.dirname(
            _os.path.abspath(__file__)).replace("\\", "/")
    try:
        for frame in reversed(traceback.extract_stack()):
            fn = frame.filename.replace("\\", "/")
            if "/site-packages/jax" in fn or "/dist-packages/jax" in fn:
                continue               # jax/jaxlib/jax_* installs
            if fn.startswith(_THIS_PKG_DIR):
                continue               # this telemetry package
            if fn.startswith(_STDLIB_DIR) and "-packages" not in fn:
                continue               # contextlib/threading plumbing
            return f"{fn}:{frame.lineno} in {frame.name}"
    except Exception:
        pass
    return ""


class RecompileDetector:
    """Scoped recompile watchdog: counts backend compiles while armed and
    attributes each to the active span path of the compiling thread.

        with RecompileDetector(allowed=0) as det:
            serve_steady_state_traffic()
        det.count            # compiles observed in scope
        det.events           # [{"span_path", "span_attrs", "source",
                             #   "duration_s", "wall_time"}]

    ``allowed`` compiles (warm-up budget) pass silently; every compile
    beyond it logs a WARNING naming the offending span path, that span's
    attrs (iteration/shape/model context the instrumentation already
    attached) and a best-effort source hint — so a steady-state recompile
    is actionable ("iteration 14 recompiled, driven from train.py:88")
    rather than just counted.
    """

    def __init__(self, *, allowed: int = 0, warn: bool = True,
                 registry: Optional[MetricsRegistry] = None):
        self.allowed = allowed
        self.warn = warn
        self.registry = registry or get_registry()
        self.count = 0
        self.events: List[dict] = []
        self._armed = False

    def _on_compile(self, span_path: str, secs: float) -> None:
        self.count += 1
        sp = current_span()           # innermost span of the compiling thread
        attrs = {k: v for k, v in (sp.attrs if sp is not None else {}).items()
                 if k != "path"}
        # the stack walk is only paid when the compile is actually going
        # to be FLAGGED (past the warm-up budget on a warning detector) —
        # a silently-counting detector (the generation decode loop keeps
        # one armed permanently) adds nothing to legitimate compiles
        flagged = self.warn and self.count > self.allowed
        source = _source_hint() if flagged else ""
        self.events.append({"span_path": span_path,
                            "span_attrs": attrs,
                            "source": source,
                            "duration_s": round(secs, 6),
                            "wall_time": time.time()})
        if self.registry.enabled:
            self.registry.counter("jax.recompiles_flagged").inc()
        if flagged:
            log.warning(
                "RecompileDetector: backend compile #%d (%.1f ms) during "
                "span '%s'%s%s — a steady-state loop should not trace; "
                "check for shape/dtype instability or un-jitted host "
                "control flow", self.count, secs * 1e3,
                span_path or "<no span>",
                f" (span attrs: {attrs})" if attrs else "",
                f" (driven from {source})" if source else "")

    def __enter__(self) -> "RecompileDetector":
        ensure_monitoring_hook()
        if not self._armed:
            _compile_subscribers.append(self._on_compile)
            self._armed = True
        return self

    def __exit__(self, *exc) -> bool:
        if self._armed:
            try:
                _compile_subscribers.remove(self._on_compile)
            except ValueError:
                pass
            self._armed = False
        return False

    @property
    def recompiles(self) -> int:
        """Compiles beyond the allowed (warm-up) budget."""
        return max(0, self.count - self.allowed)


class HostSyncError(RuntimeError):
    """Raised by HostSyncDetector(action="raise") at the sync site."""


_sync_lock = threading.Lock()
_sync_installed = False
_sync_detectors: List["HostSyncDetector"] = []


# set on an array by the tripwire after a host read that jax did not cache
_HOST_READ_MARK = "_dl4j_tpu_host_read"


def _install_sync_tripwire() -> None:
    """Wrap ArrayImpl._value (idempotent, installed once per process).

    ``_value`` is the single host-materialization funnel for implicit
    readbacks: ``float()``, ``bool()``, ``str()``, ``.tolist()``,
    iteration, printing. The wrapper costs one list check when no
    detector is armed. Only the FIRST materialization of a buffer is
    flagged — exactly the event that blocks on the device; re-reads are
    free and stay unflagged. jax caches the host copy in ``_npy_value``
    when it made one (a real device); on the zero-copy CPU backend jax
    0.9.0 keeps no cache, so the wrapper marks the array itself after the
    read. (An array read before the tripwire was first installed carries
    no mark: on the CPU its next read inside a scope is flagged once.)
    """
    global _sync_installed
    if _sync_installed:
        return
    with _sync_lock:
        if _sync_installed:
            return
        from jax._src import array as _jarray
        orig = getattr(_jarray.ArrayImpl, "_value", None)
        if not isinstance(orig, property) \
                or not hasattr(_jarray.ArrayImpl, "_npy_value"):
            # written for jax 0.9.0's ArrayImpl; anything else must not
            # pass for a clean bill of health
            raise RuntimeError(
                "HostSyncDetector: jax._src.array.ArrayImpl no longer has "
                "the _value property / _npy_value cache this tripwire "
                "wraps (written for jax 0.9.0) — it would report zero "
                "syncs whatever the code did; port it before relying on "
                "sync-freedom checks")
        fget = orig.fget

        def _traced_value(self):
            first = self._npy_value is None \
                and not getattr(self, _HOST_READ_MARK, False)
            if first and _sync_detectors:
                tid = threading.get_ident()
                for det in list(_sync_detectors):
                    det._on_sync(self, tid)
            out = fget(self)
            if first and self._npy_value is None:    # zero-copy: no cache
                setattr(self, _HOST_READ_MARK, True)
            return out

        _jarray.ArrayImpl._value = property(_traced_value)
        _sync_installed = True


class HostSyncDetector:
    """Scoped device->host readback tripwire.

        with HostSyncDetector() as det:          # action="warn"
            fit_window()
        assert det.count == 0

    ``action``: "count" (silent), "warn" (log WARNING with the span path
    and array shape), or "raise" (HostSyncError at the sync site — the
    hard mode for pinning a fused scan window sync-free in CI).
    ``thread_only=True`` (default) scopes detection to the arming thread,
    so a serving worker's legitimate readbacks on another thread don't
    trip a detector armed around a training loop.
    ``transfer_guard`` optionally arms jax's own d2h transfer guard with
    the given mode for the scope (real accelerator backends only — it is
    a no-op on the zero-copy CPU platform).
    """

    def __init__(self, *, action: str = "warn", thread_only: bool = True,
                 registry: Optional[MetricsRegistry] = None,
                 transfer_guard: Optional[str] = None):
        if action not in ("count", "warn", "raise"):
            raise ValueError(f"unknown action {action!r}")
        self.action = action
        self.thread_only = thread_only
        self.registry = registry or get_registry()
        self.transfer_guard = transfer_guard
        self.count = 0
        self.events: List[dict] = []
        self._tid = None
        self._guard_cm = None

    # called from the _value wrapper, possibly on any thread
    def _on_sync(self, arr, tid: int) -> None:
        if self.thread_only and tid != self._tid:
            return
        path = current_span_path()
        try:
            shape = tuple(arr.shape)
        except Exception:
            shape = ()
        self.count += 1
        self.events.append({"span_path": path, "shape": shape,
                            "wall_time": time.time()})
        reg = self.registry
        if reg.enabled:
            reg.counter("jax.host_syncs_flagged").inc()
            reg.record_event({
                "name": "host_sync", "ph": "i", "cat": "sync", "s": "t",
                "ts": (time.perf_counter_ns() + _EPOCH_NS) // 1000,
                "pid": 1, "tid": tid & 0xFFFFFFFF,
                "args": {"path": path, "shape": str(shape)}})
        if self.action == "warn":
            log.warning(
                "HostSyncDetector: device->host readback of shape %s "
                "during span '%s' — this blocks the async dispatch "
                "pipeline; defer the readback (score_to_float protocol) "
                "or move it off the hot path", shape, path or "<no span>")
        elif self.action == "raise":
            raise HostSyncError(
                f"unexpected device->host readback (shape {shape}) during "
                f"span '{path or '<no span>'}'")

    def __enter__(self) -> "HostSyncDetector":
        _install_sync_tripwire()
        self._tid = threading.get_ident()
        with _sync_lock:
            _sync_detectors.append(self)
        if self.transfer_guard is not None:
            import jax
            self._guard_cm = jax.transfer_guard_device_to_host(
                self.transfer_guard)
            self._guard_cm.__enter__()
        return self

    def __exit__(self, *exc) -> bool:
        with _sync_lock:
            try:
                _sync_detectors.remove(self)
            except ValueError:
                pass
        if self._guard_cm is not None:
            self._guard_cm.__exit__(*exc)
            self._guard_cm = None
        return False


def device_memory_gauges(registry: Optional[MetricsRegistry] = None
                         ) -> Dict[str, float]:
    """Snapshot per-device memory stats into ``device<i>.bytes_in_use`` /
    ``device<i>.peak_bytes_in_use`` gauges. Returns the values read.

    Backends without ``memory_stats()`` (the CPU test platform) fall back
    to live-array accounting (telemetry/memprof.py): ``bytes_in_use``
    becomes the per-device sum of ``jax.live_arrays()`` byte sizes and a
    ``device<i>.live_arrays_fallback`` marker gauge is set to 1 so a
    reader can tell allocator truth from accounting estimate — the peak
    watermark rides the Gauge's built-in ``max`` either way. Before this
    fallback the memory path silently contributed nothing on CPU, so
    tier-1 never exercised it."""
    import jax
    reg = registry or get_registry()
    out: Dict[str, float] = {}
    for i, dev in enumerate(jax.local_devices()):
        try:
            stats = dev.memory_stats()
        except Exception:
            stats = None
        if not stats:
            continue
        for key in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit"):
            if key in stats:
                name = f"device{i}.{key}"
                reg.gauge(name).set(float(stats[key]))
                out[name] = float(stats[key])
    if not out:
        global _fallback_cache
        now = time.monotonic()
        cached_t, per_dev = _fallback_cache
        if per_dev is None or now - cached_t >= _FALLBACK_MIN_INTERVAL_S:
            # the walk is O(live arrays) and this runs at every epoch
            # boundary — a long-lived process (or a test session) can
            # hold tens of thousands of live arrays, so the WALK is
            # time-throttled; the gauges are (re)set from the cached
            # values on every call either way
            from . import memprof
            try:
                per_dev = memprof.live_bytes_by_device()
            except Exception:   # pragma: no cover - defensive
                return out
            _fallback_cache = (now, per_dev)
        for dev_id, v in per_dev.items():
            name = f"device{dev_id}.bytes_in_use"
            reg.gauge(name).set(float(v))
            reg.gauge(f"device{dev_id}.live_arrays_fallback").set(1.0)
            out[name] = float(v)
    return out


# live-array fallback walk throttle: (last walk monotonic time, values)
_FALLBACK_MIN_INTERVAL_S = 5.0
_fallback_cache = (0.0, None)
