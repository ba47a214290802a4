"""Structured spans: nested wall-clock attribution for the hot paths.

``span(name, **attrs)`` opens a structured span on a thread-local stack;
on close it lands ONE Chrome-trace complete event ("ph": "X") in the
registry's trace buffer and one observation in the ``span.<name>_ms``
histogram. Nesting is the stack: a span opened inside another carries the
parent's path, so a trace of ``fit -> epoch -> window -> dispatch`` nests
in Perfetto exactly as the loop nests in code, and the jax signal hooks
(jaxsignals.py) attribute backend compiles to ``current_span_path()`` of
the compiling thread.

Sync-freedom: a span records two ``perf_counter_ns`` reads and a couple
of dict writes — it never touches a device value, so instrumenting the
dispatch loop cannot serialize it (the tier-1 sync-freedom test pins
this). When the registry is disabled, ``span()`` returns a shared no-op
context manager: one attribute check, zero allocation.

Every span is also a ``jax.profiler.TraceAnnotation`` named by its path:
while a profile is being taken (``jax.profiler.start_trace``, an
operator's capture) the spans are events of the host plane of the same
``.xplane.pb``, on the profiler's clock, beside the runtime's launch
events. With no profile active the annotation is a flag check. The
attributes a span names in ``annotate`` ride the annotation as its
metadata, so a reader of the ``.xplane.pb`` finds them on the event
(the serving loop's ``step``).

A span that ends inside another adds its duration to the parent's
``child_ms[name]``: a caller that wants its callee's time reads the
callee's own stopwatch, not the clock again.
"""
from __future__ import annotations

import threading
import time
from typing import List, Optional, Sequence

from jax.profiler import TraceAnnotation

from .registry import MetricsRegistry, get_registry
from .tracecontext import current_trace_id

__all__ = ["Span", "span", "current_span", "current_span_path",
           "record_external_span", "wall_us"]

# Chrome-trace timestamps are microseconds; anchor perf_counter_ns to the
# unix epoch once so every event in a process shares one clock domain.
_EPOCH_NS = time.time_ns() - time.perf_counter_ns()

_tls = threading.local()


def _stack() -> List["Span"]:
    s = getattr(_tls, "stack", None)
    if s is None:
        s = _tls.stack = []
    return s


class _NoopSpan:
    """Shared do-nothing span for a disabled registry: it times nothing,
    so ``dur_ms`` stays 0.0."""

    __slots__ = ()
    name = path = "<disabled>"
    dur_ms = 0.0
    child_ms = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def start(self):
        return self

    def end(self):
        return self

    def set_attr(self, key, value):
        pass


_NOOP = _NoopSpan()


class Span:
    """One timed, attributed region. Context-manager use is the norm;
    ``start()``/``end()`` exist for regions that do not nest lexically
    (e.g. ProfilerListener's capture window opens in one listener callback
    and closes in a later one). ``dur_ms`` holds the duration once the
    span has ended, for a caller that feeds a metric of its own from the
    interval the span already timed; ``child_ms`` (None until a child
    has ended) the summed durations of the spans that ended inside it, by
    name."""

    __slots__ = ("name", "attrs", "path", "registry", "dur_ms", "child_ms",
                 "_annotate", "_t0", "_tid", "_ended", "_trace_id",
                 "_annotation")

    def __init__(self, name: str, registry: MetricsRegistry, attrs: dict,
                 annotate: Sequence[str] = ()):
        self.name = name
        self.attrs = attrs
        self.registry = registry
        self.path = name          # parent path resolved at start()
        self.dur_ms = 0.0
        self.child_ms: Optional[dict] = None
        self._annotate = annotate
        self._annotation = None
        self._t0 = 0
        self._tid = 0
        self._ended = False
        self._trace_id = None

    # ------------------------------------------------------------- lifecycle
    def start(self) -> "Span":
        stack = _stack()
        if stack:
            self.path = stack[-1].path + "/" + self.name
        else:
            # a handed-off scope (tracecontext.adopt) installs the
            # producer's span path as a virtual root: the first span a
            # consumer thread opens parents under the producer's path
            root = getattr(_tls, "virtual_root", "")
            if root:
                self.path = root + "/" + self.name
        stack.append(self)
        # request tracing: stamp the ACTIVE trace context (if any) so the
        # closed event is keyed by trace id alongside its span path
        self._trace_id = current_trace_id()
        self._tid = threading.get_ident() & 0xFFFFFFFF
        attrs = self.attrs
        self._annotation = TraceAnnotation(
            self.path, **{k: attrs[k] for k in self._annotate})
        self._annotation.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def end(self) -> "Span":
        t1 = time.perf_counter_ns()
        if self._ended:
            return self
        self._ended = True
        if self._annotation is not None:
            self._annotation.__exit__(None, None, None)
        stack = _stack()
        # the common case is LIFO exit; tolerate out-of-order manual end()
        if stack and stack[-1] is self:
            stack.pop()
        elif self in stack:
            while stack and stack.pop() is not self:
                pass
        dur_ns = t1 - self._t0
        self.dur_ms = dur_ns / 1e6
        if stack:
            parent = stack[-1]
            if parent.child_ms is None:
                parent.child_ms = {}
            parent.child_ms[self.name] = \
                parent.child_ms.get(self.name, 0.0) + self.dur_ms
        reg = self.registry
        if reg.enabled:
            args = self.attrs
            args["path"] = self.path
            if self._trace_id is not None:
                args["trace_id"] = self._trace_id
            reg.record_event({"name": self.name, "ph": "X", "cat": "span",
                              "ts": (self._t0 + _EPOCH_NS) // 1000,
                              "dur": dur_ns // 1000,
                              "pid": 1, "tid": self._tid, "args": args})
            reg.histogram("span." + self.name + "_ms").observe(self.dur_ms)
        return self

    def set_attr(self, key: str, value) -> None:
        self.attrs[key] = value

    def __enter__(self) -> "Span":
        return self.start()

    def __exit__(self, *exc) -> bool:
        self.end()
        return False


_hook_ready = False


def span(name: str, annotate: Sequence[str] = (), **attrs):
    """Open a structured span (context manager). ``attrs`` must be
    host-side values (ints/strs) — passing a device array would force the
    readback this layer exists to avoid. ``annotate`` names the attributes
    (given here, not set later) that also go to the span's
    ``TraceAnnotation`` as metadata."""
    reg = get_registry()
    if not reg.enabled:
        return _NOOP
    global _hook_ready
    if not _hook_ready:
        from . import jaxsignals
        jaxsignals.ensure_monitoring_hook()   # compiles attribute to spans
        _hook_ready = True
    return Span(name, reg, attrs, annotate)


def wall_us() -> int:
    """Now, on the clock of every trace event's ``ts`` (microseconds)."""
    return (time.perf_counter_ns() + _EPOCH_NS) // 1000


def record_external_span(name: str, dur_ms: float, cat: str = "external",
                         **attrs) -> None:
    """Land a Chrome-trace complete event for a duration measured OUTSIDE
    the span stack (a profiled collective, a subprocess stage, an
    externally-timed region), attributed under the innermost open span's
    path like the jaxsignals compile events. ``cat`` distinguishes it from
    lexical spans — tools/trace2summary.py folds non-span categories into
    their own ``[name]`` buckets (per-bucket for cat="collective" events
    carrying a ``bucket`` attr) instead of inflating the enclosing span."""
    reg = get_registry()
    if not reg.enabled:
        return
    # args.path carries the ENCLOSING span path (same contract as the
    # backend_compile events): trace2summary appends "[name]" itself
    args = dict(attrs)
    args["path"] = current_span_path()
    tid_trace = current_trace_id()
    if tid_trace is not None:
        args["trace_id"] = tid_trace
    now_ns = time.perf_counter_ns()
    dur_us = max(0, int(dur_ms * 1000))
    reg.record_event({"name": name, "ph": "X", "cat": cat,
                      "ts": (now_ns + _EPOCH_NS) // 1000 - dur_us,
                      "dur": dur_us, "pid": 1,
                      "tid": threading.get_ident() & 0xFFFFFFFF,
                      "args": args})


def current_span() -> Optional[Span]:
    stack = getattr(_tls, "stack", None)
    return stack[-1] if stack else None


def current_span_path() -> str:
    """'fit/epoch/window/dispatch'-style path of the innermost open span on
    THIS thread ('' outside any span) — the attribution key the recompile
    and host-sync detectors report."""
    stack = getattr(_tls, "stack", None)
    return stack[-1].path if stack else ""
