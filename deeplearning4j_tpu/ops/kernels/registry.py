"""Kernel registry: name → Pallas impl + XLA fallback + env handling.

The reference DL4J's durable perf idea was the bolt-on accelerator-helper
library (cuDNN, ConvolutionLayer.java:72 probe): every accelerated op is a
*pair* — fast helper + always-correct fallback — behind one probe seam.
This registry is that idea made a first-class subsystem for the Pallas
kernels: each registered ``KernelSpec`` carries the fused impl, the XLA
fallback, the applicability probe, the kill-switch/interpret env names
(shared plumbing in ``envutil.py``, legacy ``DL4J_TPU_FUSED_*`` names as
aliases), a *parity pin* (tests/test_kernel_registry.py auto-generates an
interpret-mode CPU parity test per registered kernel — registering a
kernel WITHOUT a pin fails tier-1), and an optional roofline model the
perf gauges use to flag kernels running below their bound.

Builtin kernels are registered lazily (``_ensure_builtins``) so the
pallas_* modules can import ``envutil`` without a cycle.
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Any, Callable, Dict, List, Optional, Tuple

from . import envutil


@dataclasses.dataclass(frozen=True)
class ParityPin:
    """How to check fused-vs-fallback parity for one kernel.

    ``run(seed)`` executes BOTH impls on identical random inputs (the test
    harness has already forced interpret mode via the kernel's env) and
    returns ``(fused_out, fallback_out)`` — each a flat list of arrays.
    ``tol`` is the max absolute error allowed; 0.0 means bit-identical.
    """
    run: Callable[[int], Tuple[List[Any], List[Any]]]
    tol: float = 0.0
    note: str = ""


@dataclasses.dataclass(frozen=True)
class KernelSpec:
    """One registered kernel. ``fused``/``fallback`` are the two impls
    (callable; signature is kernel-specific — callers go through the
    module-level entry points, the registry is the metadata/parity/tuning
    spine). ``applicable`` is the probe predicate."""
    name: str
    fused: Callable
    fallback: Callable
    applicable: Callable[..., bool]
    kill_aliases: Tuple[str, ...] = ()
    interpret_aliases: Tuple[str, ...] = ()
    parity: Optional[ParityPin] = None
    # (shape-sig str) -> (flops, bytes) for one call — feeds the roofline
    # gauges; None = no roofline model (not flagged).
    roofline: Optional[Callable[[str], Tuple[float, float]]] = None
    tunable: str = ""                 # human description of the tunables
    default_choice: Optional[Tuple[int, ...]] = None
    notes: str = ""

    @property
    def kill_env(self) -> str:
        return envutil.kill_env_name(self.name)

    @property
    def interpret_env(self) -> str:
        return envutil.interpret_env_name(self.name)

    def enabled(self) -> bool:
        return envutil.fused_enabled(self.name, self.kill_aliases)

    def interpret_opted_in(self) -> bool:
        return envutil.interpret_opted_in(self.name, self.interpret_aliases)


_LOCK = threading.Lock()
_REGISTRY: Dict[str, KernelSpec] = {}
_BUILTINS_LOADED = False


def register(spec: KernelSpec) -> KernelSpec:
    with _LOCK:
        if spec.name in _REGISTRY:
            raise ValueError(f"kernel {spec.name!r} already registered")
        _REGISTRY[spec.name] = spec
    return spec


def _ensure_builtins() -> None:
    global _BUILTINS_LOADED
    if _BUILTINS_LOADED:
        return
    with _LOCK:
        if _BUILTINS_LOADED:
            return
        _BUILTINS_LOADED = True
    from . import builtins as _builtins  # noqa: F401 — registers on import


def get(name: str) -> KernelSpec:
    _ensure_builtins()
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(f"no kernel {name!r} registered "
                       f"(have: {sorted(_REGISTRY)})") from None


def names() -> List[str]:
    _ensure_builtins()
    return sorted(_REGISTRY)


def active_impl(name: str) -> str:
    """Which implementation a dispatch would use RIGHT NOW on this
    backend: 'fused' (TPU Pallas), 'interpret' (CPU pallas interpreter,
    parity-test opt-in), or 'fallback' (XLA path — killed, or backend
    without a fused path). Answers from the backend's NAME only; whether
    a compiled program really carries the kernel is read off the program
    (``chip_smoke.py`` counts its ``tpu_custom_call``s)."""
    spec = get(name)
    if not spec.enabled():
        return "fallback"
    import jax
    backend = jax.default_backend()
    if backend == "tpu":
        return "fused"
    if backend == "cpu" and spec.interpret_opted_in():
        return "interpret"
    return "fallback"


def parity_error(name: str, seed: int = 0) -> float:
    """Max absolute fused-vs-fallback error of kernel ``name``'s parity
    pin on THIS backend (compare against ``get(name).parity.tol``). The
    tier-1 suite runs it in the CPU interpreter, ``chip_smoke.py`` on the
    TPU — the same pin both ways."""
    import numpy as np
    fused, fallback = get(name).parity.run(seed)
    if not fused or len(fused) != len(fallback):
        raise ValueError(f"kernel {name!r}: parity pin returned "
                         f"{len(fused)} fused vs {len(fallback)} fallback "
                         f"outputs")
    return max(float(np.max(np.abs(np.asarray(a, np.float64)
                                   - np.asarray(b, np.float64))))
               for a, b in zip(fused, fallback))


def kernels_snapshot() -> Dict[str, Dict[str, Any]]:
    """One JSON-able dict per registered kernel — embedded in
    ``telemetry.perf.perf_snapshot()['kernels']`` (so perf dumps carry it)
    and read back by tools/kernels_report.py and the dashboard card."""
    from . import autotune
    out: Dict[str, Dict[str, Any]] = {}
    for name in names():
        spec = get(name)
        row: Dict[str, Any] = {
            "impl": active_impl(name),
            "enabled": spec.enabled(),
            "kill_env": spec.kill_env,
            "kill_aliases": list(spec.kill_aliases),
            "interpret_env": spec.interpret_env,
            "tunable": spec.tunable,
            "has_parity_pin": spec.parity is not None,
        }
        if spec.default_choice is not None:
            row["default_choice"] = list(spec.default_choice)
        decisions = autotune.decisions_for(name)
        if decisions:
            row["autotune"] = decisions
        out[name] = row
    return out


def record_kernel_timing(name: str, shape_sig: str,
                         measured_s: float) -> Optional[Dict[str, float]]:
    """Fold one measured kernel time into the live perf gauges and flag
    below-roofline kernels — ``perf.kernels.<name>.measured_ms`` /
    ``.roofline_ms`` / ``.vs_roofline`` / ``.below_roofline`` (1.0 when
    the kernel runs slower than 2x its roofline bound). No-op (returns
    None) when the
    kernel has no roofline model or the device's peaks are unknown."""
    spec = get(name)
    if spec.roofline is None or measured_s <= 0:
        return None
    try:
        flops, nbytes = spec.roofline(shape_sig)
    except Exception:
        return None
    from ...telemetry import get_registry
    from ...telemetry.perf import classify_roofline
    # attainable_tflops already folds in memory-bound derating
    att = classify_roofline(flops, nbytes)["attainable_tflops"]
    if not att:
        return None
    roof_s = (flops / 1e12) / att if flops else 0.0
    ratio = (measured_s / roof_s) if roof_s else 0.0
    row = {"measured_ms": measured_s * 1e3, "roofline_ms": roof_s * 1e3,
           "vs_roofline": ratio,
           "below_roofline": 1.0 if (ratio and ratio > 2.0) else 0.0}
    reg = get_registry()
    if reg.enabled:
        base = f"perf.kernels.{name}"
        for k, v in row.items():
            reg.gauge(f"{base}.{k}").set(v)
    return row
