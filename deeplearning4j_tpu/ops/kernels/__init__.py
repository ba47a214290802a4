"""Kernel library: registry of Pallas kernels + XLA fallbacks (ISSUE 17).

Import-light on purpose — ``envutil`` and ``registry`` only, so the
pallas_* modules can use the shared env plumbing without a cycle; the
builtin kernel registrations load lazily on first registry query.
"""
from . import envutil  # noqa: F401
from .registry import (KernelSpec, ParityPin, active_impl, get,  # noqa: F401
                       kernels_snapshot, names, parity_error,
                       record_kernel_timing, register)

__all__ = ["KernelSpec", "ParityPin", "active_impl", "get",
           "kernels_snapshot", "names", "parity_error",
           "record_kernel_timing", "register", "envutil"]
