"""Which device this process really runs on.

JAX falls back to the CPU with only a warning when ``JAX_PLATFORMS`` is
unset and the accelerator cannot be opened (for example because another
process holds the chip). Entry points that measure or serve on a device
check here after start-up and fail instead of carrying on.
"""
from __future__ import annotations

import os


def device_record() -> dict:
    """``{"platform", "kind", "count"}`` as JAX reports them — the label
    every benchmark result, replica ready record and ``chip_smoke.py``
    result carries."""
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def cpu_was_requested() -> bool:
    """True when ``JAX_PLATFORMS`` puts the CPU FIRST (the default backend
    it asks for — ``"tpu,cpu"`` asks for the TPU): a CPU backend is then a
    choice; otherwise it is JAX's silent fallback."""
    return os.environ.get("JAX_PLATFORMS", "").lower().split(",")[0] == "cpu"
