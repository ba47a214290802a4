"""Cold start as load-not-compile: persistent-compilation-cache accounting.

Scaling out on an SLO burn-rate signal only works if a fresh replica is
serving before the traffic spike is over — and a generation replica's
startup cost is dominated by compiling its prefill/decode/replay/COW
program set, not by loading weights. The JAX persistent compilation
cache turns that compile storm into file loads: every replica shares one
cache directory (``util.compile_cache.ensure_compile_cache`` — placed by
``JAX_COMPILATION_CACHE_DIR``, which replicas inherit from their
supervisor), the FIRST replica to see a program pays the compile and
writes the executable, and every later replica — including one spawned
mid-spike by the autoscaler — warms the identical program set in
checkpoint-load time.

Accounting: on this jax line the ``backend_compile_duration`` monitoring
event fires even when the executable was answered from the cache, so
"did this replica compile anything NEW" is ``xla_compile_count() -
xla_cache_hit_count()`` — :func:`fresh_compile_count`. The acceptance
(tests/test_fleet_process.py) is that a warm-cache replica reaches ready
with ZERO fresh compiles for already-seen programs.
"""
from __future__ import annotations


def fresh_compile_count() -> int:
    """Programs this process actually compiled (cache hits excluded)."""
    return snapshot()["fresh_compiles"]


def snapshot() -> dict:
    """The cold-start accounting block replicas publish at ready time."""
    import jax

    from ...telemetry import xla_cache_hit_count, xla_compile_count
    compiles = xla_compile_count()
    hits = xla_cache_hit_count()
    return {"cache_dir": jax.config.jax_compilation_cache_dir,
            "compiles": compiles,
            "cache_hits": hits,
            "fresh_compiles": max(0, compiles - hits)}
