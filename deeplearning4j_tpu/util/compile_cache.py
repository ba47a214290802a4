"""Where the persistent XLA compilation cache lives — placed from OUTSIDE.

Every entry point that compiles for the chip (``chip_smoke.py``, the
fleet replica child, ``__graft_entry__``) calls
:func:`ensure_compile_cache` before its first compile and nothing else in
the repo names a cache directory:

- ``JAX_COMPILATION_CACHE_DIR`` set: jax has already read it into
  ``jax_compilation_cache_dir``; this module only reports it. A machine
  that comes with the variable set keeps its cache across runs.
- not set: ``<checkout>/.jax_cache`` (git-ignored). The directory is part
  of the cache key, so it is a fixed path, never a temp dir. The variable
  is also exported so child processes (fleet replicas) share the cache —
  that sharing is what makes a replica's cold start load-not-compile.

Either way EVERY program is cached (no size or compile-time floor): a
warm start should load, not compile, the small programs too.
"""
from __future__ import annotations

import os

ENV_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def ensure_compile_cache() -> str:
    """Make sure the persistent compilation cache is on; returns its
    directory. Call BEFORE the first compile — programs compiled earlier
    stay uncached. Idempotent."""
    import jax
    if os.environ.get(ENV_CACHE_DIR):
        cache = jax.config.jax_compilation_cache_dir
    else:
        cache = os.path.join(_CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache)
        os.environ[ENV_CACHE_DIR] = cache
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache
