"""Test config: force an 8-device virtual CPU platform BEFORE jax import.

Mirrors the reference's test stance (SURVEY.md §4): the CPU backend is the
"fake device" for all tests; multi-device semantics are exercised via
xla_force_host_platform_device_count=8 (the analogue of Spark local[n]).
"""
import os
import tempfile

# Tests run on the virtual 8-device CPU platform wherever they are started
# (SURVEY.md §4: the analogue of the reference's Spark local[n] testing);
# the chip is reached only through chip_smoke.py and benchmarks/run.py.
# The env var covers this process and every child it spawns (fleet
# replicas).
os.environ["JAX_PLATFORMS"] = "cpu"
# The CPU has no row in telemetry/perf.py's DEVICE_PEAKS, so without an
# override there is no MFU/roofline gauge at all. Tests that assert those
# gauges need a denominator: set the explicit overrides ONCE here (the
# values are arbitrary for a CPU; test_perf.py pins the no-override
# behaviour by removing them).
os.environ.setdefault("BENCH_PEAK_TFLOPS", "197.0")
os.environ.setdefault("BENCH_HBM_GBPS", "819")
# Parity tests exercise the fused Pallas LSTM/attention via the interpreter
# on CPU; production CPU runs take the (much faster) XLA fallbacks instead.
os.environ.setdefault("DL4J_TPU_FUSED_LSTM_INTERPRET", "1")
os.environ.setdefault("DL4J_TPU_FUSED_ATTN_INTERPRET", "1")
os.environ.setdefault("DL4J_TPU_FUSED_ENCODE_INTERPRET", "1")
# Isolate the autotune decision cache from any user-level file: pinned
# block-size expectations (e.g. attention _blocks defaults) must not be
# overridden by stray decisions cached on this machine.
os.environ.setdefault(
    "DL4J_TPU_AUTOTUNE_CACHE",
    os.path.join(tempfile.mkdtemp(prefix="dl4j-autotune-"), "autotune.json"))
# One persistent compilation cache per test SESSION (a fresh temp dir, so
# no state crosses runs): hundreds of tests build the same tiny nets, and
# every fresh jit object recompiles a program an earlier test already
# compiled — the cache turns those into loads (~20% of tier-1's wall
# time). Children (fleet replicas) inherit it; tests that place a cache
# themselves pass their own JAX_COMPILATION_CACHE_DIR. A cache hit still
# fires the backend-compile event, so every compile-count pin is unchanged.
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      tempfile.mkdtemp(prefix="dl4j-jaxcache-"))
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402  (import after env setup)
import numpy as np  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# x64 for gradient checks (reference forces DOUBLE, GradientCheckUtil.java:92-97).
# Regular tests pass explicit float32 dtypes, so they are unaffected.
jax.config.update("jax_enable_x64", True)


@pytest.fixture
def rng():
    return np.random.default_rng(12345)


@pytest.fixture(autouse=True, scope="session")
def _flightrec_sandbox(tmp_path_factory):
    """Point the process-wide flight recorder at a session tmp dir: fault
    injections and failure-path tests dump black boxes as a side effect,
    and those must never land in the working tree."""
    from deeplearning4j_tpu.telemetry import configure_flight_recorder
    # small capacity: chaos/fault tests dump as a side effect dozens of
    # times across the suite; 256-event tails keep that cheap
    configure_flight_recorder(
        directory=str(tmp_path_factory.mktemp("flightrec")),
        capacity=256)


def pytest_collection_modifyitems(config, items):
    """DL4J_TPU_TEST_REVERSE=1 reverses collection order — the harness for
    verifying the suite is order-independent (no test may depend on state
    another test leaked)."""
    if os.environ.get("DL4J_TPU_TEST_REVERSE") == "1":
        items.reverse()


@pytest.fixture(autouse=True)
def _reset_module_rng(request):
    """Kill the test-ordering flake at its root: many modules share a
    module-level ``R = np.random.default_rng(seed)`` — MUTABLE state, so a
    test's data depended on how many draws earlier-running tests made, and
    any deselection / collection change / reordering shifted the stream
    (the statistical assertions downstream then saw different data).
    Restore each module's generator to its import-time state before every
    test: a test's data becomes a function of the test alone, in any
    order. (Import-time state is captured at the module's first-run test —
    draws only ever happen inside tests, so it equals the seeded state
    regardless of which test runs first.)"""
    import copy
    mod = getattr(request.node, "module", None)
    gen = getattr(mod, "R", None)
    if isinstance(gen, np.random.Generator):
        saved = getattr(mod, "_R_import_state", None)
        if saved is None:
            mod._R_import_state = copy.deepcopy(gen.bit_generator.state)
        else:
            gen.bit_generator.state = copy.deepcopy(saved)
    yield
