"""Kernel registry + autotune harness (ISSUE 17 tentpole).

Pins:
  - every registered kernel carries a parity pin — the parity test below
    is AUTO-GENERATED from the registry, so registering a kernel without
    a pin fails tier-1 by construction;
  - per-kernel interpret-mode CPU parity: fused (pallas interpreter) vs
    XLA fallback within the kernel's declared tolerance (0.0 = bitwise);
  - kill-switch/interpret env resolution is the ONE shared envutil
    implementation: canonical ``DL4J_TPU_KERNEL_<NAME>`` names win,
    legacy ``DL4J_TPU_FUSED_*`` names keep working as aliases
    (regression for every pre-registry script and runbook);
  - autotune decisions are measured once, cached per (kernel, shape-sig,
    backend), and REPLAYED without re-measurement; no-measurement
    backends record "defaults stand" with the reason; a cached decision
    actually changes ``pallas_attention._blocks`` while explicit env
    overrides still win;
  - ``kernels_snapshot()`` rides ``perf_snapshot()`` and
    ``record_kernel_timing`` publishes the roofline-vs-measured gauges.
"""
import json
import os

import pytest

from deeplearning4j_tpu.ops import kernels
from deeplearning4j_tpu.ops.kernels import autotune, envutil

BUILTINS = ("attention", "lstm", "threshold_encode", "int8_matmul",
            "conv1x1_bias_relu", "paged_attention", "moe_experts")


# ----------------------------------------------------------------- registry
def test_builtin_kernels_registered():
    have = kernels.names()
    for name in BUILTINS:
        assert name in have, f"builtin kernel {name!r} missing"


def test_duplicate_registration_rejected():
    spec = kernels.get("attention")
    with pytest.raises(ValueError, match="already registered"):
        kernels.register(spec)


@pytest.mark.parametrize("name", kernels.names())
def test_every_kernel_has_parity_pin(name):
    """A kernel registered without a ParityPin fails tier-1 (the contract
    that makes the parity suite auto-generated rather than opt-in)."""
    spec = kernels.get(name)
    assert spec.parity is not None, \
        f"kernel {name!r} registered without a parity pin"


@pytest.mark.parametrize("name", kernels.names())
def test_kernel_parity_interpret_mode(name, monkeypatch):
    """Auto-generated per-kernel pin: fused impl (CPU pallas interpreter)
    vs XLA fallback on identical inputs, within the declared tol."""
    spec = kernels.get(name)
    monkeypatch.setenv(spec.interpret_env, "1")
    for alias in spec.interpret_aliases:
        monkeypatch.setenv(alias, "1")
    monkeypatch.delenv(spec.kill_env, raising=False)
    for alias in spec.kill_aliases:
        monkeypatch.delenv(alias, raising=False)
    err = kernels.parity_error(name)
    assert err <= spec.parity.tol, \
        (name, err, spec.parity.tol, spec.parity.note)


# ------------------------------------------------------------ env plumbing
def test_env_names_canonical():
    assert envutil.kill_env_name("int8_matmul") == \
        "DL4J_TPU_KERNEL_INT8_MATMUL"
    assert envutil.interpret_env_name("conv1x1_bias_relu") == \
        "DL4J_TPU_KERNEL_CONV1X1_BIAS_RELU_INTERPRET"


@pytest.mark.parametrize("name,legacy", [
    ("attention", "DL4J_TPU_FUSED_ATTENTION"),
    ("lstm", "DL4J_TPU_FUSED_LSTM"),
    ("threshold_encode", "DL4J_TPU_FUSED_ENCODE"),
])
def test_legacy_kill_aliases_honored(name, legacy, monkeypatch):
    """Regression: the pre-registry DL4J_TPU_FUSED_* kill switches keep
    working through the registry dispatch."""
    spec = kernels.get(name)
    monkeypatch.delenv(spec.kill_env, raising=False)
    assert spec.enabled()
    for off in ("0", "false", "OFF"):
        monkeypatch.setenv(legacy, off)
        assert not spec.enabled(), (legacy, off)
        assert kernels.active_impl(name) == "fallback"
    # canonical name wins when both are set
    monkeypatch.setenv(spec.kill_env, "1")
    monkeypatch.setenv(legacy, "0")
    assert spec.enabled()


def test_canonical_kill_switch_new_kernels(monkeypatch):
    spec = kernels.get("int8_matmul")
    assert spec.kill_aliases == ()
    assert spec.enabled()
    monkeypatch.setenv("DL4J_TPU_KERNEL_INT8_MATMUL", "0")
    assert not spec.enabled()
    assert kernels.active_impl("int8_matmul") == "fallback"


def test_legacy_interpret_aliases_honored(monkeypatch):
    spec = kernels.get("attention")
    monkeypatch.delenv(spec.interpret_env, raising=False)
    monkeypatch.delenv("DL4J_TPU_FUSED_ATTN_INTERPRET", raising=False)
    assert not spec.interpret_opted_in()
    assert kernels.active_impl("attention") == "fallback"   # cpu, no opt-in
    monkeypatch.setenv("DL4J_TPU_FUSED_ATTN_INTERPRET", "1")
    assert spec.interpret_opted_in()
    assert kernels.active_impl("attention") == "interpret"


def test_backend_admits_rule(monkeypatch):
    monkeypatch.delenv("DL4J_TPU_KERNEL_LSTM_INTERPRET", raising=False)
    monkeypatch.delenv("DL4J_TPU_FUSED_LSTM_INTERPRET", raising=False)
    aliases = ("DL4J_TPU_FUSED_LSTM_INTERPRET",)
    assert envutil.backend_admits("lstm", "tpu", aliases)
    assert not envutil.backend_admits("lstm", "cpu", aliases)
    assert not envutil.backend_admits("lstm", "gpu", aliases)
    monkeypatch.setenv("DL4J_TPU_FUSED_LSTM_INTERPRET", "1")
    assert envutil.backend_admits("lstm", "cpu", aliases)
    assert not envutil.backend_admits("lstm", "gpu", aliases)


# ---------------------------------------------------------------- autotune
@pytest.fixture
def tuned_cache(tmp_path, monkeypatch):
    path = str(tmp_path / "autotune.json")
    monkeypatch.setenv("DL4J_TPU_AUTOTUNE_CACHE", path)
    return path


def test_autotune_measures_and_changes_default(tuned_cache):
    times = {(512, 1024): 3.0e-3, (256, 512): 1.0e-3, (128, 128): 2.0e-3}
    calls = []

    def measure(cand):
        calls.append(cand)
        return times[tuple(cand)]

    rec = autotune.decide("attention", "T9999", list(times), measure,
                          default=(512, 1024))
    assert rec["choice"] == [256, 512]
    assert rec["changed_default"] is True
    assert "argmin" in rec["why"]
    assert len(calls) == 3
    # persisted: a fresh load sees the decision
    with open(tuned_cache) as f:
        data = json.load(f)
    assert data["autotune_cache"] == 1
    key = autotune.AutotuneCache.key("attention", "T9999",
                                     autotune._backend())
    assert data["decisions"][key]["choice"] == [256, 512]


def test_autotune_replays_without_remeasuring(tuned_cache):
    def measure(cand):
        return 1.0e-3

    autotune.decide("attention", "T777", [(512, 1024)], measure,
                    default=(512, 1024))

    def boom(cand):
        raise AssertionError("replay must not re-measure")

    rec = autotune.decide("attention", "T777", [(512, 1024)], boom,
                          default=(512, 1024))
    assert rec["choice"] == [512, 1024]
    assert rec["replays"] == 1
    # the trace-time replay counts in memory and never rewrites the file
    # (it lives outside the checkout; tracing a kernel must not write it)
    before = os.stat(tuned_cache).st_mtime_ns
    assert autotune.cached_decision("attention", "T777") == [512, 1024]
    assert rec["replays"] == 2
    assert os.stat(tuned_cache).st_mtime_ns == before
    with open(tuned_cache) as f:
        data = json.load(f)
    key = autotune.AutotuneCache.key("attention", "T777",
                                     autotune._backend())
    assert data["decisions"][key]["replays"] == 1
    assert not autotune.get_cache().loaded_from_file    # built in-process
    assert autotune.AutotuneCache(tuned_cache).loaded_from_file


def test_autotune_defaults_stand_without_measurement(tuned_cache):
    """Off-TPU there is nothing trustworthy to measure — the harness must
    RECORD that defaults stand (auditable), not silently skip."""
    rec = autotune.decide("attention", "T555", [(512, 1024), (256, 256)],
                          None, default=(512, 1024))
    assert rec["choice"] == [512, 1024]
    assert rec["changed_default"] is False
    assert "defaults stand" in rec["why"]
    assert autotune.decisions_for("attention")


def test_autotune_corrupt_cache_is_empty(tuned_cache):
    with open(tuned_cache, "w") as f:
        f.write("{not json")
    assert autotune.cached_decision("attention", "T1024") is None
    rec = autotune.decide("attention", "T1024", [(512, 1024)], None,
                          default=(512, 1024))
    assert rec["choice"] == [512, 1024]


def test_attention_blocks_resolution_order(tuned_cache, monkeypatch):
    """env override -> cached autotune decision -> hand-tuned defaults."""
    from deeplearning4j_tpu.ops.pallas_attention import _blocks
    monkeypatch.delenv("DL4J_TPU_ATTN_BQ", raising=False)
    monkeypatch.delenv("DL4J_TPU_ATTN_BK", raising=False)
    # empty cache: the v5e-sweep defaults
    assert _blocks(1024) == (512, 1024)
    # a cached decision for this (T, backend) takes over
    autotune.get_cache().store(
        "attention", "T1024", autotune._backend(),
        {"choice": [256, 512], "default": [512, 1024],
         "changed_default": True, "replays": 0, "measured_ms": {},
         "why": "test"})
    assert _blocks(1024) == (256, 512)
    # a non-dividing cached choice is ignored, not an error
    autotune.get_cache().store(
        "attention", "T384", autotune._backend(),
        {"choice": [256, 512], "default": [512, 1024],
         "changed_default": True, "replays": 0, "measured_ms": {},
         "why": "test"})
    assert _blocks(384) == (128, 128)
    # explicit env override wins over the cache
    monkeypatch.setenv("DL4J_TPU_ATTN_BQ", "128")
    assert _blocks(1024) == (128, 1024)


# ------------------------------------------------- snapshot + perf gauges
def test_kernels_snapshot_shape(tuned_cache):
    snap = kernels.kernels_snapshot()
    for name in BUILTINS:
        row = snap[name]
        assert row["impl"] in ("fused", "interpret", "fallback")
        assert row["has_parity_pin"] is True
        assert row["kill_env"] == envutil.kill_env_name(name)
        assert row["interpret_env"] == envutil.interpret_env_name(name)
    assert snap["attention"]["kill_aliases"] == ["DL4J_TPU_FUSED_ATTENTION"]
    assert snap["attention"]["default_choice"] == [512, 1024]
    # an autotune decision shows up on the row
    autotune.decide("int8_matmul", "64x256x256", [(32, 128)], None,
                    default=(32, 128))
    snap = kernels.kernels_snapshot()
    assert snap["int8_matmul"]["autotune"]


def test_perf_snapshot_carries_kernels():
    from deeplearning4j_tpu.telemetry.perf import perf_snapshot
    out = perf_snapshot()
    assert "kernels" in out
    assert set(BUILTINS) <= set(out["kernels"])


def test_record_kernel_timing_publishes_roofline_gauges():
    from deeplearning4j_tpu import telemetry
    from deeplearning4j_tpu.telemetry import MetricsRegistry
    reg = MetricsRegistry(enabled=True)
    prev = telemetry.set_registry(reg)
    try:
        # run far above the bound: the below_roofline flag must trip
        row = kernels.record_kernel_timing("int8_matmul", "64x256x256",
                                           measured_s=10.0)
        assert row is not None
        assert row["vs_roofline"] > 2.0
        base = "perf.kernels.int8_matmul"
        assert reg.gauge(f"{base}.below_roofline").value == 1.0
        assert reg.gauge(f"{base}.measured_ms").value == \
            pytest.approx(10.0 * 1e3)
        assert reg.gauge(f"{base}.roofline_ms").value > 0
    finally:
        telemetry.set_registry(prev)
    assert kernels.record_kernel_timing("int8_matmul", "bogus", 1.0) is None
    assert kernels.record_kernel_timing("lstm", "4x8x128", 0.0) is None
