"""Unit tests of the benchmark's own library. Run here on the CPU:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q -p no:cacheprovider
"""
from __future__ import annotations

import importlib
import json
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

from benchmarks.lib import peaks, stats, traffic, xplane  # noqa: E402
from benchmarks.lib.correct import Checks, worst_leaf_gap  # noqa: E402

SEEDS = [1, 2, 3, 5, 8, 13, 21, 34, 55, 2**31 + 11, 2**31 + 12, 3000000019]


def _traffic(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


# ------------------------------------------------------------- percentile
@pytest.mark.parametrize("q,want", [(0, 1.0), (50, 2.5), (90, 3.7), (100, 4.0)])
def test_percentile_interpolates_between_order_statistics(q, want):
    assert stats.percentile([4.0, 1.0, 3.0, 2.0], q) == pytest.approx(want)


def test_percentile_matches_numpy_linear():
    xs = np.random.default_rng(0).lognormal(size=137).tolist()
    for q in (1, 50, 90, 95, 99):
        assert stats.percentile(xs, q) == pytest.approx(np.percentile(xs, q))


def test_percentile_refuses_an_empty_sample():
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_spread_is_quartile_distance_over_median():
    assert stats.iqr_spread([1, 2, 3, 4, 5, 6]) == pytest.approx(
        (5.25 - 1.75) / 3.5)


# ------------------------------------------------------ stratified traffic
def test_serve_chat_offers_the_same_work_for_twelve_seeds():
    from benchmarks.kinds import open_loop
    tr = _traffic("serve-chat")
    offers, orders = [], []
    for seed in SEEDS:
        reqs = open_loop.schedule(tr, 51.0, np.random.default_rng(seed), 50257)
        win = [r for r in reqs if r["span"] == "window"]
        pre = [r for r in reqs if r["span"] == "preroll"]
        offers.append((traffic.offered(win), traffic.offered(pre)))
        orders.append([len(r["prompt"]) for r in win])
        assert all(0.0 <= r["due"] < 51.0 for r in win)
        assert all(-tr["preroll_s"] <= r["due"] < 0.0 for r in pre)
        assert all(len(r["prompt"]) + r["max_tokens"] <= 1024 for r in reqs)
    assert all(o == offers[0] for o in offers)      # multiset, count, tokens
    assert len({tuple(o) for o in orders}) == len(SEEDS)   # order differs
    assert offers[0][0]["count"] == round(tr["rate_per_s"] * 51.0)


def test_serve_longprompt_offers_the_same_work_for_twelve_seeds():
    """Every block holds the same multiset, whatever the seed: the blocks
    of three seeds in full, the head block of all twelve."""
    from benchmarks.kinds import closed_loop
    tr = _traffic("serve-longprompt")
    n = tr["block"]
    one_block = traffic.offered([{"prompt": [0] * p, "max_tokens": o} for p, o
                                 in traffic.stratified_pairs(tr["lengths"], n)])
    for seed in SEEDS[::5]:
        reqs = closed_loop.request_list(tr, np.random.default_rng(seed), 50257)
        assert len(reqs) == n * tr["blocks"]
        assert all(traffic.offered(reqs[i:i + n]) == one_block
                   for i in range(0, len(reqs), n))
    heads = []
    for seed in SEEDS:
        head = closed_loop.request_list(dict(tr, blocks=1),
                                        np.random.default_rng(seed), 50257)
        assert traffic.offered(head) == one_block
        heads.append(tuple(len(r["prompt"]) for r in head))
    assert len(set(heads)) == len(SEEDS)


@pytest.mark.parametrize("seed", [7, 2**31 + 13, 3000000019])
def test_a_longer_list_starts_with_the_list_it_replaces(seed):
    """``blocks`` went from 64 to 512 (PR 26): for a given seed the first
    4,096 requests are the ones the cell sent before, token for token."""
    from benchmarks.kinds import closed_loop
    tr = _traffic("serve-longprompt")
    was = closed_loop.request_list(dict(tr, blocks=64),
                                   np.random.default_rng(seed), 50257)
    now = closed_loop.request_list(tr, np.random.default_rng(seed), 50257)
    assert len(was) == 4096 < len(now)
    for a, b in zip(was, now):
        assert a["max_tokens"] == b["max_tokens"]
        assert np.array_equal(a["prompt"], b["prompt"])


def _closed_loop_cells():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    return [pytest.param(w, bench["run_seconds"], id=w["name"])
            for w in bench["workloads"]
            if _traffic(w["traffic"])["kind"] == "closed_loop"]


@pytest.mark.parametrize("w,run_seconds", _closed_loop_cells())
def test_a_closed_loop_list_outlasts_what_the_chip_can_prefill(w, run_seconds):
    """A list that runs out fails ``request_list_outlasted_the_window`` and
    may not be cycled (the prefix cache would serve a repeated prompt), so it
    holds more than the chip could take at 100% of its bf16 peak through
    pre-roll and window: prompts only, causal attention at the half it
    needs, without the head at positions whose logits nobody reads."""
    tr = _traffic(w["traffic"])
    cfg = json.load(open(os.path.join(BENCH, "configs", w["config"],
                                      "config.json")))
    flops = importlib.import_module(
        f"benchmarks.families.{cfg['family']}.flops")
    head = flops.head_flops_per_token(cfg)
    pairs = traffic.stratified_pairs(tr["lengths"], tr["block"])
    per_request = sum(
        p * (flops.forward_flops_per_token(cfg, (p + 1) / 2.0) - head)
        for p, _ in pairs) / len(pairs)
    peak = w["chips"] * max(p["bf16_flops"] for p in peaks.PEAKS.values())
    most = tr["callers"] + (tr["preroll_s"] + run_seconds) * \
        peak / per_request
    assert tr["block"] * tr["blocks"] >= most


def test_mid_quantiles_follow_the_distribution():
    d = {"kind": "lognormal", "median": 192, "sigma": 0.8, "lo": 16, "hi": 768}
    xs = traffic.mid_quantiles(d, 101)
    assert xs == sorted(xs) and xs[50] == 192
    assert min(xs) >= 16 and max(xs) <= 768
    u = traffic.mid_quantiles({"kind": "uniform", "lo": 1, "hi": 8}, 64)
    assert [u.count(v) for v in range(1, 9)] == [8] * 8


def test_conditioned_arrivals_keep_count_and_span():
    for seed in SEEDS:
        ts = traffic.conditioned_arrivals(np.random.default_rng(seed), 100, -5.0, 40.0)
        assert len(ts) == 100 and ts == sorted(ts)
        assert -5.0 <= ts[0] and ts[-1] < 40.0
    a = traffic.conditioned_arrivals(np.random.default_rng(1), 100, 0.0, 40.0)
    b = traffic.conditioned_arrivals(np.random.default_rng(2), 100, 0.0, 40.0)
    assert a != b


# -------------------------------------------------------- trace reduction
def test_union_and_op_family():
    assert xplane.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert xplane.op_family("%fusion.123") == "fusion"
    assert xplane.op_family("copy.4") == "copy"
    assert xplane.op_family("slice_bitcast_fusion") == "slice_bitcast_fusion"


def test_reduce_on_a_synthetic_trace():
    trace = {"devices": {"/device:TPU:0": [("fusion.1", 0.0, 2e9),
                                           ("copy.2", 1e9, 2e9),
                                           ("fusion.3", 6e9, 1e9)]},
             "sync_ns": 0.0}
    out = xplane.reduce(trace, (0.0, 10e9),
                        [("fit", 0.0, 10e9), ("fit/step", 3e9, 6e9)])
    assert out["busy_s"] == pytest.approx(4.0)
    assert out["window_s"] == pytest.approx(10.0)
    assert out["device_ops"][0] == ["fusion", pytest.approx(3.0)]
    gaps = dict(out["idle_gaps"])
    assert gaps["fit/step"] == pytest.approx(3.0)      # the gap 3..6
    assert gaps["fit"] == pytest.approx(3.0)           # the gap 7..10
    assert "outside-spans" not in gaps


def test_idle_gaps_are_split_over_the_spans_they_overlap():
    trace = {"devices": {"/device:TPU:0": [("copy.1", 0.0, 1e9),
                                           ("copy.2", 5e9, 1e9)]},
             "sync_ns": 0.0}
    out = xplane.reduce(trace, (0.0, 6e9), [("step", 0.0, 2e9), ("step", 4e9, 6e9)])
    gaps = dict(out["idle_gaps"])                      # the gap 1..5
    assert gaps["step"] == pytest.approx(2.0)
    assert gaps["outside-spans"] == pytest.approx(2.0)


def test_device_clock_is_lined_up_with_blocking_spans():
    # device work recorded 1.2 ms early against the spans that block on it
    spans = [("decode", k * 50e6, k * 50e6 + 48e6) for k in range(20)]
    events = [("copy.%d" % k, k * 50e6 + 0.5e6 - 1.2e6, 46e6) for k in range(20)]
    trace = {"devices": {"/device:TPU:0": events}, "sync_ns": 0.0}
    out = xplane.reduce(trace, (0.0, 1e9), spans, blocking=True)
    assert 0.7 <= out["device_clock_shift_ms"][0] <= 1.7
    gaps = dict(out["idle_gaps"])
    assert gaps["outside-spans"] == pytest.approx(20 * 2e6 / 1e9, rel=0.05)


def test_reduce_the_recorded_trace():
    """A trace recorded on a TPU v5e (tests/record_tiny_trace.py): three
    2048^3 bf16 matmuls with 10 ms pauses, under a bench.sync annotation."""
    path = os.path.join(HERE, "data", "tiny.xplane.pb")
    trace = xplane.load(path)
    assert trace["sync_ns"] is not None
    assert len(trace["devices"]) == 1
    (events,) = trace["devices"].values()
    t0 = min(s for _, s, _ in events)
    t1 = max(s + d for _, s, d in events)
    out = xplane.reduce(trace, (t0, t1))
    assert 0.0 < out["busy_s"] < out["window_s"]
    # three pauses of 10 ms on the host leave the device idle most of the time
    assert out["busy_s"] / out["window_s"] < 0.5
    assert out["device_ops"] and out["idle_gaps"][0][0] == "outside-spans"


# ------------------------------------------------------------ peaks, checks
def test_unknown_device_kind_has_no_peak():
    assert peaks.peak("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        peaks.peak("cpu")
    assert peaks.mfu_pct(1e12, 98.5, "TPU v5 lite") == pytest.approx(50.0)


def test_worst_leaf_gap_uses_the_median_leaf_for_small_leaves():
    ref = {"a": 1.0, "b": 2.0, "c": 1e-6}
    prog = {"a": 1.1, "b": 2.0, "c": 0.0}
    assert worst_leaf_gap(prog, ref) == pytest.approx(0.1)
    with pytest.raises(ValueError):
        worst_leaf_gap({"a": 1.0}, ref)


def test_checks_need_every_row_and_at_least_one():
    c = Checks()
    assert not c.correct
    c.at_most("x", 1.0, 2.0)
    assert c.correct
    c.at_most("nan", float("nan"), 2.0)
    assert not c.correct


# ----------------------------------------------------------- the harness
def _run(*args, env=None):
    e = dict(os.environ, JAX_PLATFORMS="cpu", **(env or {}))
    return subprocess.run([sys.executable, os.path.join(BENCH, "run.py"), *args],
                          cwd=ROOT, env=e, capture_output=True, text=True,
                          timeout=900)


def test_a_real_cell_refuses_a_cpu_nobody_asked_for():
    p = _run("--workload", "gpt2m-train-1k", "--seed", "1", "--seconds", "1",
             "--trace", "0")
    assert p.returncode != 0
    assert "platform 'cpu'" in p.stderr
    assert not p.stdout.strip().endswith("}")          # no result line


@pytest.mark.parametrize("cell", ["toy-train", "toy-serve-chat",
                                  "toy-serve-longprompt"])
def test_rehearsal_last_line_has_the_keys_and_counts_only(cell):
    p = _run("--workload", cell, "--seed", str(2**31 + 5), "--seconds", "2",
             "--trace", "1")
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    device_names = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]}
    assert not device_names & set(line["metrics"])     # counts only
    assert all(k.startswith("rehearsal.") for k in line["metrics"])
    log = p.stdout[:p.stdout.rindex('{"correct"')]
    assert not any(name in log for name in device_names)   # nor in the log


def test_benchmark_json_names_files_that_exist():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for c in bench["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
    for w in bench["workloads"]:
        assert os.path.isfile(os.path.join(BENCH, "traffic", w["traffic"] + ".json"))
        assert os.path.isfile(os.path.join(BENCH, "limits", w["name"] + ".json"))
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics", m["name"] + ".py"))
        assert m["moves"] in e2e
    # and the converse: a retired metric does not leave its reader behind
    assert {f[:-3] for f in os.listdir(os.path.join(BENCH, "layer_metrics"))
            if f.endswith(".py")} == {m["name"] for m in bench["per_layer"]}
