"""The readers of what the program says about itself (ISSUE 24), on
hand-made observations; the kernel costs against numbers worked by hand;
and the rehearsal cells, which have to read every new serving metric.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q -p no:cacheprovider
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

from benchmarks import run as bench_run  # noqa: E402
from benchmarks.lib import kernel_costs, program_events  # noqa: E402

EPOCH_NS = 1_700_000_000 * 10**9        # wall clock at perf_counter 0
T0, T1 = 100.0, 110.0                   # a 10 s window on perf_counter


def _us(t: float) -> int:
    """Wall-clock microseconds, as the program stamps them, of clock t."""
    return (int(t * 1e9) + EPOCH_NS) // 1000


def span(name, start, dur, cat="span", **args):
    return {"name": name, "ph": "X", "cat": cat, "ts": _us(start),
            "dur": int(dur * 1e6), "args": args}


def instant(name, at, **args):
    return {"name": name, "ph": "i", "cat": "event", "ts": _us(at),
            "args": args}


def obs_of(kind, events):
    return {"kind": kind, "events": events, "window_perf": (T0, T1),
            "epoch_ns": EPOCH_NS, "trace": None}


def read(name, obs):
    return bench_run.load_reader(name).read(obs)


SERVING = [("programs.dispatch_p50_ms.lat", "open_loop"),
           ("sched.host_serial_pct.lat", "open_loop"),
           ("sched.admit_wait_p50_ms.lat", "open_loop"),
           ("kvcache.step_gathered_over_live.lat", "open_loop"),
           ("sched.host_serial_pct.tput", "closed_loop"),
           ("programs.prefill_pad_pct.tput", "closed_loop"),
           ("kvcache.step_gathered_over_live.tput", "closed_loop")]
TRAIN = ["kernels.flash_fwd_roofline_pct.train",
         "kernels.flash_bwd_roofline_pct.train"]


# ---------------------------------------------- nothing to read gives None
@pytest.mark.parametrize("name,kind", SERVING)
def test_a_program_that_records_nothing_new_gives_none(name, kind):
    """The commit before this one has the two blocking spans and nothing
    else: every new reader finds nothing, returns None, raises nothing."""
    old = [span("generation.decode_step", T0 + i, 0.05, model="lm", slots=2)
           for i in range(12)] + \
          [span("generation.prefill", T0 + i + 0.5, 0.1, model="lm", batch=1,
                rung=64) for i in range(12)]
    assert read(name, obs_of(kind, old)) is None
    assert read(name, obs_of(kind, [])) is None
    assert read(name, {"kind": kind}) is None
    other = "closed_loop" if kind == "open_loop" else "open_loop"
    assert read(name, obs_of(other, old)) is None


@pytest.mark.parametrize("name", TRAIN)
def test_kernel_rooflines_need_the_trace_and_the_names(name):
    base = {"kind": "fit_cycle", "rate": 43.8, "device": {"kind": "TPU v5 lite"},
            "config": {"n_head": 16, "n_embd": 1024, "n_layer": 24},
            "traffic": {"batch": 8, "seq_len": 1024}}
    assert read(name, dict(base, trace=None)) is None
    unnamed = {"window_s": 4.0, "by_op_s": {"jvp__": 0.356,
                                            "transpose_jvp___": 0.750}}
    assert read(name, dict(base, trace=unnamed)) is None
    half = {"window_s": 4.0, "by_op_s": {"flash_attention_bwd_dq": 0.3}}
    assert read(name, dict(base, trace=half)) is None
    assert read(name, {"kind": "open_loop"}) is None


# ------------------------------------------------------ hand-made windows
def test_dispatch_median_takes_the_decode_program_only():
    ev = [span("generation.dispatch", T0 + 0.5 * i, 0.001 * (i + 1),
               program="decode") for i in range(11)]          # 1..11 ms
    ev += [span("generation.dispatch", T0 + 0.5 * i + 0.1, 0.2,
                program="prefill") for i in range(11)]
    assert read("programs.dispatch_p50_ms.lat",
                obs_of("open_loop", ev)) == pytest.approx(6.0)
    assert read("programs.dispatch_p50_ms.lat",
                obs_of("open_loop", ev[:9])) is None      # under ten steps


@pytest.mark.parametrize("name,kind", [
    ("sched.host_serial_pct.lat", "open_loop"),
    ("sched.host_serial_pct.tput", "closed_loop")])
def test_host_serial_share_counts_admit_and_emit_clipped_to_the_window(
        name, kind):
    ev = [span("generation.admit_batch", T0 + 1, 0.10, cat="phase"),
          span("generation.emit", T0 + 2, 0.05, cat="phase"),
          span("generation.emit", T0 - 0.02, 0.05, cat="phase"),  # 0.03 in
          span("generation.emit", T1 + 1, 0.05, cat="phase"),     # outside
          span("generation.idle_wait", T0 + 3, 2.0, cat="phase"),  # not host
          span("generation.decode_step", T0 + 5, 1.0)]
    assert read(name, obs_of(kind, ev)) == pytest.approx(
        100.0 * (0.10 + 0.05 + 0.03) / 10.0)


def test_admit_wait_is_the_median_of_the_windows_admissions():
    ev = [instant("generation.admit", T0 + 0.5 * i, queue_ms=float(i), slot=0,
                  prompt_len=7) for i in range(1, 12)]       # 1..11 ms
    ev += [instant("generation.admit", T0 - 1.0, queue_ms=500.0),  # pre-roll
           instant("generation.finish", T0 + 1.0, queue_ms=900.0)]
    assert read("sched.admit_wait_p50_ms.lat",
                obs_of("open_loop", ev)) == pytest.approx(6.0)


def test_prefill_padding_is_one_minus_tokens_over_padded():
    ev = [span("generation.prefill", T0 + 1, 0.1, rows=2, tokens=1200,
               padded_tokens=2 * 768),
          span("generation.prefill", T0 + 2, 0.1, rows=1, tokens=900,
               padded_tokens=1024),
          span("generation.prefill", T1 + 2, 0.1, rows=4, tokens=1,
               padded_tokens=4096)]                         # outside
    assert read("programs.prefill_pad_pct.tput",
                obs_of("closed_loop", ev)) == pytest.approx(
        100.0 * (1.0 - 2100.0 / 2560.0))


@pytest.mark.parametrize("name,kind", [
    ("kvcache.step_gathered_over_live.lat", "open_loop"),
    ("kvcache.step_gathered_over_live.tput", "closed_loop")])
def test_gathered_over_live_is_the_mean_of_the_steps_ratios(name, kind):
    ev = [span("generation.decode_step", T0 + 0.1 * i, 0.05, slots=2,
               live_tokens=1024 * (1 + i % 2), gathered_tokens=16384)
          for i in range(10)]                     # ratios 16 and 8 in turn
    ev.append(span("generation.decode_step", T0 + 5, 0.05, slots=0,
                   live_tokens=0, gathered_tokens=16384))   # nothing live
    assert read(name, obs_of(kind, ev)) == pytest.approx(12.0)


# ------------------------------------------------------------ kernel costs
def test_flash_costs_against_numbers_worked_by_hand():
    """8 x 1024 tokens, 16 heads of 64, causal, bfloat16. A causal row i
    attends to i + 1 positions, 1025 / 2 on average; each of QK^T and PV is
    2 x 8 x 16 x 1024 x 512.5 x 64 = 8,598,323,200 FLOPs."""
    flops, nbytes = kernel_costs.flash_fwd_cost(8, 16, 1024, 64)
    assert flops == 2 * 8_598_323_200 == 17_196_646_400
    # q, k, v, o: 8 x 16 x 1024 x 64 x 2 bytes = 16,777,216 each; the
    # log-sum-exp one float32 a row: 8 x 16 x 1024 x 4 = 524,288
    assert nbytes == 4 * 16_777_216 + 524_288 == 67_633_152
    flops_b, nbytes_b = kernel_costs.flash_bwd_cost(8, 16, 1024, 64)
    assert flops_b == 4 * 8_598_323_200 == 34_393_292_800
    assert nbytes_b == 7 * 16_777_216 + 2 * 524_288 == 118_489_088
    # without the causal half: twice 1024 / 1025 of it
    full, _ = kernel_costs.flash_fwd_cost(8, 16, 1024, 64, causal=False)
    assert full == 2 * 2 * 8 * 16 * 1024 * 1024 * 64


def test_roofline_shares_of_the_ledgers_train_trace():
    """PR 23's traced train run: 43.81 samples/s, 4 s traced, forward
    0.356 s and backward 0.750 s of device time. 24 layers x 5.476 steps/s
    x 4 s = 525.7 calls of each kernel; compute binds both."""
    obs = {"kind": "fit_cycle", "rate": 43.81,
           "device": {"kind": "TPU v5 lite"},
           "config": {"n_head": 16, "n_embd": 1024, "n_layer": 24},
           "traffic": {"batch": 8, "seq_len": 1024},
           "trace": {"window_s": 4.0, "by_op_s": {
               "flash_attention_fwd": 0.356, "flash_attention_bwd_dq": 0.300,
               "flash_attention_bwd_dkv": 0.450, "fusion": 0.6}}}
    calls = 24 * (43.81 / 8) * 4.0
    fwd = read("kernels.flash_fwd_roofline_pct.train", obs)
    assert fwd == pytest.approx(
        100.0 * calls * 17_196_646_400 / 197e12 / 0.356)
    assert 12.5 < fwd < 13.5
    bwd = read("kernels.flash_bwd_roofline_pct.train", obs)
    assert bwd == pytest.approx(
        100.0 * calls * 34_393_292_800 / 197e12 / 0.750)
    assert 11.5 < bwd < 13.0


# --------------------------------------------------------- the rehearsal
@pytest.mark.parametrize("cell,stands_for", [
    ("toy-serve-chat", "gpt2m-serve-chat"),
    ("toy-serve-longprompt", "gpt2m-serve-longprompt")])
def test_rehearsal_reads_every_new_serving_metric_of_its_cell(cell,
                                                              stands_for):
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    new = {n for n, _ in SERVING}
    want = {m["name"] for m in bench["per_layer"]
            if m["name"] in new and stands_for in m["workloads"]}
    assert len(want) == (4 if cell == "toy-serve-chat" else 3)
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
         "--seed", str(2**31 + 24), "--seconds", "2", "--trace", "1"],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert want <= set(line["rehearsal_layer_metrics_read"])
    log = p.stdout[:p.stdout.rindex('{"correct"')]
    names = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]}
    assert not any(name in log for name in names)   # no device metric's name


def test_every_new_metric_is_appended_with_its_reader():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    names = [m["name"] for m in bench["per_layer"]]
    new = [n for n, _ in SERVING] + TRAIN
    assert sorted(names[-len(new):]) == sorted(new)      # at the end
    for n in new:
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics", n + ".py"))
