"""The readers of the serving loop's own account (ISSUE 42), on hand-made
observations against numbers worked by hand; the parent's events, which
carry none of the new attributes, read as nothing; and the CPU rehearsal
cells, which have to read every new metric of the cell they stand for."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
for _p in (os.path.join(BENCH, "tests"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

# the hand-made observations of PR 24's reader tests: a 10 s window on
# ``time.perf_counter`` (T0, T1), events stamped in wall-clock microseconds
from test_program_readers import (T0, T1, _us, instant, obs_of,  # noqa: E402
                                  read, span)

from benchmarks.lib import pass_events  # noqa: E402

PASS = "generation.decode_step"
PAIRS = ["programs.decode_read_wait_pct", "sched.pass_offcpu_pct",
         "sched.loop_preemptions_per_s", "programs.longest_pass_ms",
         "sched.stalls_in_window", "telemetry.window_events_lost_pct"]
LAT_ONLY = ["sched.ttft_inside_p50_ms", "sched.tpot_inside_p50_ms",
            "sched.stream_handoff_p50_ms"]
KIND = {"lat": "open_loop", "tput": "closed_loop"}
NEW = [f"{n}.{k}" for n in PAIRS for k in KIND] + \
    [n + ".lat" for n in LAT_ONLY]


def a_pass(start, wall_ms, step, **args):
    return span(PASS, start, wall_ms / 1e3, step=step, **args)


# three passes inside the window and one that ended before it opened:
# walls of 2, 4 and 14 ms; the second and the fourth sampled the loop
# thread's usage
PASSES = [
    a_pass(99.0, 500.0, 1, read_wait_ms=499.0, loop_cpu_ms=100.0, nivcsw=3),
    a_pass(101.0, 2.0, 2, read_wait_ms=0.5, loop_cpu_ms=1100.0, nivcsw=4),
    a_pass(102.0, 4.0, 3, read_wait_ms=3.0),
    a_pass(103.0, 14.0, 4, read_wait_ms=1.5, loop_cpu_ms=2312.0, nivcsw=9),
]
# between the ends of the two sampled passes, 101.002 .. 103.014: 1.212 s
# of CPU, an idle wait of 0.5 s, a read of 0.1 s and 0.05 s of a read
# that began before: 2.012 - 1.212 - 0.65 = 0.15 s unaccounted
WAITS = [span("generation.idle_wait", 101.5, 0.5, cat="phase"),
         span("generation.readback", 102.5, 0.1, program="prefill"),
         span("generation.readback", 100.952, 0.1, program="decode"),
         span("generation.readback", 104.0, 1.0, program="decode")]


@pytest.mark.parametrize("k", sorted(KIND))
def test_the_pass_readers_against_numbers_worked_by_hand(k):
    events = PASSES + WAITS + [instant("generation.stall", 103.014, step=4),
                               instant("generation.stall", 99.5, step=1)]
    obs = obs_of(KIND[k], events)
    # read waits 0.5 + 3 + 1.5 of walls 2 + 4 + 14: the pass that ended
    # before the window is clipped away
    assert read(f"programs.decode_read_wait_pct.{k}", obs) == \
        pytest.approx(100 * 5.0 / 20.0)
    assert read(f"sched.pass_offcpu_pct.{k}", obs) == \
        pytest.approx(100 * 0.15 / 2.012, rel=1e-3)
    assert read(f"sched.loop_preemptions_per_s.{k}", obs) == \
        pytest.approx((9 - 4) / 10.0)
    assert read(f"programs.longest_pass_ms.{k}", obs) == pytest.approx(14.0)
    assert read(f"sched.stalls_in_window.{k}", obs) == 1.0
    assert read(f"telemetry.window_events_lost_pct.{k}", obs) == 0.0
    # one sampled pass in the window: no interval to account for
    one = obs_of(KIND[k], PASSES[:3] + WAITS)
    assert read(f"sched.pass_offcpu_pct.{k}", one) is None
    assert read(f"sched.loop_preemptions_per_s.{k}", one) is None
    # a cell of the other kind is not this reader's
    other = obs_of(KIND["tput" if k == "lat" else "lat"], events)
    for name in PAIRS:
        assert read(f"{name}.{k}", other) is None


def test_a_sound_window_reads_no_stall():
    assert read("sched.stalls_in_window.lat",
                obs_of("open_loop", PASSES)) == 0.0


def a_request(rid, sent, ttft_ms, tokens, gap_ms, reason="length"):
    first = sent + ttft_ms / 1e3
    last = first + gap_ms * (tokens - 1) / 1e3
    return span("generation.request", sent, last - sent + 1e-4,
                cat="request", request=rid, reason=reason, tokens=tokens,
                ttft_ms=ttft_ms, first_token_us=_us(first),
                last_token_us=_us(last))


class Stream:
    def __init__(self, request_id):
        self.request_id = request_id


def test_the_request_readers_against_numbers_worked_by_hand():
    # eleven requests sent inside the window, a second apart from 100.2
    # on: time to first token 10, 11, ... 20 ms; gaps of 2.0, 2.1, ... ms;
    # the client stamps each first token 0.1 ms x its number later. The
    # last, sent at 110.2, lies outside; one sent before the window and
    # one cancelled are out of the gap's sample
    recs = [a_request(i, 100.2 + i, 10.0 + i, 5, 2.0 + 0.1 * i)
            for i in range(11)]
    recs.append(a_request(50, 99.0, 99.0, 5, 9.0))
    recs.append(a_request(51, 104.5, 15.0, 5, 9.0, reason="cancelled"))
    clients = [{"stream": Stream(i), "stamps": [
        100.2 + i + (10.0 + i) / 1e3 + 1e-4 * i]} for i in range(11)]
    clients.append({"stream": Stream(77), "stamps": [105.0]})   # no record
    clients.append({"stream": None, "stamps": []})              # refused
    obs = dict(obs_of("open_loop", recs), summary={"window": clients})
    # submitted inside the window: numbers 0..9 and the cancelled one
    assert read("sched.ttft_inside_p50_ms.lat", obs) == pytest.approx(15.0)
    # finished by count inside it: numbers 0..9, gaps 2.0 .. 2.9
    assert read("sched.tpot_inside_p50_ms.lat", obs) == \
        pytest.approx(2.45, abs=2e-3)
    # hand-overs of 0, 0.1, ... 1.0 ms over all eleven paired clients
    assert read("sched.stream_handoff_p50_ms.lat", obs) == \
        pytest.approx(0.5, abs=2e-3)
    for name in LAT_ONLY:
        assert read(name + ".lat", dict(obs, kind="closed_loop")) is None
    # under ten in the sample: not a median
    few = dict(obs_of("open_loop", recs[:9]),
               summary={"window": clients[:9]})
    for name in LAT_ONLY:
        assert read(name + ".lat", few) is None


@pytest.mark.parametrize("name", NEW)
def test_the_parents_events_read_as_nothing(name):
    """Spans, admissions and client records as the commit before leaves
    them: no ``step``, no record, a stream without ``request_id``."""
    k = name.rsplit(".", 1)[1]
    events = [span(PASS, 101.0 + i, 0.002, slots=2, live_tokens=40,
                   overlapped=1) for i in range(12)]
    events += [instant("generation.admit", 101.5, slot=0, queue_ms=1.0)]
    clients = [{"stream": object(), "stamps": [101.0 + i]}
               for i in range(12)]
    obs = dict(obs_of(KIND[k], events), summary={"window": clients})
    got = read(name, obs)
    if name.startswith("telemetry."):
        assert got == 0.0       # the ring is the parent's too
    else:
        assert got is None
    assert read(name, {"kind": KIND[k], "window_perf": (T0, T1),
                       "epoch_ns": 0}) is None


@pytest.mark.parametrize("handed,first_at,lost", [
    (7, 104.0, 0.0),          # under the capacity: whatever the first
    (8, 99.0, 0.0),           # at it, from before the window: all kept
    (8, 102.5, 25.0),         # at it: the window's first quarter is gone
    (9, 107.0, 70.0),         # past it (a reader handed more than a ring)
    (8, 111.0, 100.0)])       # nothing of the window was kept
def test_events_lost_at_under_and_past_the_capacity(handed, first_at, lost):
    events = [a_pass(first_at + 0.01 * i, 2.0, i) for i in range(handed)]
    obs = obs_of("closed_loop", events)
    assert pass_events.events_lost_pct(obs, capacity=8) == \
        pytest.approx(lost)
    # the reader itself asks the process's registry for its capacity
    from deeplearning4j_tpu.telemetry import get_registry
    assert handed < get_registry().trace_capacity
    assert read("telemetry.window_events_lost_pct.tput", obs) == 0.0


def test_every_new_metric_is_appended_with_its_reader_and_its_cells():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    # one stretch of the list, as PR 42 appended it (later PRs append behind)
    names = [m["name"] for m in bench["per_layer"]]
    first = min(names.index(n) for n in NEW)
    rows = bench["per_layer"][first:first + len(NEW)]
    assert sorted(m["name"] for m in rows) == sorted(NEW)
    tput = [w["name"] for w in bench["workloads"]
            if w["name"] in {"gpt2m-serve-longprompt",
                             "lfm2moe-serve-extract",
                             "kanana2-serve-longdoc",
                             "laguna-serve-codebase",
                             "minicpm-sala-serve-longctx"}]
    for m in rows:
        assert os.path.isfile(
            os.path.join(BENCH, "layer_metrics", m["name"] + ".py"))
        if m["name"].endswith(".lat"):
            assert (m["moves"], m["workloads"]) == (
                "tpot_p50_ms", ["gpt2m-serve-chat"])
        else:
            assert (m["moves"], m["workloads"]) == (
                "serve_tokens_per_s", tput)


# ISSUE 43: how often the admission pass went ahead of an earlier arrival
JUMPED = "sched.admit_jumped_pct.tput"


def an_admit(at, **args):
    return instant("generation.admit", at, request=1, queue_ms=1.0, **args)


@pytest.mark.parametrize("kind,jumped,want", [
    # four admissions inside the window, two ahead of somebody; the one
    # before the window opened is not counted
    ("closed_loop", [0, 3, 0, 1], 50.0),
    ("closed_loop", [0, 0, 0], 0.0),           # arrival order throughout
    ("closed_loop", [2], 100.0),
    ("closed_loop", [], None),                 # nothing admitted
    ("open_loop", [0, 1], None)])              # not this reader's cell
def test_the_share_of_admissions_that_jumped(kind, jumped, want):
    events = [an_admit(99.0, jumped=5)] + \
        [an_admit(101.0 + i, jumped=j) for i, j in enumerate(jumped)]
    got = read(JUMPED, obs_of(kind, events))
    assert got == (want if want is None else pytest.approx(want))


def test_the_parents_admissions_carry_no_jumped_and_read_as_nothing():
    events = [an_admit(101.0 + i) for i in range(4)]
    assert read(JUMPED, obs_of("closed_loop", events)) is None
    assert read(JUMPED, {"kind": "closed_loop", "window_perf": (T0, T1),
                         "epoch_ns": 0}) is None


def test_the_jumped_share_is_appended_behind_pr_42s_with_its_reader():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    names = [m["name"] for m in bench["per_layer"]]
    assert names.index(JUMPED) > max(names.index(n) for n in NEW)
    assert bench["per_layer"][names.index(JUMPED)] == {
        "name": JUMPED, "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "scheduler",
        "moves": "serve_tokens_per_s",
        "workloads": ["gpt2m-serve-longprompt", "lfm2moe-serve-extract",
                      "kanana2-serve-longdoc", "laguna-serve-codebase",
                      "minicpm-sala-serve-longctx"]}
    assert os.path.isfile(os.path.join(BENCH, "layer_metrics", JUMPED + ".py"))


@pytest.mark.parametrize("cell,stands_for", [
    ("toy-serve-chat", "gpt2m-serve-chat"),
    ("toy-serve-longprompt", "gpt2m-serve-longprompt")])
def test_the_rehearsal_reads_every_new_metric_of_its_cell(cell, stands_for):
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    want = {m["name"] for m in bench["per_layer"]
            if m["name"] in NEW + [JUMPED] and stands_for in m["workloads"]}
    assert len(want) == (9 if cell == "toy-serve-chat" else 7)
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
         "--seed", str(2**31 + 42), "--seconds", "2", "--trace", "1"],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert want <= set(line["rehearsal_layer_metrics_read"])
