"""The fleet as deployed: real replica OS processes behind the front door.

One module-scoped fleet — two supervised replica subprocesses sharing a
persistent compilation cache, an affinity FleetRouter with a live health
poller, and the FleetHTTPServer front door. Pins the subsystem's
acceptance behaviors end to end:

  - readiness gating (ready file + /health 200) and the /health steering
    payload a router steers on;
  - front-door token streams byte-identical to the single-process
    reference (naive_generate);
  - chaos SIGKILL loses ONLY the in-flight stream — closed with
    ``reason: "replica_lost"`` — while the router marks the victim dead,
    dumps a flight-recorder black box, and survivors keep serving;
  - pre-first-token failures replay idempotently on a survivor (exact
    greedy sequence, ``fleet.retry`` trace marker);
  - a replica joining a WARM compilation cache reaches ready with zero
    fresh backend compiles, then drains out with exit code 0.

Every destructive test revives its victim before returning — the suite
must pass in any order (DL4J_TPU_TEST_REVERSE=1).
"""
import json
import os
import time
from types import SimpleNamespace

import pytest

from deeplearning4j_tpu import telemetry
from deeplearning4j_tpu.models.decode import (TransformerDecodeSpec,
                                              naive_generate)
from deeplearning4j_tpu.models.zoo_extra import transformer_lm
from deeplearning4j_tpu.serving.fleet import (FleetCollector, FleetHTTPServer,
                                              FleetRouter, ReplicaProcess)
from deeplearning4j_tpu.serving.fleet.collector import FRONT_DOOR
from deeplearning4j_tpu.telemetry import MetricsRegistry
from deeplearning4j_tpu.telemetry.flightrec import get_flight_recorder
from deeplearning4j_tpu.telemetry.spool import read_spool
from deeplearning4j_tpu.util.httpjson import HTTPClient

# big enough that a 200-token decode takes tens of ms on CPU — the chaos
# test needs the SIGKILL to land while tokens are still being produced,
# and a d16/1-block LM finishes the whole stream inside the kill latency
MODEL_KW = dict(vocab_size=64, d_model=64, n_heads=4, n_blocks=2,
                max_length=256, seed=7, dtype="float32", token_input=True)
GEN_KW = dict(block_len=16, max_seq_len=224, decode_slots=2,
              prefill_batches=[1], num_blocks=32, queue_limit=64)


@pytest.fixture(scope="module")
def fleet(tmp_path_factory):
    work = tmp_path_factory.mktemp("fleet")
    spec = {"model": {"zoo": "transformer_lm", "kwargs": MODEL_KW},
            "model_name": "lm", "generation": GEN_KW}
    # the compile cache is placed from outside, by the env the children
    # inherit (util/compile_cache.py) — the replicas share this one
    env = {"JAX_COMPILATION_CACHE_DIR": str(work / "cache")}
    procs = {rid: ReplicaProcess(spec, rid, workdir=str(work), env=env)
             for rid in ("f0", "f1")}
    router = FleetRouter(policy="affinity", health_period_s=0.1).start()
    front = FleetHTTPServer(router)
    client = HTTPClient(max_per_host=4, timeout=60.0)
    try:
        for rid in ("f0", "f1"):       # parallel spawn, serial readiness
            procs[rid].start()
        for rid in ("f0", "f1"):
            router.add_process(procs[rid], wait_ready=True, timeout=240.0)
        base = f"http://127.0.0.1:{front.start()}"
        yield SimpleNamespace(work=work, spec=spec, env=env, procs=procs,
                              router=router, front=front, base=base,
                              client=client)
    finally:
        client.close()
        front.stop(close_router=True)   # drain-stops every live replica


def _revive(fleet, rid):
    """Restore the 2-replica fixture state after a destructive test."""
    proc = fleet.procs[rid]
    fleet.router.remove_replica(rid)
    if proc.alive:
        proc.kill()
    proc.restart()
    fleet.router.add_process(proc, wait_ready=True, timeout=240.0)


def _net():
    return transformer_lm(**MODEL_KW).init()


def _stream_lines(fleet, payload, on_line=None):
    body = json.dumps(payload).encode()
    lines = []
    with fleet.client.stream("POST", fleet.base + "/generate", body=body,
                             headers={"Content-Type": "application/json"},
                             timeout=120.0) as resp:
        assert resp.status == 200
        for raw in resp:
            if not raw.strip():
                continue
            obj = json.loads(raw)
            lines.append(obj)
            if on_line is not None:
                on_line(obj)
    return lines


def _blocking(fleet, payload, model=None):
    path = "/generate" + (f"/{model}" if model else "")
    return fleet.client.request_json(
        "POST", fleet.base + path, payload={**payload, "stream": False},
        timeout=120.0)


def _wait_state(router, rid, state, timeout=10.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        rows = {r["id"]: r for r in router.replicas()}
        if rows.get(rid, {}).get("state") == state:
            return True
        time.sleep(0.05)
    return False


# -------------------------------------------------------------- readiness
def test_readiness_gate_and_health_steering(fleet):
    for rid, proc in fleet.procs.items():
        info = proc.ready_info
        assert info["port"] > 0 and info["pid"] > 0
        assert info["ready_s"] > 0
        assert info["cache_dir"] == fleet.env["JAX_COMPILATION_CACHE_DIR"]
        assert "fresh_compiles" in info
        # every replica names the device it REALLY runs on
        assert info["platform"] == "cpu" and info["device_kind"]
        # the steering payload the router (and autoscaler) steer on
        status, health = fleet.client.request_json(
            "GET", proc.base_url + "/health", timeout=10.0)
        assert status == 200
        s = health["steering"]
        for key in ("queue_depth", "in_flight", "slot_occupancy",
                    "block_pool_free_frac", "prefix_hit_rate",
                    "prefix_lookups", "block_len"):
            assert key in s, key
        assert s["block_len"] == 16
        assert health["replica"]["id"] == rid
        assert health["replica"]["platform"] == "cpu"
    # front door aggregates
    status, body = fleet.client.request_json(
        "GET", fleet.base + "/health", timeout=10.0)
    assert status == 200 and body["ready"] == 2
    assert body["states"] == {"f0": "ready", "f1": "ready"}
    status, m = fleet.client.request_json(
        "GET", fleet.base + "/metrics", timeout=30.0)
    assert status == 200 and m["policy"] == "affinity"
    assert set(m["replicas"]) == {"f0", "f1"}
    assert set(m["replica_metrics"]) <= {"f0", "f1"}
    assert fleet.router.block_len == 16     # adopted from steering


# ------------------------------------------------------------ correctness
def test_front_door_matches_single_process_reference(fleet):
    net = _net()
    prompt = list(range(2, 18))
    want = naive_generate(net, prompt, 8, pad_to=64,
                          spec=TransformerDecodeSpec(net))
    lines = _stream_lines(fleet, {"prompt": prompt, "max_tokens": 8})
    toks = [l["token"] for l in lines if "token" in l]
    assert toks == want
    done = lines[-1]
    assert done["done"] and done["reason"] == "length"
    assert done["replica"] in ("f0", "f1")
    # blocking rides the same affinity: same tokens, same replica
    status, body = _blocking(fleet, {"prompt": prompt, "max_tokens": 8})
    assert status == 200
    assert body["tokens"] == want
    assert body["replica"] == done["replica"]


# ------------------------------------------------------------------ chaos
def test_sigkill_loses_only_the_inflight_stream(fleet):
    prompt = [5, 9, 13, 17] * 6        # 24 tokens: one full 16-block
    _, probe = _blocking(fleet, {"prompt": prompt, "max_tokens": 2})
    victim = probe["replica"]
    survivor = "f1" if victim == "f0" else "f0"
    try:
        killed = []

        def kill_at_first_token(obj):
            if "token" in obj and not killed:
                killed.append(True)
                fleet.router.kill_replica(victim)

        lines = _stream_lines(fleet,
                              {"prompt": prompt, "max_tokens": 200},
                              on_line=kill_at_first_token)
        done = lines[-1]
        assert done["done"] is True
        # the contract: the stream is CLOSED with an explicit reason, and
        # only this stream is lost — nothing replays after first token
        assert done["reason"] == "replica_lost"
        assert done["replica"] == victim
        n_tokens = sum(1 for l in lines if "token" in l)
        assert done["tokens"] == n_tokens
        assert n_tokens < 200
        # router notices on its own (poller) and marks the victim dead
        assert _wait_state(fleet.router, victim, "dead", timeout=10.0)
        # the black box: a fleet_replica_lost dump naming the victim
        dump_dir = get_flight_recorder().directory
        dumps = [f for f in os.listdir(dump_dir)
                 if "fleet_replica_lost" in f]
        assert dumps
        assert any(json.load(open(os.path.join(dump_dir, f)))
                   ["info"].get("replica") == victim for f in dumps)
        # survivors keep serving
        status, body = _blocking(fleet, {"prompt": prompt,
                                         "max_tokens": 4})
        assert status == 200 and body["replica"] == survivor
    finally:
        _revive(fleet, victim)


def test_pre_first_token_kill_replays_idempotently(fleet):
    """Kill the affinity target BEFORE the request: the router fails over
    and the client sees one clean greedy sequence — the retry-idempotency
    pin — plus the fleet.retry trace marker."""
    prompt = [3, 6, 9, 12] * 5          # distinct prefix from other tests
    _, probe = _blocking(fleet, {"prompt": prompt, "max_tokens": 2})
    victim = probe["replica"]
    net = _net()
    want = naive_generate(net, prompt, 6, pad_to=64,
                          spec=TransformerDecodeSpec(net))
    fleet.router.stop()                 # freeze state: victim stays READY
    reg = MetricsRegistry(enabled=True)
    prev = telemetry.set_registry(reg)
    try:
        fleet.procs[victim].kill()
        lines = list(fleet.router.stream_generate(
            {"prompt": prompt, "max_tokens": 6}))
        toks = [l["token"] for l in lines if "token" in l]
        assert toks == want             # never partial, never double
        done = lines[-1]
        assert done["reason"] == "length"
        assert done["replica"] != victim
        assert done["retries"] >= 1
        names = [e["name"] for e in reg.trace_events()]
        assert "fleet.retry" in names
    finally:
        telemetry.set_registry(prev)
        fleet.router.start()
        _revive(fleet, victim)


# ---------------------------------------------------------- observability
def test_cross_process_trace_stitching(fleet, tmp_path):
    """ISSUE 19 acceptance: ONE X-Trace-Id through front door -> router ->
    replica subprocess comes back as a single ts-ordered timeline with
    per-process replica attribution — front-door spans from the local
    ring, replica spans pulled over /debug/trace — and trace2timeline
    renders the same stitched view."""
    from tools.trace2timeline import format_timeline, load_merged, timeline
    tid = "feedface2026"
    reg = MetricsRegistry(enabled=True)
    prev = telemetry.set_registry(reg)
    col = FleetCollector(fleet.router, registry=reg)
    try:
        status, body = fleet.client.request_json(
            "POST", fleet.base + "/generate",
            payload={"prompt": [2, 4, 6, 8], "max_tokens": 3,
                     "stream": False},
            headers={"X-Trace-Id": tid}, timeout=120.0)
        assert status == 200
        rid = body["replica"]
        assert col.pull_once() > 0 and col.pull_errors == 0
        events = col.events_for_trace(tid)
        replicas = {e["args"]["replica"] for e in events}
        assert {FRONT_DOOR, rid} <= replicas    # both processes present
        names = [e["name"] for e in events]
        assert any(n.startswith("fleet.") for n in names)       # front
        assert any(n.startswith("generation.") for n in names)  # replica
        ts = [e["ts"] for e in events]
        assert ts == sorted(ts)         # epoch-anchored cross-process order
        # receipt at the front door precedes the replica's work (epoch-
        # anchored ts makes cross-process ordering meaningful; fleet.route
        # is recorded when the forward RESOLVES, so it lands later)
        assert names.index("fleet.request") \
            < min(i for i, n in enumerate(names)
                  if n.startswith("generation."))
        # same timeline through the offline tool
        f = tmp_path / "stitched.json"
        f.write_text(json.dumps({"events": events}))
        rows = timeline(load_merged([str(f)]), tid)
        assert [r["name"] for r in rows] == names
        text = format_timeline(rows)
        assert "replica" in text.splitlines()[0] and rid in text
    finally:
        col.stop()
        telemetry.set_registry(prev)


def test_sigkill_black_box_recovered_from_spool(fleet):
    """ISSUE 19 acceptance, crash-durability half: SIGKILL a replica and
    its last periodic spool spill still tells the story — readable from
    disk, embedded as ``victim_spill`` in the fleet_replica_lost dump,
    and ingested by the collector so the victim's spans stitch into the
    fleet timeline after death."""
    tid = "cafebabe2026"
    reg = MetricsRegistry(enabled=True)
    prev = telemetry.set_registry(reg)
    col = FleetCollector(fleet.router, registry=reg)
    victim = None
    try:
        status, body = fleet.client.request_json(
            "POST", fleet.base + "/generate",
            payload={"prompt": [4, 8, 12, 16], "max_tokens": 3,
                     "stream": False},
            headers={"X-Trace-Id": tid}, timeout=120.0)
        assert status == 200
        victim = body["replica"]
        time.sleep(0.8)                 # > 2 spool periods: spill lands
        fleet.router.kill_replica(victim)
        assert _wait_state(fleet.router, victim, "dead", timeout=10.0)
        # the black box on disk outlived the process
        spill = read_spool(fleet.procs[victim].spool_path)
        assert spill is not None and spill["replica"] == victim
        assert spill["pid"] > 0 and spill["seq"] > 0
        assert any(e.get("args", {}).get("trace_id") == tid
                   for e in spill["events"])
        # ...and is embedded in the fleet_replica_lost dump
        dump_dir = get_flight_recorder().directory
        embedded = []
        for fn in os.listdir(dump_dir):
            if "fleet_replica_lost" not in fn:
                continue
            info = json.load(open(os.path.join(dump_dir, fn)))["info"]
            if info.get("replica") == victim and info.get("victim_spill"):
                embedded.append(info["victim_spill"])
        assert any(any(e.get("args", {}).get("trace_id") == tid
                       for e in s.get("events", []))
                   for s in embedded), "no dump embeds the victim's spill"
        # the collector recovers the victim's spans from the spool
        col.pull_once()
        assert col.spools_recovered >= 1
        events = col.events_for_trace(tid)
        assert any(e["args"]["replica"] == victim
                   and e["name"].startswith("generation.")
                   for e in events)
    finally:
        col.stop()
        telemetry.set_registry(prev)
        if victim is not None:
            _revive(fleet, victim)


@pytest.mark.slow
def test_chaos_soak_kill_revive_rounds(fleet):
    """Three kill/recover rounds: every lost stream closes with a reason,
    the fleet returns to full strength each time."""
    for round_i in range(3):
        prompt = [7 + round_i, 11, 19, 23] * 5
        _, probe = _blocking(fleet, {"prompt": prompt, "max_tokens": 2})
        victim = probe["replica"]
        try:
            killed = []

            def kill_once(obj, victim=victim, killed=killed):
                if "token" in obj and not killed:
                    killed.append(True)
                    fleet.router.kill_replica(victim)

            lines = _stream_lines(fleet,
                                  {"prompt": prompt, "max_tokens": 200},
                                  on_line=kill_once)
            assert lines[-1]["done"] is True
            assert lines[-1]["reason"] in ("replica_lost", "length")
            assert _wait_state(fleet.router, victim, "dead", timeout=10.0)
        finally:
            _revive(fleet, victim)
        status, _ = fleet.client.request_json(
            "GET", fleet.base + "/health", timeout=10.0)
        assert status == 200
        assert fleet.router.ready_count() == 2


# -------------------------------------------------------------- elasticity
def test_warm_cache_replica_joins_and_drains_out(fleet):
    """The autoscaler's scale-out path: a third replica pointed at the
    WARM shared compilation cache must reach ready as load-not-compile —
    zero fresh backend compiles — and scale-in must drain, not drop."""
    f2 = ReplicaProcess(fleet.spec, "f2", workdir=str(fleet.work),
                        env=fleet.env)
    added = False
    try:
        fleet.router.add_process(f2, wait_ready=True, timeout=240.0)
        added = True
        # the cold-start acceptance: load, don't compile
        assert f2.ready_info["fresh_compiles"] == 0
        assert f2.ready_info["cache_hits"] > 0
        assert fleet.router.ready_count() == 3
        assert fleet.router.drain_replica("f2", timeout=20.0) is True
        added = False
        assert {r["id"] for r in fleet.router.replicas()} \
            == {"f0", "f1"}
        assert f2.proc.returncode == 0      # SIGTERM -> drain -> clean exit
    finally:
        if added:
            fleet.router.remove_replica("f2")
        if f2.alive:
            f2.terminate(drain=False)


@pytest.mark.slow
def test_orphaned_replica_exits_when_supervisor_is_killed(fleet):
    """SIGKILL the SUPERVISOR (not the replica): the child gets no signal
    (own session), so without the ppid orphan watchdog it would serve
    nobody forever — the leak this pin exists to prevent."""
    import signal
    import subprocess
    import sys
    import textwrap
    spec_path = str(fleet.work / "orphan.spec.json")
    with open(spec_path, "w") as f:
        json.dump(fleet.spec, f)        # warm shared cache: fast ready
    script = textwrap.dedent("""
        import json, sys, time
        from deeplearning4j_tpu.serving.fleet import ReplicaProcess
        spec = json.load(open(sys.argv[1]))
        p = ReplicaProcess(spec, "orphan", workdir=sys.argv[2]).start()
        p.wait_ready(timeout=240.0)
        print(json.dumps({"replica_pid": p.pid}), flush=True)
        time.sleep(600)                 # hang until SIGKILLed
    """)
    env = {**os.environ, **fleet.env,
           "PYTHONPATH": os.pathsep.join(
               [os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                os.environ.get("PYTHONPATH", "")])}
    sup = subprocess.Popen(
        [sys.executable, "-c", script, spec_path, str(fleet.work)],
        stdout=subprocess.PIPE, env=env)
    try:
        replica_pid = json.loads(sup.stdout.readline())["replica_pid"]
        os.kill(replica_pid, 0)         # alive under a live supervisor
        sup.send_signal(signal.SIGKILL)
        sup.wait()
        deadline = time.monotonic() + 15.0
        while time.monotonic() < deadline:
            try:
                os.kill(replica_pid, 0)
            except ProcessLookupError:
                break                   # orphan noticed the reparent, exited
            time.sleep(0.25)
        else:
            os.kill(replica_pid, signal.SIGKILL)
            pytest.fail("orphaned replica still alive 15s after its "
                        "supervisor was SIGKILLed")
    finally:
        if sup.poll() is None:
            sup.kill()
            sup.wait()


def test_compile_cache_default_dir(monkeypatch):
    """With JAX_COMPILATION_CACHE_DIR unset the cache is the fixed
    <checkout>/.jax_cache, exported for child processes (the env-set leg
    is pinned by the fixture: every replica's ready record reports the
    directory its inherited env named). Restores the process-global jax
    config."""
    import jax

    from deeplearning4j_tpu.serving.fleet import coldstart
    from deeplearning4j_tpu.util import compile_cache
    olds = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_entry_size_bytes",
        "jax_persistent_cache_min_compile_time_secs")}
    monkeypatch.setenv(compile_cache.ENV_CACHE_DIR, "")     # = unset
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    try:
        cache = compile_cache.ensure_compile_cache()
        assert cache == os.path.join(repo, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == cache
        assert os.environ[compile_cache.ENV_CACHE_DIR] == cache
        assert compile_cache.ensure_compile_cache() == cache   # idempotent
    finally:
        for k, v in olds.items():
            jax.config.update(k, v)
    snap = coldstart.snapshot()
    assert {"compiles", "cache_hits", "fresh_compiles"} <= set(snap)
    assert snap["fresh_compiles"] >= 0
