"""Elastic fault-tolerant training (parallel/elastic.py + util/async_checkpoint
+ parallel/faults.py).

The acceptance contract: training with an injected worker kill AND a
truncated newest checkpoint resumes from the last valid checkpoint on the
re-formed mesh and reaches the same result as an uninterrupted run —
bit-identical when the mesh shape is unchanged, within float tolerance
when the mesh shrank (the psum is the same reduction in a different
association order). Plus: async checkpointing adds zero blocking
device->host readbacks to the steady-state step loop (HostSyncDetector
tripwire, same harness as test_telemetry)."""
import os
import signal
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from deeplearning4j_tpu import MultiLayerNetwork, NeuralNetConfiguration
from deeplearning4j_tpu.datasets.dataset import ListDataSetIterator
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.optimize.updaters import Adam
from deeplearning4j_tpu.parallel import (CoordinationFlake, CorruptCheckpoint,
                                         ElasticTrainer, FaultInjector,
                                         FaultPlan, KillWorker,
                                         ParallelWrapper, PreemptAt,
                                         RecoveryFailedError, SlowCollective)
from deeplearning4j_tpu.parallel.faults import (corrupt_newest_sharded,
                                                truncate_newest_sharded)
from deeplearning4j_tpu.parallel.mesh import make_mesh
from deeplearning4j_tpu.telemetry import HostSyncDetector, get_registry
from deeplearning4j_tpu.util import async_checkpoint as ac
from deeplearning4j_tpu.util.distributed_checkpoint import (
    is_valid, latest_sharded_step, read_manifest,
    restore_latest_sharded_checkpoint, save_sharded_checkpoint)
from deeplearning4j_tpu.util.retry import RetryPolicy

R = np.random.default_rng(41)


def _net(seed=7):
    conf = (NeuralNetConfiguration(seed=seed, updater=Adam(1e-2),
                                   dtype="float32")
            .list(DenseLayer(n_in=6, n_out=16, activation="tanh"),
                  OutputLayer(n_out=3, activation="softmax", loss="mcxent"))
            .build())
    return MultiLayerNetwork(conf).init()


_X = R.normal(size=(64, 6)).astype(np.float32)
_Y = np.eye(3, dtype=np.float32)[R.integers(0, 3, 64)]


def _it(bs=8):
    return ListDataSetIterator(features=_X, labels=_Y, batch_size=bs)


def _flat(net):
    return np.concatenate([np.asarray(l).ravel()
                           for l in jax.tree.leaves(net.params)])


def _devs(n=4):
    return jax.devices()[:n]


def _baseline(tmp_path, num_steps=20, **kw):
    a = _net()
    tr = ElasticTrainer(a, checkpoint_dir=str(tmp_path / "base"),
                        devices=_devs(), checkpoint_every_n_steps=4,
                        keep_last=4, **kw)
    tr.fit(_it(), num_steps=num_steps)
    return a, tr


# ------------------------------------------------------- async writer unit
def test_async_writer_writes_valid_checkpoints(tmp_path):
    mesh = make_mesh((4,), ("data",), _devs())
    rep = NamedSharding(mesh, P())
    tree = {"a": jax.device_put(jnp.arange(8.0), rep)}
    w = ac.AsyncCheckpointWriter(str(tmp_path), keep_last=2)
    try:
        w.submit(5, tree, extra={"step_in_epoch": 3})
        assert w.flush(timeout=30.0)
    finally:
        w.close()
    assert w.last_completed_step == 5
    assert latest_sharded_step(str(tmp_path)) == 5
    assert read_manifest(str(tmp_path), 5)["extra"] == {"step_in_epoch": 3}
    like = {"a": jax.device_put(jnp.zeros(8), rep)}
    step, got, extra = restore_latest_sharded_checkpoint(str(tmp_path), like)
    assert step == 5 and extra == {"step_in_epoch": 3}
    np.testing.assert_array_equal(np.asarray(got["a"]), np.arange(8.0))


def test_async_writer_latest_wins_coalescing(tmp_path, monkeypatch):
    """A slow write coalesces queued submits: only the newest pending
    snapshot is kept, drops are counted, step time never waits."""
    gate = threading.Event()
    written = []
    orig = ac.save_sharded_checkpoint

    def slow_save(directory, step, tree, extra=None):
        gate.wait(10.0)
        written.append(step)
        return orig(directory, step, tree, extra=extra)

    monkeypatch.setattr(ac, "save_sharded_checkpoint", slow_save)
    mesh = make_mesh((4,), ("data",), _devs())
    rep = NamedSharding(mesh, P())
    tree = {"a": jax.device_put(jnp.ones(4), rep)}
    reg = get_registry()
    before = reg.snapshot()["counters"].get("elastic.checkpoint.dropped", 0)
    w = ac.AsyncCheckpointWriter(str(tmp_path), keep_last=4)
    try:
        assert w.submit(1, tree)          # picked up by the (gated) writer
        time.sleep(0.05)
        assert w.submit(2, tree)          # pending slot
        assert not w.submit(3, tree)      # replaces pending 2
        gate.set()
        assert w.flush(timeout=30.0)
    finally:
        w.close()
    assert written == [1, 3]              # 2 was coalesced away
    after = reg.snapshot()["counters"].get("elastic.checkpoint.dropped", 0)
    assert after - before == 1


def test_async_writer_survives_write_errors(tmp_path, monkeypatch):
    calls = {"n": 0}
    orig = ac.save_sharded_checkpoint

    def flaky(directory, step, tree, extra=None):
        calls["n"] += 1
        if calls["n"] == 1:
            raise OSError("disk went away")
        return orig(directory, step, tree, extra=extra)

    monkeypatch.setattr(ac, "save_sharded_checkpoint", flaky)
    mesh = make_mesh((4,), ("data",), _devs())
    tree = {"a": jax.device_put(jnp.ones(4), NamedSharding(mesh, P()))}
    w = ac.AsyncCheckpointWriter(str(tmp_path))
    try:
        w.submit(1, tree)
        w.flush(timeout=30.0)
        assert isinstance(w.last_error, OSError)
        assert w.last_completed_step is None
        w.submit(2, tree)                  # the writer thread survived
        w.flush(timeout=30.0)
        assert w.last_completed_step == 2
    finally:
        w.close()


# ------------------------------------------------- sharded restore fallback
def _save_two(tmp_path):
    mesh = make_mesh((4,), ("data",), _devs())
    rep = NamedSharding(mesh, P())
    t1 = {"a": jax.device_put(jnp.full(6, 1.0), rep)}
    t2 = {"a": jax.device_put(jnp.full(6, 2.0), rep)}
    save_sharded_checkpoint(str(tmp_path), 1, t1)
    save_sharded_checkpoint(str(tmp_path), 2, t2)
    like = {"a": jax.device_put(jnp.zeros(6), rep)}
    return like


def test_restore_falls_back_past_truncated_newest(tmp_path):
    like = _save_two(tmp_path)
    assert truncate_newest_sharded(str(tmp_path)) == 2
    assert not is_valid(str(tmp_path), 2)
    assert is_valid(str(tmp_path), 1)
    assert latest_sharded_step(str(tmp_path)) == 1
    step, got, _ = restore_latest_sharded_checkpoint(str(tmp_path), like)
    assert step == 1
    np.testing.assert_array_equal(np.asarray(got["a"]), np.full(6, 1.0))


def test_restore_falls_back_past_corrupt_member(tmp_path):
    """Mid-file bit flips keep the zip directory intact (is_zipfile
    passes) — the CRC failure during the actual read must fall back."""
    like = _save_two(tmp_path)
    assert corrupt_newest_sharded(str(tmp_path)) == 2
    step, got, _ = restore_latest_sharded_checkpoint(str(tmp_path), like)
    assert step == 1
    np.testing.assert_array_equal(np.asarray(got["a"]), np.full(6, 1.0))


def test_restore_with_nothing_valid_returns_like(tmp_path):
    mesh = make_mesh((4,), ("data",), _devs())
    like = {"a": jax.device_put(jnp.zeros(3),
                                NamedSharding(mesh, P()))}
    step, got, extra = restore_latest_sharded_checkpoint(str(tmp_path), like)
    assert step is None and extra == {}
    assert got is like


# ------------------------------------------------------------ elastic loop
def test_elastic_no_fault_matches_parallel_wrapper(tmp_path):
    """Supervision (step callback + async checkpointing) must add
    NOTHING to the math: an unfaulted elastic run is bit-identical to a
    plain ParallelWrapper fit over the same steps."""
    a = _net()
    ParallelWrapper(a, mesh=make_mesh((4,), ("data",), _devs()),
                    prefetch_buffer=0).fit(_it(), epochs=3)   # 24 steps
    b = _net()
    tr = ElasticTrainer(b, checkpoint_dir=str(tmp_path),
                        devices=_devs(), checkpoint_every_n_steps=4)
    tr.fit(_it(), num_steps=24)
    assert tr.steps_done == 24 and tr.recoveries == 0
    np.testing.assert_array_equal(_flat(a), _flat(b))


def test_kill_plus_truncated_checkpoint_recovers_bit_identical(tmp_path):
    """THE acceptance scenario: worker kill at step 13 with the newest
    checkpoint truncated on disk. Recovery must skip the damaged save,
    restore the older valid one, re-form the mesh (rejoin -> same
    shape), replay, and land bit-identical to an uninterrupted run."""
    a, _ = _baseline(tmp_path)
    b = _net()
    inj = FaultInjector(FaultPlan(
        CorruptCheckpoint(step=13, mode="truncate"),
        KillWorker(step=13, worker=1, rejoin=True)))
    tr = ElasticTrainer(b, checkpoint_dir=str(tmp_path / "faulted"),
                        devices=_devs(), checkpoint_every_n_steps=4,
                        keep_last=4, fault_injector=inj)
    tr.fit(_it(), num_steps=20)
    assert tr.recoveries == 1
    assert tr.steps_done == 20
    assert get_registry().snapshot()["counters"].get(
        "elastic.recoveries", 0) >= 1
    np.testing.assert_array_equal(_flat(a), _flat(b))


def test_kill_without_rejoin_shrinks_mesh_and_converges(tmp_path):
    """A permanently lost worker re-forms a smaller mesh; the resumed run
    reaches the same result within float tolerance (different psum
    association order)."""
    a, _ = _baseline(tmp_path)
    b = _net()
    inj = FaultInjector(FaultPlan(KillWorker(step=11, worker=2,
                                             rejoin=False)))
    tr = ElasticTrainer(b, checkpoint_dir=str(tmp_path / "faulted"),
                        devices=_devs(), checkpoint_every_n_steps=4,
                        fault_injector=inj)
    tr.fit(_it(), num_steps=20)
    assert tr.recoveries == 1 and len(tr._devices) == 3
    assert tr.steps_done == 20
    np.testing.assert_allclose(_flat(a), _flat(b), rtol=1e-4, atol=1e-5)


def test_recovery_through_fused_windows_bit_identical(tmp_path):
    """steps_per_dispatch=2: the supervised loop runs K-fused windows;
    kill + recovery resumes mid-grid and must still be bit-identical to
    the unfaulted K=1 elastic run (the scan-window contract composes
    with recovery)."""
    a, _ = _baseline(tmp_path)
    b = _net()
    inj = FaultInjector(FaultPlan(KillWorker(step=14, worker=0,
                                             rejoin=True)))
    tr = ElasticTrainer(b, checkpoint_dir=str(tmp_path / "w"),
                        devices=_devs(), checkpoint_every_n_steps=4,
                        steps_per_dispatch=2, fault_injector=inj)
    tr.fit(_it(), num_steps=20)
    assert tr.recoveries == 1
    np.testing.assert_array_equal(_flat(a), _flat(b))


def test_no_checkpoint_yet_restarts_from_scratch(tmp_path):
    """Worker loss before the first checkpoint lands: recovery re-inits
    deterministically at step 0 and the full run still matches the
    baseline bit-for-bit."""
    a, _ = _baseline(tmp_path, num_steps=16)
    b = _net()
    inj = FaultInjector(FaultPlan(KillWorker(step=3, worker=1,
                                             rejoin=True)))
    tr = ElasticTrainer(b, checkpoint_dir=str(tmp_path / "scratch"),
                        devices=_devs(), checkpoint_every_n_steps=100,
                        fault_injector=inj)
    tr.fit(_it(), num_steps=16)
    assert tr.recoveries == 1
    np.testing.assert_array_equal(_flat(a), _flat(b))


def test_cross_process_resume_from_directory(tmp_path):
    """A FRESH trainer pointed at an existing checkpoint dir continues
    where the previous 'process' stopped — and matches the single-run
    baseline bit-for-bit (mid-epoch position from the manifest)."""
    a, _ = _baseline(tmp_path, num_steps=20)
    d = str(tmp_path / "resume")
    b = _net()
    ElasticTrainer(b, checkpoint_dir=d, devices=_devs(),
                   checkpoint_every_n_steps=4).fit(_it(), num_steps=10)
    c = _net()
    tr = ElasticTrainer(c, checkpoint_dir=d, devices=_devs(),
                        checkpoint_every_n_steps=4)
    tr.fit(_it(), num_steps=20)
    assert tr.steps_done == 20
    np.testing.assert_array_equal(_flat(a), _flat(c))


# ------------------------------------------------------------- coordination
def test_coordination_flakes_are_retried(tmp_path):
    a, _ = _baseline(tmp_path)
    b = _net()
    inj = FaultInjector(FaultPlan(
        KillWorker(step=13, worker=1, rejoin=True),
        CoordinationFlake(step=13, failures=2)))
    tr = ElasticTrainer(b, checkpoint_dir=str(tmp_path / "flaky"),
                        devices=_devs(), checkpoint_every_n_steps=4,
                        fault_injector=inj,
                        retry_policy=RetryPolicy(max_attempts=4,
                                                 base_delay_s=0.001,
                                                 sleep=lambda s: None))
    tr.fit(_it(), num_steps=20)
    assert tr.recoveries == 1
    assert inj.coordination_attempts == 3      # 2 flakes + 1 success
    np.testing.assert_array_equal(_flat(a), _flat(b))


def test_coordination_give_up_raises_recovery_failed(tmp_path):
    b = _net()
    inj = FaultInjector(FaultPlan(
        KillWorker(step=6, worker=1, rejoin=True),
        CoordinationFlake(step=6, failures=10)))
    tr = ElasticTrainer(b, checkpoint_dir=str(tmp_path),
                        devices=_devs(), checkpoint_every_n_steps=4,
                        fault_injector=inj,
                        retry_policy=RetryPolicy(max_attempts=3,
                                                 base_delay_s=0.001,
                                                 sleep=lambda s: None))
    with pytest.raises(RecoveryFailedError, match="gave up"):
        tr.fit(_it(), num_steps=20)


def test_max_recoveries_cap(tmp_path):
    b = _net()
    plan = FaultPlan(*[KillWorker(step=s, worker=0, rejoin=True)
                       for s in (3, 6, 9)])
    tr = ElasticTrainer(b, checkpoint_dir=str(tmp_path),
                        devices=_devs(), checkpoint_every_n_steps=2,
                        max_recoveries=2, fault_injector=FaultInjector(plan))
    with pytest.raises(RecoveryFailedError, match="max_recoveries"):
        tr.fit(_it(), num_steps=20)


# ------------------------------------------------------------ degraded mode
def test_degraded_mode_enters_and_exits(tmp_path):
    """Slow-collective latency above the budget flips the loop into
    SparkNet-style averaging windows (one collective per K steps) and
    flips back once the interconnect recovers."""
    b = _net()
    inj = FaultInjector(FaultPlan(
        SlowCollective(step=4, until_step=16, delay_ms=400.0)))
    tr = ElasticTrainer(b, checkpoint_dir=str(tmp_path),
                        devices=_devs(), checkpoint_every_n_steps=100,
                        sync_latency_budget_ms=50.0, latency_window=2,
                        degraded_averaging_window=4,
                        degraded_exit_patience=2, fault_injector=inj)
    tr.fit(_it(), num_steps=32)
    assert tr.steps_done >= 32
    assert tr.degraded_transitions == 2
    modes = [m for _, m in tr.mode_history]
    assert modes == ["averaging", "sync"]
    enter_step, exit_step = (s for s, _ in tr.mode_history)
    assert enter_step < 16 <= exit_step
    assert tr.mode == "sync"
    snap = get_registry().snapshot()
    assert snap["counters"].get("elastic.degraded_transitions", 0) >= 2
    assert np.isfinite(_flat(b)).all()


# -------------------------------------------------------------- preemption
def test_preemption_flushes_final_checkpoint_and_resumes(tmp_path):
    a, _ = _baseline(tmp_path, num_steps=20)
    d = str(tmp_path / "preempt")
    b = _net()
    inj = FaultInjector(FaultPlan(PreemptAt(step=9)))
    tr = ElasticTrainer(b, checkpoint_dir=d, devices=_devs(),
                        checkpoint_every_n_steps=4, fault_injector=inj)
    tr.fit(_it(), num_steps=20)
    assert tr.preempted
    assert tr.steps_done == 9
    # the final flush landed a checkpoint at EXACTLY the preempt step
    assert latest_sharded_step(d) == 9
    assert read_manifest(d, 9)["extra"]["step_in_epoch"] == 1
    # a fresh "process" resumes and matches the uninterrupted baseline
    c = _net()
    tr2 = ElasticTrainer(c, checkpoint_dir=d, devices=_devs(),
                         checkpoint_every_n_steps=4)
    tr2.fit(_it(), num_steps=20)
    assert not tr2.preempted
    np.testing.assert_array_equal(_flat(a), _flat(c))


def test_sigterm_guard_triggers_clean_preemption(tmp_path):
    """A real SIGTERM through PreemptionGuard takes the same clean path:
    flag set by the handler, final checkpoint flushed, fit returns."""

    class _SignalAt(FaultInjector):
        def __init__(self, at):
            super().__init__()
            self.at = at
            self.sent = False

        def on_step(self, step, trainer=None):
            if not self.sent and step >= self.at:
                self.sent = True
                signal.raise_signal(signal.SIGTERM)

    b = _net()
    d = str(tmp_path)
    inj = _SignalAt(at=6)
    tr = ElasticTrainer(b, checkpoint_dir=d, devices=_devs(),
                        checkpoint_every_n_steps=4, fault_injector=inj)
    with tr.preemption_guard() as guard:
        tr.fit(_it(), num_steps=20)
    assert guard.triggered and tr.preempted
    assert tr.steps_done == 6
    assert latest_sharded_step(d) == 6


# ------------------------------------------------- sync-freedom (acceptance)
def test_elastic_steady_state_adds_zero_host_syncs(tmp_path):
    """The tier-1 sync-freedom pin, extended to the elastic path: a
    steady-state supervised loop WITH async checkpointing active —
    including the initial restore and periodic submits — performs zero
    blocking device->host readbacks on the step-loop thread (the writer
    thread's materialization is the designed exception)."""
    b = _net()
    d = str(tmp_path)
    tr = ElasticTrainer(b, checkpoint_dir=d, devices=_devs(),
                        checkpoint_every_n_steps=4, final_checkpoint=False)
    # warm-up: compiles + first-touch caches may legitimately sync
    tr.fit(_it(), num_steps=8)
    with HostSyncDetector(action="count") as det:
        tr.fit(_it(), num_steps=24)       # restore -> steady loop -> submits
    assert tr.steps_done == 24
    assert det.count == 0, \
        f"syncs at {[e['span_path'] for e in det.events]}"
    # the async writer did run (checkpoints landed during the guarded fit)
    assert latest_sharded_step(d) >= 20


def test_same_process_continuation_before_first_full_pass(tmp_path):
    """Regression: a fit() stopping mid-epoch BEFORE any clean pass
    (epoch length still unknown) must record its position so a
    continuation fit() on the same trainer resumes there instead of
    replaying the epoch prefix."""
    a, _ = _baseline(tmp_path, num_steps=16)
    b = _net()
    tr = ElasticTrainer(b, checkpoint_dir=str(tmp_path / "cont"),
                        devices=_devs(), checkpoint_every_n_steps=4)
    tr.fit(_it(), num_steps=5)           # stops mid-epoch, L unknown
    assert tr.steps_done == 5
    tr.fit(_it(), num_steps=16)          # continuation, same trainer
    assert tr.steps_done == 16
    np.testing.assert_array_equal(_flat(a), _flat(b))


def test_non_resettable_exhausted_iterator_raises(tmp_path):
    """A generator that exhausts and can't reset must raise instead of
    spinning the supervised loop forever at zero progress."""
    b = _net()
    one_epoch = iter([d for d in _it()])     # no reset(): one pass only
    tr = ElasticTrainer(b, checkpoint_dir=str(tmp_path), devices=_devs(),
                        checkpoint_every_n_steps=4)
    with pytest.raises(ValueError, match="resettable"):
        tr.fit(one_epoch, num_steps=20)      # epoch has only 8 batches
    assert b.iteration_count == 8            # the one pass did train


def test_averaging_path_remainder_batch_fallback():
    """Regression (found by the chaos soak): the K-step averaging path
    used to die on the shard_map divisibility error when the batch size
    stopped tiling the mesh (exactly what happens when degraded mode
    runs on a recovery-shrunk mesh). Remainder batches now dispatch the
    replicated-feed averaging program."""
    net = _net()
    pw = ParallelWrapper(net, mesh=make_mesh((3,), ("data",), _devs(3)),
                         training_mode="averaging", averaging_frequency=4,
                         average_updaters=True, prefetch_buffer=0)
    pw.fit(_it(bs=8), epochs=1)           # 8 % 3 != 0 on every batch
    assert net.iteration_count == 8
    assert np.isfinite(_flat(net)).all()


# ------------------------------------------------------------- chaos (slow)
@pytest.mark.slow
def test_chaos_soak_random_fault_plan(tmp_path):
    """Seeded random weather: kills (mixed rejoin), checkpoint damage,
    slow-collective windows. N recoveries later the run completes every
    step with finite params."""
    rng = np.random.default_rng(1234)
    kills = sorted(rng.choice(np.arange(6, 120, 3), size=4, replace=False))
    faults = []
    for i, s in enumerate(kills):
        faults.append(KillWorker(step=int(s), worker=int(rng.integers(0, 4)),
                                 rejoin=bool(i % 2)))
        if i % 2:
            faults.append(CorruptCheckpoint(
                step=int(s), mode="truncate" if i % 4 else "flip"))
    faults.append(SlowCollective(step=40, until_step=70, delay_ms=300.0))
    inj = FaultInjector(FaultPlan(*faults))
    b = _net()
    tr = ElasticTrainer(b, checkpoint_dir=str(tmp_path), devices=_devs(),
                        checkpoint_every_n_steps=5, keep_last=4,
                        sync_latency_budget_ms=60.0, latency_window=2,
                        degraded_averaging_window=4, max_recoveries=16,
                        fault_injector=inj)
    tr.fit(_it(), num_steps=130)
    assert tr.steps_done >= 130
    assert tr.recoveries == 4
    assert np.isfinite(_flat(b)).all()
    out = b.output(_X[:8])
    assert np.isfinite(np.asarray(out)).all()
