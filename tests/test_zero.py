"""ZeRO-style sharded weight update (parallel/zero.py): group/bucket
layout, replicated-update parity (per-step, fused windows, remainder
batches, stage 1 vs 2, heterogeneous lr groups), sharded-state
checkpointing with manifest layout metadata + re-shard restore onto a
different mesh size, elastic kill->resume with sharded updater state and
the zero.* telemetry."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from deeplearning4j_tpu import MultiLayerNetwork, NeuralNetConfiguration
from deeplearning4j_tpu import telemetry
from deeplearning4j_tpu.datasets.dataset import ListDataSetIterator
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.optimize.updaters import Adam, Sgd
from deeplearning4j_tpu.parallel import (ElasticTrainer, FaultInjector,
                                         FaultPlan, KillWorker,
                                         ParallelWrapper, ZeroUpdateEngine,
                                         is_zero_state, make_zero_resharder)
from deeplearning4j_tpu.parallel.faults import truncate_newest_sharded
from deeplearning4j_tpu.parallel.mesh import make_mesh
from deeplearning4j_tpu.util.distributed_checkpoint import (
    read_manifest, restore_latest_sharded_checkpoint,
    restore_sharded_checkpoint, save_sharded_checkpoint)

R = np.random.default_rng(47)
# sharded (flat, packed) vs replicated (per-leaf) update after a few Adam
# steps on O(1) params: differently shaped programs, <= 1 f32 ulp/step
_VS_REPLICATED_ATOL = 1e-6


def _net(seed=7, updater=None, bias_lr=None):
    layers = [DenseLayer(n_in=6, n_out=24, activation="tanh"),
              DenseLayer(n_in=24, n_out=16, activation="tanh",
                         **({"bias_learning_rate": bias_lr}
                            if bias_lr else {})),
              OutputLayer(n_out=3, activation="softmax", loss="mcxent")]
    conf = (NeuralNetConfiguration(seed=seed, updater=updater or Adam(5e-3),
                                   dtype="float32")
            .list(*layers).build())
    return MultiLayerNetwork(conf).init()


def _data(n=128):
    x = R.normal(size=(n, 6)).astype(np.float32)
    yi = (x.sum(-1) > 0).astype(int) + (x[:, 0] > 1).astype(int)
    return x, np.eye(3, dtype=np.float32)[yi]


def _flat(net):
    return np.asarray(net.params_flat())


# ------------------------------------------------------------------ layout
def test_groups_partition_every_unfrozen_leaf_once():
    net = _net()
    eng = ZeroUpdateEngine.from_net(net, make_mesh(), stage=2,
                                    bucket_bytes=256)
    seen = sorted(i for g in eng.groups for b in g.buckets
                  for i in b.indices)
    assert seen == list(range(len(jax.tree.leaves(net.params))))
    for g in eng.groups:
        for b in g.buckets:
            assert b.lb == -(-b.nb // eng.n)        # ceil padding
        assert g.length == sum(b.lb for b in g.buckets)


def test_layout_splits_heterogeneous_lr_into_groups():
    """A bias_learning_rate override changes that leaf's lr multiplier —
    it must land in its OWN group (each group's flat update runs with a
    single traced-scalar lr, the bit-identity precondition)."""
    uniform = ZeroUpdateEngine.from_net(_net(), make_mesh(), stage=1)
    assert len(uniform.groups) == 1
    split = ZeroUpdateEngine.from_net(_net(bias_lr=0.5), make_mesh(),
                                      stage=1)
    assert len(split.groups) == 2
    mults = sorted(g.lr_mult for g in split.groups)
    assert mults[0] == 1.0 and mults[1] != 1.0


def test_engine_rejects_grad_norm_and_bad_stage():
    conf = (NeuralNetConfiguration(seed=1, updater=Sgd(0.1),
                                   gradient_normalization="clipl2perlayer")
            .list(DenseLayer(n_in=4, n_out=4, activation="tanh"),
                  OutputLayer(n_out=2, activation="softmax", loss="mcxent"))
            .build())
    net = MultiLayerNetwork(conf).init()
    with pytest.raises(ValueError, match="normalization"):
        ZeroUpdateEngine.from_net(net, make_mesh())
    with pytest.raises(ValueError, match="stage"):
        ZeroUpdateEngine.from_net(_net(), make_mesh(), stage=3)


def test_wrapper_rejects_bad_combinations():
    from deeplearning4j_tpu.parallel.accumulation import PsumAccumulator
    with pytest.raises(ValueError, match="zero_stage"):
        ParallelWrapper(_net(), zero_stage=1,
                        gradient_accumulator=PsumAccumulator())
    with pytest.raises(ValueError, match="averaging"):
        ParallelWrapper(_net(), zero_stage=1, training_mode="averaging",
                        averaging_frequency=4)
    with pytest.raises(ValueError, match="zero_stage"):
        ParallelWrapper(_net(), zero_stage=7)
    # averaging_frequency=1 IS the sync path: allowed
    ParallelWrapper(_net(), zero_stage=2, training_mode="averaging",
                    averaging_frequency=1)


def test_elastic_rejects_zero_plus_degraded_mode():
    with pytest.raises(ValueError, match="degraded"):
        ElasticTrainer(_net(), zero_stage=1, sync_latency_budget_ms=5.0)


def test_wrapper_rejects_overlap_sync_plus_zero():
    """Regression: overlap_sync=True with zero_stage was silently
    ignored (zero takes the dispatch) — it must refuse like the other
    non-composing flag pairs do."""
    with pytest.raises(ValueError, match="overlap_sync"):
        ParallelWrapper(_net(), zero_stage=2, overlap_sync=True)


def test_zero_handles_parameterless_layers():
    """Regression: a net containing a layer with NO params (activation/
    dropout/pooling — an empty param dict) crashed the opt-state
    alignment (the empty dict was mistaken for a stateless leaf). The
    sharded update must match the replicated one on such nets."""
    from deeplearning4j_tpu.nn.layers import ActivationLayer
    x, y = _data()

    def mk():
        conf = (NeuralNetConfiguration(seed=9, updater=Adam(5e-3),
                                       dtype="float32")
                .list(DenseLayer(n_in=6, n_out=16, activation="identity"),
                      ActivationLayer(activation="tanh"),
                      OutputLayer(n_out=3, activation="softmax",
                                  loss="mcxent"))
                .build())
        return MultiLayerNetwork(conf).init()

    ref = mk()
    it = ListDataSetIterator(features=x, labels=y, batch_size=64)
    ParallelWrapper(ref).fit(it, epochs=2)
    it.reset()
    net = mk()
    pw = ParallelWrapper(net, zero_stage=2)
    pw.fit(it, epochs=2)
    np.testing.assert_allclose(_flat(net), _flat(ref), rtol=0,
                               atol=_VS_REPLICATED_ATOL)
    # round-trips through the replicated format too
    pw.gather_opt_state()
    ref_state = net.updater.init(net.params)
    assert jax.tree.structure(net.opt_state) == \
        jax.tree.structure(ref_state)


def test_zero_frozen_layer_state_round_trips():
    """Regression: a frozen layer's leaves are excluded from the sharded
    update, but its (init, never-updated) state must come back from
    gather_opt_state() in the updater.init shape so model zips keep
    loading — and NONZERO frozen state is refused loudly instead of
    being silently zeroed."""
    from deeplearning4j_tpu.util.serialization import (
        restore_multilayer_network, write_model)
    x, y = _data()

    def mk():
        conf = (NeuralNetConfiguration(seed=9, updater=Adam(5e-3),
                                       dtype="float32")
                .list(DenseLayer(n_in=6, n_out=16, activation="tanh",
                                 frozen=True),
                      OutputLayer(n_out=3, activation="softmax",
                                  loss="mcxent"))
                .build())
        return MultiLayerNetwork(conf).init()

    ref = mk()
    it = ListDataSetIterator(features=x, labels=y, batch_size=64)
    ParallelWrapper(ref).fit(it, epochs=2)
    it.reset()
    net = mk()
    pw = ParallelWrapper(net, zero_stage=2)
    pw.fit(it, epochs=2)
    np.testing.assert_allclose(_flat(net), _flat(ref), rtol=0,
                               atol=_VS_REPLICATED_ATOL)
    # the frozen layer never entered the update: bitwise its init values
    for k, v in mk().params[0].items():
        np.testing.assert_array_equal(np.asarray(net.params[0][k]),
                                      np.asarray(v))
    pw.gather_opt_state()
    assert jax.tree.structure(net.opt_state) == \
        jax.tree.structure(net.updater.init(net.params))
    import tempfile
    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "m.zip")
        write_model(net, path)
        back = restore_multilayer_network(path)
        np.testing.assert_allclose(_flat(back), _flat(net), atol=1e-7)
    # nonzero frozen state cannot enter the sharded format silently
    poisoned = mk()
    poisoned.opt_state = jax.tree.map(lambda a: a + 1.0,
                                      poisoned.opt_state)
    eng = ZeroUpdateEngine.from_net(poisoned, make_mesh(), stage=2)
    with pytest.raises(ValueError, match="frozen"):
        eng.shard_opt_state(poisoned.opt_state)


def test_state_shard_roundtrip_and_bytes():
    """shard -> unshard -> shard must be bitwise lossless (pure
    redistribution), and the per-replica state allocation must shrink
    ~mesh-size-x (padding costs a few %)."""
    net = _net()
    eng = ZeroUpdateEngine.from_net(net, make_mesh(), stage=2,
                                    bucket_bytes=512)
    sharded = eng.shard_opt_state(net.opt_state)
    assert is_zero_state(sharded)
    rep = eng.unshard_opt_state(sharded)
    back = eng.shard_opt_state(rep)
    for a, b in zip(jax.tree.leaves(sharded), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    ratio = eng.replicated_state_bytes / eng.shard_state_bytes
    assert ratio >= 0.75 * eng.n, ratio
    # a state sharded for a different mesh size must be refused loudly
    eng2 = ZeroUpdateEngine.from_net(net, make_mesh((4,), ("data",),
                                                    jax.devices()[:4]),
                                     stage=2, bucket_bytes=512)
    with pytest.raises(ValueError, match="re-shard"):
        eng2.check_state(sharded)


# ------------------------------------------------------------------ parity
def test_zero_parity_default_bucket_bit_identical():
    """THE acceptance pin, at the default bucket size after N steps on
    the 8-device mesh, Adam state and all: stage 1 and stage 2 track the
    replicated (overlap) update, and each other, to _VS_REPLICATED_ATOL.
    Bitwise equality held on jax 0.4 and does not on 0.9 — not between
    the sharded and replicated paths, and not between the two stages
    either: the flat Adam chain over packed buckets, the per-leaf chain,
    and the two stages' collectives are all differently shaped programs,
    and whether XLA fuses them to the same last ulp (<= 1 ulp/step) is
    the compiler's choice, never this code's contract. What the code
    does guarantee bitwise — the same gradient reduction and packing, so
    a stateless SGD update is bit-identical at every bucket size — stays
    pinned by test_zero_sgd_bit_identical_every_bucket_size."""
    x, y = _data()
    it = ListDataSetIterator(features=x, labels=y, batch_size=64)
    ref = _net()
    ParallelWrapper(ref, overlap_sync=True).fit(it, epochs=2)
    flats = []
    for stage in (1, 2):
        it.reset()
        net = _net()
        ParallelWrapper(net, zero_stage=stage).fit(it, epochs=2)
        flats.append(_flat(net))
        np.testing.assert_allclose(flats[-1], _flat(ref), rtol=0,
                                   atol=_VS_REPLICATED_ATOL)
    np.testing.assert_allclose(flats[0], flats[1], rtol=0,
                               atol=_VS_REPLICATED_ATOL)


@pytest.mark.slow
def test_zero_stage1_equals_stage2_every_bucket_size():
    """Stages differ ONLY in the collective op (all-reduce+slice vs
    psum_scatter) over one shared packing graph — within float tolerance
    of each other and of the replicated path at every bucket size (the
    Adam chains are differently shaped programs that may fuse with
    different rounding — <= 1 ulp/step, same caveat as the scan
    window's; see test_zero_parity_default_bucket_bit_identical)."""
    x, y = _data()
    ref = _net()
    it = ListDataSetIterator(features=x, labels=y, batch_size=64)
    ParallelWrapper(ref).fit(it, epochs=2)
    for bb in (256, 1 << 30):
        flats = []
        for stage in (1, 2):
            it.reset()
            net = _net()
            ParallelWrapper(net, zero_stage=stage, bucket_bytes=bb).fit(
                it, epochs=2)
            flats.append(_flat(net))
        np.testing.assert_allclose(flats[0], flats[1], rtol=0,
                                   atol=_VS_REPLICATED_ATOL)
        np.testing.assert_allclose(flats[0], _flat(ref), rtol=0,
                                   atol=_VS_REPLICATED_ATOL)


@pytest.mark.slow
def test_zero_sgd_bit_identical_every_bucket_size():
    """With a stateless elementwise rule the flat update has no fusable
    multi-op chain: SGD pins bitwise against the replicated path at
    every bucket size, multi-bucket groups included."""
    x, y = _data()
    it = ListDataSetIterator(features=x, labels=y, batch_size=64)
    ref = _net(updater=Sgd(0.1))
    ParallelWrapper(ref).fit(it, epochs=2)
    for bb in (256, 1 << 30):
        it.reset()
        net = _net(updater=Sgd(0.1))
        ParallelWrapper(net, zero_stage=2, bucket_bytes=bb).fit(it, epochs=2)
        np.testing.assert_array_equal(_flat(ref), _flat(net))


def test_zero_window_bit_identical_to_per_step():
    """K fused zero steps (steps_per_dispatch) == K per-step zero
    dispatches, bitwise — the grad_sync/update_fn seams ride
    train_step_math into the scan body structurally."""
    x, y = _data(128)
    a, b = _net(), _net()
    b.set_params_flat(a.params_flat())
    it = ListDataSetIterator(features=x, labels=y, batch_size=32)
    ParallelWrapper(a, zero_stage=2).fit(it, epochs=2)
    it.reset()
    ParallelWrapper(b, zero_stage=2, steps_per_dispatch=2).fit(it, epochs=2)
    np.testing.assert_array_equal(_flat(a), _flat(b))


def test_zero_remainder_batch_dispatches_replicated_feed():
    """A batch that does not tile the mesh takes the replicated-feed
    zero program — sharded update and collectives intact — and tracks
    the single-net fit."""
    x, y = _data(100)            # batch 64 -> remainder 36 (36 % 8 != 0)
    single = _net()
    single.fit(iterator=ListDataSetIterator(features=x, labels=y,
                                            batch_size=64),
               epochs=2, async_prefetch=False)
    # stage 2 only: the remainder path differs from stage 1 solely in
    # the grad collective, and stage1==stage2 is pinned separately
    it = ListDataSetIterator(features=x, labels=y, batch_size=64)
    net = _net()
    pw = ParallelWrapper(net, zero_stage=2)
    pw.fit(it, epochs=2)
    assert pw._remainder_step is not None         # the remainder took it
    np.testing.assert_allclose(_flat(net), _flat(single),
                               rtol=2e-5, atol=2e-6)


@pytest.mark.slow
def test_zero_bias_lr_override_parity():
    """Heterogeneous lr multipliers (bias_learning_rate) split the
    layout into groups; the multi-group sharded update must still match
    the replicated path at the default bucket size."""
    x, y = _data()
    it = ListDataSetIterator(features=x, labels=y, batch_size=64)
    ref = _net(bias_lr=0.5)
    ParallelWrapper(ref).fit(it, epochs=2)
    it.reset()
    net = _net(bias_lr=0.5)
    pw = ParallelWrapper(net, zero_stage=2)
    pw.fit(it, epochs=2)
    assert len(pw._zero().groups) == 2
    np.testing.assert_allclose(_flat(ref), _flat(net), atol=1e-6)


@pytest.mark.slow
def test_zero_converges():
    x, y = _data(256)
    it = ListDataSetIterator(features=x, labels=y, batch_size=64)
    net = _net()
    s0 = net.score(x, y)
    ParallelWrapper(net, zero_stage=2).fit(it, epochs=12)
    assert net.score(x, y) < s0
    assert net.evaluate(x, y).accuracy() > 0.8


def test_gather_opt_state_restores_replicated_format():
    x, y = _data()
    it = ListDataSetIterator(features=x, labels=y, batch_size=64)
    net = _net()
    pw = ParallelWrapper(net, zero_stage=2)
    pw.fit(it, epochs=1)
    assert is_zero_state(net.opt_state)
    pw.gather_opt_state()
    assert not is_zero_state(net.opt_state)
    # structure matches a fresh updater.init
    ref = net.updater.init(net.params)
    assert jax.tree.structure(net.opt_state) == jax.tree.structure(ref)


def test_write_model_refuses_sharded_state(tmp_path):
    from deeplearning4j_tpu.util.serialization import write_model
    x, y = _data()
    it = ListDataSetIterator(features=x, labels=y, batch_size=64)
    net = _net()
    pw = ParallelWrapper(net, zero_stage=1)
    pw.fit(it, epochs=1)
    with pytest.raises(ValueError, match="gather_opt_state"):
        write_model(net, str(tmp_path / "m.zip"))
    pw.gather_opt_state()
    write_model(net, str(tmp_path / "m.zip"))     # now fine


# ------------------------------------------------------------- checkpoints
def _ckpt_tree(net, eng):
    return {"params": net.params, "state": net.state,
            "opt": eng.shard_opt_state(net.opt_state)
            if not is_zero_state(net.opt_state) else net.opt_state}


def test_manifest_sharding_block_and_same_mesh_restore(tmp_path):
    x, y = _data()
    it = ListDataSetIterator(features=x, labels=y, batch_size=64)
    net = _net()
    pw = ParallelWrapper(net, zero_stage=2)
    pw.fit(it, epochs=1)
    eng = pw._zero()
    save_sharded_checkpoint(str(tmp_path), 3, _ckpt_tree(net, eng),
                            extra={"step_in_epoch": 1},
                            sharding=eng.sharding_meta())
    man = read_manifest(str(tmp_path), 3)
    assert man["sharding"]["format"] == "zero-flat"
    assert man["sharding"]["num_shards"] == 8
    assert man["sharding"]["groups"][0]["bucket_elems"]
    # same mesh: direct restore, bitwise
    like = {"params": net.params, "state": net.state,
            "opt": eng.init_opt_state()}
    got = restore_sharded_checkpoint(str(tmp_path), 3, like)
    for a, b in zip(jax.tree.leaves(got["opt"]),
                    jax.tree.leaves(net.opt_state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_reshard_restore_onto_smaller_mesh(tmp_path):
    """State saved on the 8-shard layout restores onto a 4-device mesh
    via the resharder (all-gather -> re-slice): unsharding both must
    give the SAME per-leaf state values (redistribution, not math)."""
    x, y = _data()
    it = ListDataSetIterator(features=x, labels=y, batch_size=64)
    net = _net()
    pw = ParallelWrapper(net, zero_stage=2)
    pw.fit(it, epochs=1)
    eng8 = pw._zero()
    save_sharded_checkpoint(str(tmp_path), 5, _ckpt_tree(net, eng8),
                            sharding=eng8.sharding_meta())
    mesh4 = make_mesh((4,), ("data",), jax.devices()[:4])
    eng4 = ZeroUpdateEngine.from_net(net, mesh4, stage=2)
    rep = NamedSharding(mesh4, P())
    like = {"params": jax.tree.map(
                lambda a: jax.device_put(jnp.asarray(a), rep), net.params),
            "state": jax.tree.map(
                lambda a: jax.device_put(jnp.asarray(a), rep), net.state),
            "opt": eng4.init_opt_state()}
    step, got, _ = restore_latest_sharded_checkpoint(
        str(tmp_path), like, resharder=make_zero_resharder(eng4))
    assert step == 5
    eng4.check_state(got["opt"])          # shaped for the 4-shard layout
    rep8 = eng8.unshard_opt_state(net.opt_state)
    rep4 = eng4.unshard_opt_state(got["opt"])
    for a, b in zip(jax.tree.leaves(rep8), jax.tree.leaves(rep4)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # params rode along untouched
    for a, b in zip(jax.tree.leaves(net.params),
                    jax.tree.leaves(got["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_reshard_restore_falls_back_past_truncated_newest(tmp_path):
    """Regression (satellite): the re-shard path must compose with the
    damaged-save fallback — a truncated newest checkpoint is skipped and
    the older valid save re-shards instead of the restore aborting."""
    x, y = _data()
    it = ListDataSetIterator(features=x, labels=y, batch_size=64)
    net = _net()
    pw = ParallelWrapper(net, zero_stage=2)
    pw.fit(it, epochs=1)
    eng8 = pw._zero()
    save_sharded_checkpoint(str(tmp_path), 5, _ckpt_tree(net, eng8),
                            sharding=eng8.sharding_meta())
    pw.fit(it, epochs=1)
    save_sharded_checkpoint(str(tmp_path), 9, _ckpt_tree(net, eng8),
                            sharding=eng8.sharding_meta())
    truncate_newest_sharded(str(tmp_path))
    mesh4 = make_mesh((4,), ("data",), jax.devices()[:4])
    eng4 = ZeroUpdateEngine.from_net(net, mesh4, stage=2)
    rep = NamedSharding(mesh4, P())
    like = {"params": jax.tree.map(
                lambda a: jax.device_put(jnp.asarray(a), rep), net.params),
            "state": jax.tree.map(
                lambda a: jax.device_put(jnp.asarray(a), rep), net.state),
            "opt": eng4.init_opt_state()}
    step, got, _ = restore_latest_sharded_checkpoint(
        str(tmp_path), like, resharder=make_zero_resharder(eng4))
    assert step == 5                      # walked past the truncated 9
    eng4.check_state(got["opt"])


# ----------------------------------------------------------------- elastic
_EX = R.normal(size=(64, 6)).astype(np.float32)
_EY = np.eye(3, dtype=np.float32)[R.integers(0, 3, 64)]


def _eit(bs=8):
    return ListDataSetIterator(features=_EX, labels=_EY, batch_size=bs)


def _enet(seed=7):
    conf = (NeuralNetConfiguration(seed=seed, updater=Adam(1e-2),
                                   dtype="float32")
            .list(DenseLayer(n_in=6, n_out=16, activation="tanh"),
                  OutputLayer(n_out=3, activation="softmax", loss="mcxent"))
            .build())
    return MultiLayerNetwork(conf).init()


def _devs(n=4):
    return jax.devices()[:n]


_ZB_FLAT = {}


def _zero_baseline_flat(num_steps=16):
    """Unfaulted elastic-zero reference params, computed once per process
    (fixed seeds + module-level data: identical in any test order)."""
    if num_steps not in _ZB_FLAT:
        import tempfile
        with tempfile.TemporaryDirectory() as td:
            a = _enet()
            ElasticTrainer(a, checkpoint_dir=os.path.join(td, "zbase"),
                           devices=_devs(), checkpoint_every_n_steps=4,
                           keep_last=4, zero_stage=2).fit(
                _eit(), num_steps=num_steps)
            _ZB_FLAT[num_steps] = _flat(a)
    return _ZB_FLAT[num_steps]


def test_elastic_zero_matches_plain_zero_wrapper(tmp_path):
    """Supervision + async sharded-state checkpointing must add NOTHING
    to the zero math: an unfaulted elastic zero run is bit-identical to
    a plain ParallelWrapper(zero_stage) fit over the same steps."""
    a = _enet()
    ParallelWrapper(a, mesh=make_mesh((4,), ("data",), _devs()),
                    zero_stage=2, prefetch_buffer=0).fit(_eit(), epochs=2)
    b = _enet()
    tr = ElasticTrainer(b, checkpoint_dir=str(tmp_path), devices=_devs(),
                        checkpoint_every_n_steps=4, zero_stage=2)
    tr.fit(_eit(), num_steps=16)
    assert tr.steps_done == 16 and tr.recoveries == 0
    np.testing.assert_array_equal(_flat(a), _flat(b))
    # the on-disk manifests carry the shard-layout block
    from deeplearning4j_tpu.util.distributed_checkpoint import \
        latest_sharded_step
    st = latest_sharded_step(str(tmp_path))
    assert read_manifest(str(tmp_path), st)["sharding"]["num_shards"] == 4


def test_elastic_zero_kill_rejoin_bit_identical(tmp_path):
    """Worker kill with rejoin -> same-shape mesh re-form: the sharded
    updater state restores from the async checkpoints and the run lands
    bit-identical to the unfaulted elastic zero run, resuming mid-grid
    through K=2 fused windows."""
    base = _zero_baseline_flat()
    # K=2 is the stronger pin (fused windows + recovery); the K=1 zero
    # elastic loop is covered by the no-fault and shrunk-mesh tests
    b = _enet()
    inj = FaultInjector(FaultPlan(KillWorker(step=13, worker=1,
                                             rejoin=True)))
    tr = ElasticTrainer(b, checkpoint_dir=str(tmp_path / "zf"),
                        devices=_devs(), checkpoint_every_n_steps=4,
                        keep_last=4, zero_stage=2,
                        steps_per_dispatch=2, fault_injector=inj)
    tr.fit(_eit(), num_steps=16)
    assert tr.recoveries == 1 and tr.steps_done == 16
    np.testing.assert_array_equal(base, _flat(b))


def test_elastic_zero_shrunk_mesh_reshards_state(tmp_path):
    """THE re-shard acceptance scenario: a permanently lost worker
    re-forms a 3-device mesh; the 4-shard updater state re-shards on
    restore (all-gather -> re-slice) instead of aborting, and the run
    converges to the baseline within float tolerance."""
    base = _zero_baseline_flat()
    b = _enet()
    inj = FaultInjector(FaultPlan(KillWorker(step=11, worker=2,
                                             rejoin=False)))
    tr = ElasticTrainer(b, checkpoint_dir=str(tmp_path / "shrink"),
                        devices=_devs(), checkpoint_every_n_steps=4,
                        zero_stage=2, fault_injector=inj)
    tr.fit(_eit(), num_steps=16)
    assert tr.recoveries == 1 and len(tr._devices) == 3
    assert tr.steps_done == 16
    np.testing.assert_allclose(base, _flat(b), rtol=1e-4, atol=1e-5)


# --------------------------------------------------------------- telemetry
def test_zero_gauges_and_collective_launch_accounting():
    reg = telemetry.get_registry()
    telemetry.reset()
    x, y = _data(128)
    it = ListDataSetIterator(features=x, labels=y, batch_size=64)
    net = _net()
    pw = ParallelWrapper(net, zero_stage=2, bucket_bytes=512)
    pw.fit(it, epochs=1)                              # 2 steps
    eng = pw._zero()
    assert reg.gauge("zero.shard_bytes").value == eng.shard_state_bytes
    assert reg.gauge("zero.gathered_bytes").value == eng.gathered_bytes
    snap = reg.snapshot()
    # per step: reduce launches + group all-gathers + fused state/loss
    assert snap["counters"]["parallel.collective_launches"] == \
        2 * (eng.collectives_per_step + 1)


def test_zero_profile_emits_collective_trace_phases(tmp_path):
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    import trace2summary

    reg = telemetry.get_registry()
    telemetry.reset()
    net = _net()
    eng = ZeroUpdateEngine.from_net(net, make_mesh(), stage=2,
                                    bucket_bytes=512)
    with telemetry.span("fit"):
        out = eng.profile(make_mesh())
    assert out["reduce_scatter"] and out["all_gather"]
    assert reg.gauge("zero.shard_bytes").value == eng.shard_state_bytes
    trace = tmp_path / "trace.json"
    reg.write_chrome_trace(str(trace))
    rows = trace2summary.summarize(trace2summary.load_events(str(trace)))
    phases = {r["phase"] for r in rows}
    # the all-gather launches fold under the zero.allgather span; every
    # reduce-scatter bucket gets its own [reduce_scatter:g.b] phase
    assert "fit/zero.allgather" in phases, phases
    for r in out["reduce_scatter"]:
        assert f"fit/[reduce_scatter:{r['group']}.{r['bucket']}]" \
            in phases, phases
    for r in out["all_gather"]:
        assert f"fit/zero.allgather/[all_gather:{r['group']}]" in phases, \
            phases
