"""Tensor-parallel (data, model) meshes (parallel/tensor_parallel.py).

Covers the ISSUE 20 tentpole acceptance criteria on the 8-virtual-device
CPU mesh: the Megatron layout rules (attention Q/K/V column- / Wo
row-parallel, MLP ff1/ff2 split, LSTM 4H gate blocks), m=1 bit-identity
with the 1-D data path, (2, 2) float-tolerance parity including the
steps_per_dispatch / zero_stage compositions, per-replica memory
reduction, model-sharded paged decode (token-identical, pool bytes/m per
chip, hot-swap executable reuse), the write_model host-gather seam and
the per-chip ProgramCostIndex division."""

import numpy as np
import pytest

import jax
from jax.sharding import PartitionSpec as P

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.models.zoo_extra import (text_generation_lstm,
                                                 transformer_lm)
from deeplearning4j_tpu.parallel import (ParallelWrapper, build_param_specs,
                                         host_gather, per_replica_bytes,
                                         sharded_leaf_count)
from deeplearning4j_tpu.parallel.mesh import make_mesh

V = 29


def _net(seed=11, d_model=16, n_heads=4, max_length=16):
    return transformer_lm(vocab_size=V, d_model=d_model, n_heads=n_heads,
                          n_blocks=1, max_length=max_length, seed=seed,
                          token_input=True).init()


def _data(n=2, b=8, t=8, seed=0):
    rs = np.random.RandomState(seed)
    return [DataSet(rs.randint(1, V, (b, t)).astype(np.int32),
                    np.eye(V)[rs.randint(0, V, (b, t))].astype(np.float32))
            for _ in range(n)]


def _flat(net):
    return np.concatenate([np.asarray(l).ravel()
                           for l in jax.tree.leaves(host_gather(net.params))])


def _maxdiff(a, b):
    return float(np.max(np.abs(a - b))) if a.size else 0.0


# ------------------------------------------------------------ layout rules
def test_transformer_spec_rules():
    net = _net()
    specs = build_param_specs(net, 2)
    names = list(net.vertex_names)
    checked = {"attn": 0, "ff1": 0, "ff2": 0}
    for name, vspecs in zip(names, specs):
        if not isinstance(vspecs, dict):
            continue
        if name.endswith("_attn"):
            checked["attn"] += 1
            for k, s in vspecs.items():
                if k in ("Wq", "Wk", "Wv"):
                    assert s == P(None, "model"), (name, k, s)
                elif k == "Wo":
                    assert s == P("model", None), (name, k, s)
                else:           # biases ride the post-psum add
                    assert s == P(), (name, k, s)
        elif name.endswith("_ff1"):
            checked["ff1"] += 1
            assert vspecs["W"] == P(None, "model")
            assert vspecs["b"] == P("model")
        elif name.endswith("_ff2"):
            checked["ff2"] += 1
            assert vspecs["W"] == P("model", None)
            assert vspecs.get("b", P()) == P()
        else:                   # embeddings / layernorms / head: replicated
            for k, s in vspecs.items():
                assert s == P(), (name, k, s)
    assert all(checked.values()), checked
    assert sharded_leaf_count(specs) >= 6


def test_m1_specs_are_fully_replicated():
    specs = build_param_specs(_net(), 1)
    assert sharded_leaf_count(specs) == 0
    for s in jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P)):
        assert s == P()


def test_indivisible_leaf_degrades_alone():
    """d_model=18 does not divide by m=4, so the attention projections
    fall back to replicated — but the 4*18-wide MLP still shards. The
    rule table degrades per leaf, never the whole mesh."""
    net = _net(d_model=18, n_heads=3)
    specs = build_param_specs(net, 4)
    for name, vspecs in zip(net.vertex_names, specs):
        if isinstance(vspecs, dict) and name.endswith("_attn"):
            for k, s in vspecs.items():
                assert s == P(), (name, k, s)
    assert sharded_leaf_count(specs) > 0


def test_lstm_gate_spec_rules():
    lstm = text_generation_lstm(vocab_size=20, hidden=16).init()
    specs = build_param_specs(lstm, 2)
    gates = 0
    for lspecs in specs:
        if not isinstance(lspecs, dict) or "R" not in lspecs:
            for s in jax.tree.leaves(
                    lspecs, is_leaf=lambda x: isinstance(x, P)):
                assert s == P()     # embedding / dense head: replicated
            continue
        gates += 1
        assert lspecs["W"] == P(None, "model")
        assert lspecs["R"] == P(None, "model")
        assert lspecs["b"] == P("model")
    assert gates >= 1


def test_model_axis_refuses_averaging_and_accumulator():
    net = _net()
    with pytest.raises(ValueError, match="model-axis"):
        ParallelWrapper(net, mesh_shape=(2, 2), training_mode="averaging",
                        averaging_frequency=2)
    with pytest.raises(ValueError, match="model-sharded"):
        ParallelWrapper(net, mesh_shape=(2, 2),
                        gradient_accumulator=object())
    with pytest.raises(ValueError, match="mesh_shape"):
        ParallelWrapper(net, mesh_shape=(2, 2, 2))


# ------------------------------------------------------- training parity
@pytest.fixture(scope="module")
def dp_ref():
    """Flat 4-device data-parallel baseline (the pre-ISSUE-20 path)."""
    net = _net()
    ParallelWrapper(net, mesh_shape=(4,)).fit(_data(), epochs=1)
    return _flat(net)


@pytest.fixture(scope="module")
def tp22():
    """One (2, 2) training shared by the parity / bytes / save tests."""
    net = _net()
    ParallelWrapper(net, mesh_shape=(2, 2)).fit(_data(), epochs=1)
    return net


def test_41_mesh_bit_identical_to_flat_dp(dp_ref):
    """(4, 1) is the SAME program as the 1-D data mesh: m=1 leaves every
    spec P(), so the results must be bitwise equal, not just close."""
    net = _net()
    ParallelWrapper(net, mesh_shape=(4, 1)).fit(_data(), epochs=1)
    np.testing.assert_array_equal(_flat(net), dp_ref)


def test_22_mesh_tracks_dp_and_shrinks_replicas(dp_ref, tp22):
    d = _maxdiff(_flat(tp22), dp_ref)
    assert d < 1e-4, f"(2,2) diverged from dp: maxdiff {d}"
    full = int(dp_ref.nbytes)
    assert per_replica_bytes(tp22.params) < full
    assert per_replica_bytes(tp22.opt_state) < 2 * full
    # a (4, 1) replica holds all of it (Adam: two more copies); with the
    # embeddings and norms replicated, m=2 still frees over a sixth
    held = per_replica_bytes(tp22.params) + per_replica_bytes(tp22.opt_state)
    assert 1.2 * held < 3 * full, (held, full)


def test_22_composes_with_steps_per_dispatch_and_zero(dp_ref):
    net = _net()
    ParallelWrapper(net, mesh_shape=(2, 2), steps_per_dispatch=2,
                    zero_stage=2).fit(_data(), epochs=1)
    d = _maxdiff(_flat(net), dp_ref)
    assert d < 1e-4, f"(2,2)+spd2+zero2 diverged from dp: maxdiff {d}"


def test_write_model_gathers_model_sharded_params(tp22, tmp_path):
    """Satellite: a zip written from a tensor-parallel net is layout-free
    — restore on an unsharded process round-trips bitwise."""
    from deeplearning4j_tpu.util.serialization import (
        restore_computation_graph, write_model)
    path = str(tmp_path / "tp.zip")
    write_model(tp22, path)
    back = restore_computation_graph(path)
    np.testing.assert_array_equal(_flat(back), _flat(tp22))
    ref_opt = np.concatenate([np.asarray(l).ravel() for l in
                              jax.tree.leaves(host_gather(tp22.opt_state))])
    got_opt = np.concatenate([np.asarray(l).ravel() for l in
                              jax.tree.leaves(back.opt_state)])
    np.testing.assert_allclose(got_opt, ref_opt, atol=1e-6)


# -------------------------------------------------------- sharded decode
@pytest.fixture(scope="module")
def decode_pair():
    from deeplearning4j_tpu.serving.generation.programs import (
        GenerationConfig, GenerationProgramSet)
    net = _net(seed=3)
    cfg = dict(block_len=8, max_seq_len=16, decode_slots=2,
               prefill_batches=(1,))
    mesh = make_mesh((1, 2), ("data", "model"), jax.devices()[:2])
    rep = GenerationProgramSet(net, config=GenerationConfig(**cfg)).warm()
    sh = GenerationProgramSet(net, config=GenerationConfig(**cfg),
                              mesh=mesh).warm()
    return net, rep, sh


def _greedy_tokens(ps, n_decode=3):
    from deeplearning4j_tpu.serving.generation.programs import pack_prefill
    cache, key = ps.make_cache(), ps.fresh_key()
    prompt = np.zeros((1, 16), np.int32)
    prompt[0, :3] = [3, 5, 7]
    t, cache, key = ps.run_prefill(
        cache, pack_prefill(prompt, np.array([3], np.int32),
                            np.array([[1, 2]], np.int32),
                            np.array([0], np.int32),
                            np.zeros((1,), np.float32),
                            np.zeros((1,), np.int32)), key)
    out = [int(np.asarray(t)[0])]
    for i in range(n_decode):
        t, cache, key = ps.run_decode(
            cache, np.array([out[-1], 0], np.int32),
            np.array([3 + i, 0], np.int32),
            np.array([[1, 2], [0, 0]], np.int32),
            np.array([True, False]), key,
            np.zeros((2,), np.float32), np.zeros((2,), np.int32))
        out.append(int(np.asarray(t)[0]))
    return out


def test_sharded_decode_token_identical_and_pool_halved(decode_pair):
    _, rep, sh = decode_pair
    assert sh.model_shards == 2 and rep.model_shards == 1
    toks_rep, toks_sh = _greedy_tokens(rep), _greedy_tokens(sh)
    assert toks_rep == toks_sh, (toks_rep, toks_sh)
    assert sh.kv_pool_chip_bytes * 2 == rep.kv_pool_chip_bytes


def test_with_params_from_keeps_mesh_and_executables(decode_pair):
    from deeplearning4j_tpu.telemetry import xla_compile_count
    net, _, sh = decode_pair
    swapped = sh.with_params_from(_net(seed=9))
    assert swapped.model_shards == 2
    assert swapped.kv_pool_chip_bytes == sh.kv_pool_chip_bytes
    compiles0 = xla_compile_count()
    _greedy_tokens(swapped, n_decode=1)
    assert xla_compile_count() == compiles0, \
        "param swap on a sharded set must reuse the warmed executables"


def test_sharded_decode_refusals(decode_pair):
    from deeplearning4j_tpu.serving.generation.programs import (
        GenerationConfig, GenerationProgramSet)
    _, _, sh = decode_pair
    mesh = sh.mesh
    cfg = GenerationConfig(block_len=8, max_seq_len=16, decode_slots=2)
    lstm = text_generation_lstm(vocab_size=20, hidden=16).init()
    with pytest.raises(ValueError, match="paged"):
        GenerationProgramSet(lstm, config=cfg, mesh=mesh)
    odd = _net(d_model=18, n_heads=3)
    with pytest.raises(ValueError, match="n_heads"):
        GenerationProgramSet(odd, config=cfg, mesh=mesh)


# ---------------------------------------------------------- cost index
def test_cost_index_divides_by_model_axis():
    """A tp program's XLA cost counts the whole model; each chip runs
    1/m of it, so the per-chip MFU gauges must fold flops/m."""
    from deeplearning4j_tpu import telemetry
    from deeplearning4j_tpu.telemetry import MetricsRegistry
    from deeplearning4j_tpu.telemetry.perf import ProgramCostIndex
    reg = MetricsRegistry(enabled=True)
    prev = telemetry.set_registry(reg)
    try:
        idx = ProgramCostIndex()
        e = idx.register("fit/tp_step", flops_per_step=2e9,
                         bytes_per_step=1e6, model_axis_size=2,
                         timing_metric="t_ms")
        assert e.flops_per_step == pytest.approx(1e9)
        assert e.bytes_per_step == pytest.approx(5e5)
        assert e.model_axis_size == 2
        for _ in range(4):
            reg.histogram("t_ms").observe(2.0)
        row = {r["path"]: r for r in idx.fold(reg)}["fit/tp_step"]
        assert row["model_axis_size"] == 2
        # 1e9 per-chip flops / 2ms = 0.5 achieved TFLOP/s per chip
        assert row["achieved_tflops"] == pytest.approx(0.5, rel=1e-6)
    finally:
        telemetry.set_registry(prev)
