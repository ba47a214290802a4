"""Overlapped gradient synchronization (parallel/overlap.py): bucket
schedule packing, bucketed/fused pmean parity with the per-leaf sweep,
ParallelWrapper overlap-path parity (per-step, fused scan window, all
bucket sizes), the fused Pallas threshold-encode kernel vs the XLA path,
and the per-bucket collective telemetry/trace plumbing."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from deeplearning4j_tpu import MultiLayerNetwork, NeuralNetConfiguration
from deeplearning4j_tpu import telemetry
from deeplearning4j_tpu.datasets.dataset import ListDataSetIterator
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.optimize.updaters import Adam, Sgd
from deeplearning4j_tpu.parallel import ParallelWrapper
from deeplearning4j_tpu.parallel.mesh import make_mesh, shard_map
from deeplearning4j_tpu.parallel.overlap import (build_bucket_schedule,
                                                 bucketed_pmean, fused_pmean,
                                                 profile_schedule)

R = np.random.default_rng(23)


# ------------------------------------------------------------- scheduling
def test_bucket_schedule_covers_every_leaf_once():
    tree = {"a": jnp.zeros((100,)), "b": jnp.zeros((3, 7)),
            "c": (jnp.zeros((50,)), jnp.zeros((2, 2, 2)))}
    sched = build_bucket_schedule(tree, bucket_bytes=256)
    seen = sorted(i for b in sched.buckets for i in b.indices)
    assert seen == list(range(sched.num_leaves))
    assert sched.total_bytes == sum(
        int(np.prod(s)) * dt.itemsize
        for s, dt in zip(sched.leaf_shapes, sched.leaf_dtypes))


def test_bucket_schedule_reverse_order_and_singletons():
    """Buckets pack from the LAST leaf backwards (backward-pass production
    order) and a leaf >= bucket_bytes ships as its own singleton."""
    leaves = [jnp.zeros((4,)), jnp.zeros((1000,)), jnp.zeros((4,)),
              jnp.zeros((4,))]
    sched = build_bucket_schedule(leaves, bucket_bytes=64)
    # bucket 0 holds the tail leaves (3, 2), the 1000-elem leaf is a
    # singleton, leaf 0 closes the schedule
    assert sched.buckets[0].indices == (3, 2)
    assert sched.buckets[1].indices == (1,)   # the big leaf, alone
    assert sched.buckets[2].indices == (0,)


def test_bucket_schedule_separates_dtypes():
    leaves = [jnp.zeros((8,), jnp.float32), jnp.zeros((8,), jnp.bfloat16),
              jnp.zeros((8,), jnp.float32)]
    sched = build_bucket_schedule(leaves, bucket_bytes=1 << 20)
    for b in sched.buckets:
        dts = {sched.leaf_dtypes[i] for i in b.indices}
        assert len(dts) == 1, b


def test_bucket_schedule_rejects_empty_and_bad_bytes():
    with pytest.raises(ValueError, match="empty"):
        build_bucket_schedule([], 1024)
    with pytest.raises(ValueError, match="bucket_bytes"):
        build_bucket_schedule([jnp.zeros((4,))], 0)


# ------------------------------------------------- pmean grouping parity
def _rand_tree():
    return {"w1": jnp.asarray(R.normal(size=(64, 32)).astype(np.float32)),
            "b1": jnp.asarray(R.normal(size=(32,)).astype(np.float32)),
            "w2": jnp.asarray(R.normal(size=(32, 8)).astype(np.float32)),
            "b2": jnp.asarray(R.normal(size=(8,)).astype(np.float32))}


def _run_on_mesh(fn, tree):
    mesh = make_mesh()
    leaves, treedef = jax.tree.flatten(tree)
    wrapped = shard_map(
        lambda *ls: tuple(jax.tree.leaves(
            fn(jax.tree.unflatten(treedef, ls)))),
        mesh=mesh, in_specs=(P(),) * len(leaves),
        out_specs=(P(),) * len(leaves), check_vma=False)
    out = jax.jit(wrapped)(*leaves)
    return jax.tree.unflatten(treedef, out)


def test_bucketed_pmean_bit_identical_to_per_leaf_sweep():
    """Grouping must not change any element's reduction: bucketed_pmean
    (all bucket sizes, incl. one-giant-bucket and per-leaf) == the
    per-leaf tree.map(pmean) sweep, bitwise, on the 8-device mesh."""
    tree = _rand_tree()
    ref = _run_on_mesh(
        lambda t: jax.tree.map(lambda a: jax.lax.pmean(a, "data"), t), tree)
    for bucket_bytes in (1, 2048, 1 << 30):
        sched = build_bucket_schedule(tree, bucket_bytes)
        got = _run_on_mesh(lambda t: bucketed_pmean(t, sched, "data"), tree)
        for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(got)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_fused_pmean_bit_identical_to_per_leaf_sweep():
    tree = _rand_tree()
    ref = _run_on_mesh(
        lambda t: jax.tree.map(lambda a: jax.lax.pmean(a, "data"), t), tree)
    got = _run_on_mesh(lambda t: fused_pmean(t, "data"), tree)
    for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_bucketed_pmean_rejects_mismatched_tree():
    tree = _rand_tree()
    sched = build_bucket_schedule(tree, 2048)
    other = {"x": jnp.zeros((4,))}
    with pytest.raises(ValueError, match="schedule"):
        bucketed_pmean(other, sched, "data")


# ------------------------------------------------ ParallelWrapper parity
def _net(seed=7, updater=None):
    conf = (NeuralNetConfiguration(seed=seed, updater=updater or Sgd(0.1))
            .list(DenseLayer(n_in=6, n_out=24, activation="tanh"),
                  DenseLayer(n_in=24, n_out=16, activation="tanh"),
                  OutputLayer(n_out=3, activation="softmax", loss="mcxent"))
            .build())
    return MultiLayerNetwork(conf).init()


def _data(n=128):
    x = R.normal(size=(n, 6)).astype(np.float32)
    yi = (x.sum(-1) > 0).astype(int) + (x[:, 0] > 1).astype(int)
    return x, np.eye(3, dtype=np.float32)[yi]


def test_overlap_sync_parity_all_bucket_sizes():
    """Same seed -> bit-identical params after N steps for every bucket
    size (per-leaf, default, one-bucket), and the overlap path tracks the
    GSPMD sync path."""
    x, y = _data()
    it = ListDataSetIterator(features=x, labels=y, batch_size=64)
    ref = _net()
    ParallelWrapper(ref).fit(it, epochs=3)
    ref_flat = np.asarray(ref.params_flat())
    flats = []
    for bucket_bytes in (1, 4 * 2 ** 20, 1 << 30):
        it.reset()
        net = _net()
        ParallelWrapper(net, overlap_sync=True,
                        bucket_bytes=bucket_bytes).fit(it, epochs=3)
        flats.append(np.asarray(net.params_flat()))
    for f in flats[1:]:
        np.testing.assert_array_equal(flats[0], f)
    # vs the GSPMD path: same math, different collective plumbing — on
    # the CPU test backend this is elementwise-identical too, but the
    # pinned contract is numerical equivalence
    np.testing.assert_allclose(flats[0], ref_flat, atol=1e-6)


def test_overlap_window_bit_identical_to_per_step():
    """K fused overlap steps (steps_per_dispatch) == K per-step overlap
    dispatches, bitwise — the grad_sync seam rides train_step_math into
    the scan body structurally."""
    x, y = _data(128)
    a = _net(updater=Adam(5e-3))
    b = _net(updater=Adam(5e-3))
    b.set_params_flat(a.params_flat())
    it = ListDataSetIterator(features=x, labels=y, batch_size=32)
    ParallelWrapper(a, overlap_sync=True, bucket_bytes=2048).fit(it, epochs=2)
    it.reset()
    ParallelWrapper(b, overlap_sync=True, bucket_bytes=2048,
                    steps_per_dispatch=2).fit(it, epochs=2)
    np.testing.assert_array_equal(np.asarray(a.params_flat()),
                                  np.asarray(b.params_flat()))


def test_overlap_sync_converges():
    x, y = _data(256)
    it = ListDataSetIterator(features=x, labels=y, batch_size=64)
    net = _net(updater=Adam(5e-3))
    pw = ParallelWrapper(net, overlap_sync=True)
    s0 = net.score(x, y)
    pw.fit(it, epochs=12)
    assert net.score(x, y) < s0
    assert net.evaluate(x, y).accuracy() > 0.8


def test_sync_remainder_batch_dispatches_replicated():
    """Regression: a batch whose size does not tile the mesh (the
    end-of-epoch remainder the prefetcher ships unsharded) raised the
    divisibility error on BOTH sync paths — shard_map (overlap) and
    jit+in_shardings (GSPMD) each enforce it — killing the epoch. It
    must dispatch through the replicated-feed program instead, with the
    identical update, and the single-net fit is the ground truth."""
    from deeplearning4j_tpu.datasets.dataset import ListDataSetIterator as LI
    x, y = _data(100)           # batch 64 -> remainder 36 (36 % 8 != 0)
    single = _net()
    single.fit(iterator=LI(features=x, labels=y, batch_size=64), epochs=2,
               async_prefetch=False)
    for kw in ({}, {"overlap_sync": True, "bucket_bytes": 2048}):
        it = LI(features=x, labels=y, batch_size=64)
        net = _net()
        pw = ParallelWrapper(net, **kw)
        pw.fit(it, epochs=2)
        assert pw._remainder_step is not None    # the remainder took it
        np.testing.assert_allclose(np.asarray(net.params_flat()),
                                   np.asarray(single.params_flat()),
                                   rtol=2e-5, atol=2e-6)


def test_sync_remainder_window_dispatches_replicated():
    """Window variant: uniformly non-divisible batches stack into regular
    windows, which neither fused sync program can tile — the replicated
    window program must take the dispatch on the plain and overlap
    paths, bit-identical to each other."""
    x, y = _data(120)           # batches of 60; 60 % 8 != 0
    it = ListDataSetIterator(features=x, labels=y, batch_size=60)
    ref = _net()
    pw_ref = ParallelWrapper(ref, steps_per_dispatch=2)
    pw_ref.fit(it, epochs=2)
    assert pw_ref._remainder_window_step is not None
    it.reset()
    net = _net()
    pw = ParallelWrapper(net, overlap_sync=True, bucket_bytes=2048,
                         steps_per_dispatch=2)
    pw.fit(it, epochs=2)
    assert pw._remainder_window_step is not None
    np.testing.assert_array_equal(np.asarray(net.params_flat()),
                                  np.asarray(ref.params_flat()))


def test_overlap_rejects_accumulator():
    from deeplearning4j_tpu.parallel.accumulation import PsumAccumulator
    with pytest.raises(ValueError, match="overlap_sync"):
        ParallelWrapper(_net(), overlap_sync=True,
                        gradient_accumulator=PsumAccumulator())


def test_overlap_rejects_averaging_path():
    """Regression: overlap_sync on the K-step averaging path was silently
    ignored (no bucketing, no metrics) — it must refuse like the
    accumulator combination does."""
    with pytest.raises(ValueError, match="averaging"):
        ParallelWrapper(_net(), overlap_sync=True,
                        training_mode="averaging", averaging_frequency=4)
    # averaging_frequency=1 IS the sync path: allowed
    ParallelWrapper(_net(), overlap_sync=True, training_mode="averaging",
                    averaging_frequency=1)


def test_encode_signs_multidim_takes_xla_fallback():
    """Regression: a kernel-eligible leading dim on a 2-D residual was
    routed into the Pallas kernel, which only serves the flat 1-D view —
    the public dispatcher must fall back instead of raising."""
    from deeplearning4j_tpu.ops.compression import threshold_encode_signs
    r = jnp.asarray(R.normal(0, 2e-3, (70000, 4)).astype(np.float32))
    signs, res = threshold_encode_signs(r, 1e-3)
    assert signs.shape == r.shape
    t = jnp.asarray(1e-3, r.dtype)
    s_ref = jnp.where(jnp.abs(r) >= t, jnp.sign(r), jnp.zeros((), r.dtype))
    np.testing.assert_array_equal(np.asarray(signs),
                                  np.asarray(s_ref.astype(jnp.int8)))
    np.testing.assert_array_equal(np.asarray(res), np.asarray(r - s_ref * t))


def test_overlap_collective_launch_telemetry():
    reg = telemetry.get_registry()
    telemetry.reset()
    x, y = _data(128)
    it = ListDataSetIterator(features=x, labels=y, batch_size=64)
    net = _net()
    pw = ParallelWrapper(net, overlap_sync=True, bucket_bytes=512)
    pw.fit(it, epochs=1)
    n_buckets = len(pw._bucket_schedule)
    assert n_buckets >= 2
    assert reg.gauge("parallel.bucket_count").value == n_buckets
    # 2 steps/epoch x (grad buckets + the fused state/loss launch)
    assert reg.counter("parallel.collective_launches").value == \
        2 * (n_buckets + 1)


# ------------------------------------------- profiling + trace folding
def test_profile_schedule_emits_per_bucket_collective_events(tmp_path):
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    import trace2summary

    reg = telemetry.get_registry()
    telemetry.reset()
    tree = _rand_tree()
    sched = build_bucket_schedule(tree, 2048)
    with telemetry.span("fit"):
        out = profile_schedule(make_mesh(), sched)
    assert len(out["buckets"]) == len(sched)
    assert out["collective_ms"] > 0
    assert reg.gauge("parallel.collective_ms").value == \
        pytest.approx(out["collective_ms"], rel=0.01)
    trace = tmp_path / "trace.json"
    reg.write_chrome_trace(str(trace))
    rows = trace2summary.summarize(trace2summary.load_events(str(trace)))
    phases = {r["phase"] for r in rows}
    # every bucket's psum folds into its OWN [bucket_psum:i] phase,
    # nested under the span it ran in
    for i in range(len(sched)):
        assert f"fit/[bucket_psum:{i}]" in phases, phases


# --------------------------------------------------- pallas fused encode
def test_pallas_encode_bit_identical_to_xla_fallback():
    from deeplearning4j_tpu.ops.compression import threshold_encode_signs
    from deeplearning4j_tpu.ops.pallas_compression import (
        fused_threshold_encode_applicable, threshold_encode_pallas)

    n_block = 1 << 16
    for n in (n_block, n_block + 77, 2 * n_block + 12345):
        for dt in (jnp.float32, jnp.bfloat16):
            assert fused_threshold_encode_applicable(n, dt)
            r = jnp.asarray(R.normal(0, 2e-3, (n,)), dt)
            t = jnp.asarray(1e-3, r.dtype)
            s_ref = jnp.where(jnp.abs(r) >= t, jnp.sign(r),
                              jnp.zeros((), r.dtype))
            signs, res = threshold_encode_pallas(r, 1e-3)
            assert signs.dtype == jnp.int8 and res.dtype == r.dtype
            np.testing.assert_array_equal(
                np.asarray(signs), np.asarray(s_ref.astype(jnp.int8)))
            np.testing.assert_array_equal(
                np.asarray(res), np.asarray(r - s_ref * t))
            # the front-door dispatcher routes to the same result
            signs2, res2 = threshold_encode_signs(r, 1e-3)
            np.testing.assert_array_equal(np.asarray(signs),
                                          np.asarray(signs2))
            np.testing.assert_array_equal(np.asarray(res), np.asarray(res2))


def test_pallas_encode_gating():
    from deeplearning4j_tpu.ops.pallas_compression import \
        fused_threshold_encode_applicable as app
    assert not app(100, jnp.float32)          # below one block
    assert not app(1 << 20, jnp.int8)         # non-float dtype
    old = os.environ.get("DL4J_TPU_FUSED_ENCODE")
    try:
        os.environ["DL4J_TPU_FUSED_ENCODE"] = "0"
        assert not app(1 << 20, jnp.float32)  # kill switch
    finally:
        if old is None:
            os.environ.pop("DL4J_TPU_FUSED_ENCODE", None)
        else:
            os.environ["DL4J_TPU_FUSED_ENCODE"] = old


def test_encoded_accumulator_identical_with_and_without_kernel():
    """EncodedAccumulator's dense combine must produce the SAME update and
    residual whether the Pallas kernel or the XLA fallback encodes —
    pinned at a kernel-eligible size on the 8-device mesh."""
    from deeplearning4j_tpu.parallel.accumulation import EncodedAccumulator

    n, sz = 8, 1 << 16
    mesh = make_mesh()
    acc = EncodedAccumulator(threshold=1e-3)
    grads = jnp.asarray(R.normal(0, 2e-3, (n, sz)).astype(np.float32))
    state = jnp.zeros((n, sz), jnp.float32)

    def worker(g, s):
        u, ns = acc.combine(g[0], s[0], axis="data")
        return u[None], ns[None]

    fn = jax.jit(shard_map(worker, mesh=mesh,
                           in_specs=(P("data"), P("data")),
                           out_specs=(P("data"), P("data")),
                           check_vma=False))
    u_pallas, ns_pallas = fn(grads, state)
    old = os.environ.get("DL4J_TPU_FUSED_ENCODE")
    try:
        os.environ["DL4J_TPU_FUSED_ENCODE"] = "0"
        fn2 = jax.jit(shard_map(worker, mesh=mesh,
                                in_specs=(P("data"), P("data")),
                                out_specs=(P("data"), P("data")),
                                check_vma=False))
        u_xla, ns_xla = fn2(grads, state)
    finally:
        if old is None:
            os.environ.pop("DL4J_TPU_FUSED_ENCODE", None)
        else:
            os.environ["DL4J_TPU_FUSED_ENCODE"] = old
    np.testing.assert_array_equal(np.asarray(u_pallas), np.asarray(u_xla))
    np.testing.assert_array_equal(np.asarray(ns_pallas), np.asarray(ns_xla))
