"""Fused flash-attention kernels (ops/pallas_attention.py): parity against
the XLA reference path (parallel/ring_attention.attention) across
causal x mask x dtype, gradients included, plus the layer-level seam.
Interpreter mode on CPU (conftest sets DL4J_TPU_FUSED_ATTN_INTERPRET)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.ops import pallas_attention as pa
from deeplearning4j_tpu.ops.pallas_attention import (flash_attention,
                                                     fused_attention_applicable)
from deeplearning4j_tpu.parallel.ring_attention import attention

R = np.random.default_rng(11)
B, H, T, D = 2, 2, 256, 128


def _qkv(dtype=jnp.float32):
    return tuple(jnp.asarray(R.normal(size=(B, H, T, D)), dtype)
                 for _ in range(3))


def _mask():
    lens = R.integers(T // 4, T, B)
    return jnp.asarray((np.arange(T)[None, :] < lens[:, None])
                       .astype(np.float32))


def test_applicability_probe():
    assert fused_attention_applicable(B, H, T, D, jnp.float32)
    assert fused_attention_applicable(B, H, T, D, jnp.bfloat16)
    # GPT-2-class head dims ride Mosaic's minor-dim padding (round-5)
    assert fused_attention_applicable(B, H, T, 64, jnp.float32)
    assert fused_attention_applicable(B, H, T, 96, jnp.float32)
    assert not fused_attention_applicable(B, H, T, 80, jnp.float32)   # odd D
    assert not fused_attention_applicable(B, H, 200, D, jnp.float32)  # T%128
    assert not fused_attention_applicable(B, H, 128, D, jnp.float32)  # tiny T
    assert not fused_attention_applicable(B, H, T, D, jnp.float64)


@pytest.mark.parametrize("d", [
    64,
    # d=96 in the slow lane (ISSUE 14 tier-1 budget reclaim): ~5s second
    # head-dim config; d=64 keeps the small-head-dim kernel path tier-1
    pytest.param(96, marks=pytest.mark.slow),
])
def test_small_head_dim_parity(d):
    """D=64/96 (the common transformer head dims) engage the fused path
    and match the XLA reference, gradients included."""
    q, k, v = (jnp.asarray(R.normal(size=(B, H, T, d)), jnp.float32)
               for _ in range(3))
    km = _mask()
    ours = flash_attention(q, k, v, causal=True, key_mask=km)
    ref = attention(q, k, v, causal=True, key_mask=km)
    np.testing.assert_allclose(np.asarray(ours), np.asarray(ref), atol=2e-5)

    def lf(fn):
        def loss(q, k, v):
            out = fn(q, k, v, causal=True, key_mask=km)
            return jnp.sum(out * out)
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    for name, a, b in zip("qkv", lf(flash_attention), lf(attention)):
        rel = (float(jnp.max(jnp.abs(a - b)))
               / (float(jnp.max(jnp.abs(b))) + 1e-9))
        assert rel < 1e-4, (name, rel)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("masked", [False, True])
def test_forward_parity(causal, masked):
    q, k, v = _qkv()
    km = _mask() if masked else None
    ours = flash_attention(q, k, v, causal=causal, key_mask=km)
    ref = attention(q, k, v, causal=causal, key_mask=km)
    np.testing.assert_allclose(np.asarray(ours), np.asarray(ref), atol=2e-5)


def test_gradient_parity_causal_masked():
    q, k, v = _qkv()
    km = _mask()

    def lf(fn):
        def loss(q, k, v):
            out = fn(q, k, v, causal=True, key_mask=km)
            return jnp.sum(out * out)
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    g_fused = lf(flash_attention)
    g_ref = lf(attention)
    for name, a, b in zip("qkv", g_fused, g_ref):
        rel = (float(jnp.max(jnp.abs(a - b)))
               / (float(jnp.max(jnp.abs(b))) + 1e-9))
        assert rel < 1e-4, (name, rel)


def test_asymmetric_blocks_parity_t1024():
    """A non-causal call at T>=1024 keeps the ASYMMETRIC default (BQ=512,
    BK=1024); a causal one, the config every real model run uses, takes
    256 x 256 tiles inside one resident block. Parity incl. gradients
    guards kernel edits that are only correct for one of them."""
    from deeplearning4j_tpu.ops.pallas_attention import _blocks, _resident
    assert _blocks(1024) == (512, 1024)
    assert _blocks(1024, causal=True) == (256, 256)
    assert _resident(1024, 256, 256, True) == (1024, 1024)
    T2 = 1024
    q, k, v = (jnp.asarray(R.normal(size=(1, 2, T2, 64)), jnp.float32)
               for _ in range(3))
    km = jnp.asarray((np.arange(T2)[None, :] <
                      np.asarray([700, 1024])[:, None]).astype(np.float32))
    km = km[:1]
    ours = flash_attention(q, k, v, causal=True, key_mask=km)
    ref = attention(q, k, v, causal=True, key_mask=km)
    np.testing.assert_allclose(np.asarray(ours), np.asarray(ref), atol=2e-5)

    def lf(fn):
        def loss(q, k, v):
            return jnp.sum(fn(q, k, v, causal=True, key_mask=km) ** 2)
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    for name, a, b in zip("qkv", lf(flash_attention), lf(attention)):
        rel = (float(jnp.max(jnp.abs(a - b)))
               / (float(jnp.max(jnp.abs(b))) + 1e-9))
        assert rel < 1e-4, (name, rel)


def test_bf16_io_close_to_f32():
    qf, kf, vf = _qkv(jnp.float32)
    q, k, v = (a.astype(jnp.bfloat16) for a in (qf, kf, vf))
    out_bf = flash_attention(q, k, v, causal=True)
    out_f = flash_attention(qf, kf, vf, causal=True)
    assert out_bf.dtype == jnp.bfloat16
    # f32 accumulation + f32 softmax recurrence: bf16 operand rounding
    # of p per block compounds only mildly across T/BK updates
    np.testing.assert_allclose(np.asarray(out_bf, np.float32),
                               np.asarray(out_f), atol=0.05)


def test_fully_masked_row_is_uniform_not_nan():
    q, k, v = _qkv()
    km = jnp.zeros((B, T), jnp.float32)     # everything masked
    out = flash_attention(q, k, v, key_mask=km)
    ref = jnp.mean(v, axis=2, keepdims=True)  # uniform attention
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(np.asarray(out),
                               np.broadcast_to(np.asarray(ref), out.shape),
                               atol=2e-5)


def test_layer_routes_through_fused_path(monkeypatch):
    """SelfAttentionLayer parity fused-vs-XLA through the layer seam
    (Dh = n_out/n_heads = 128 makes the probe pass)."""
    from deeplearning4j_tpu.nn.inputs import InputType
    from deeplearning4j_tpu.nn.layers import SelfAttentionLayer

    layer = SelfAttentionLayer(n_in=16, n_out=256, n_heads=2, causal=True)
    params, state = layer.init(jax.random.PRNGKey(0),
                               InputType.recurrent(16, T), jnp.float32)
    x = jnp.asarray(R.normal(size=(2, T, 16)), jnp.float32)
    outs = {}
    for flag in ("1", "0"):
        monkeypatch.setenv("DL4J_TPU_FUSED_ATTENTION", flag)
        out, _ = layer.apply(params, state, x)
        outs[flag] = np.asarray(out)
    np.testing.assert_allclose(outs["1"], outs["0"], atol=3e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_multi_block_grid_parity(causal):
    """T=768 -> _block(768)=256 -> a 3x3 block grid: exercises the
    online-softmax (acc,m,l) rescale carry across k-blocks, the causal
    block-skip predicate, and cross-block dq/dkv accumulation — logic a
    single-block T=256 test never touches. Interpreter mode = f32-exact."""
    T2 = 768
    q, k, v = (jnp.asarray(R.normal(size=(1, 2, T2, 128)), jnp.float32)
               for _ in range(3))
    lens = R.integers(T2 // 4, T2, 1)
    km = jnp.asarray((np.arange(T2)[None, :] < lens[:, None])
                     .astype(np.float32))
    for mask in (None, km):
        ours = flash_attention(q, k, v, causal=causal, key_mask=mask)
        ref = attention(q, k, v, causal=causal, key_mask=mask)
        np.testing.assert_allclose(np.asarray(ours), np.asarray(ref),
                                   atol=3e-5,
                                   err_msg=f"mask={mask is not None}")

    def lf(fn):
        def loss(q, k, v):
            return jnp.sum(fn(q, k, v, causal=causal, key_mask=km) ** 2)
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", lf(flash_attention), lf(attention)):
        rel = (float(jnp.max(jnp.abs(a - b)))
               / (float(jnp.max(jnp.abs(b))) + 1e-9))
        assert rel < 1e-4, (name, rel)


def test_kernels_split_per_device_on_a_mesh():
    """A program traced for several devices must run the kernels per
    device block instead of refusing to lower them (on a TPU a bare Mosaic
    call under a multi-device jit raises "cannot be automatically
    partitioned"; the CPU interpreter path hides that, so this pins the
    split RULE): under the mesh tracing context, forward+backward on a
    (data, model) mesh, masked and causal, is bit-equal to the one-device
    program and keeps the (batch, heads) split — also from inside a
    shard_map that is already manual over ``data`` (the overlap/ZeRO step
    on a 2-D mesh); a head count the model axis does not divide repeats
    the work on that axis instead of mis-splitting it."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    B, H, T, D = 2, 2, 128, 64
    q, k, v = (jnp.asarray(R.standard_normal((B, H, T, D)) * 0.3, jnp.float32)
               for _ in range(3))
    mask = jnp.asarray(R.random((B, T)) > 0.2)

    def fwd(q, k, v, mask):
        return flash_attention(q, k, v, causal=True, key_mask=mask)
    grad = jax.grad(lambda *a: jnp.sum(fwd(*a) ** 2), argnums=(0, 1, 2))
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("data", "model"))
    bh = NamedSharding(mesh, P("data", "model"))
    with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
        got = jax.jit(grad, in_shardings=(bh, bh, bh, NamedSharding(
            mesh, P("data"))))(q, k, v, mask)
        inside_manual_data = jax.jit(jax.shard_map(
            fwd, mesh=mesh, in_specs=(P("data"),) * 4, out_specs=P("data"),
            axis_names={"data"}, check_vma=False))(q, k, v, mask)
    for a, b in zip(got, jax.jit(grad)(q, k, v, mask)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert tuple(a.sharding.spec)[:2] == ("data", "model")
    want = np.asarray(jax.jit(fwd)(q, k, v, mask))
    np.testing.assert_array_equal(np.asarray(inside_manual_data), want)
    mesh3 = Mesh(np.array(jax.devices()[:6]).reshape(2, 3), ("data", "model"))
    with jax.sharding.use_abstract_mesh(mesh3.abstract_mesh):
        uneven = jax.jit(fwd, in_shardings=NamedSharding(mesh3, P("data")))(
            q, k, v, mask)
    np.testing.assert_array_equal(np.asarray(uneven), want)


# ------------------------------------------------ the causal tile schedule
def _rel_err(a, b):
    return float(jnp.max(jnp.abs(a - b))) / (float(jnp.max(jnp.abs(b))) + 1e-9)


def _grads(fn, q, k, v, **kw):
    return jax.grad(lambda q, k, v: jnp.sum(fn(q, k, v, **kw) ** 2),
                    argnums=(0, 1, 2))(q, k, v)


@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("t", [256, 768, 1024, 2048])
@pytest.mark.parametrize("masked", [False, True])
def test_causal_parity_across_schedules(masked, t, d):
    """Forward and gradients against the XLA reference wherever the causal
    schedule changes shape: one tile (256), a 3 x 3 triangle in one
    resident block (768), the cells' 4 x 4 (1024), and two resident blocks
    a side with a full block under the diagonal (2048), at the head dim
    the cells run and at a whole lane tile, with and without the key mask
    of a padded batch."""
    rng = np.random.default_rng(1000 * t + d + masked)
    q, k, v = (jnp.asarray(rng.normal(size=(1, 1, t, d)), jnp.float32)
               for _ in range(3))
    km = None
    if masked:
        km = jnp.asarray((np.arange(t)[None, :] < int(0.7 * t))
                         .astype(np.float32))
    ours = flash_attention(q, k, v, causal=True, key_mask=km)
    ref = attention(q, k, v, causal=True, key_mask=km)
    np.testing.assert_allclose(np.asarray(ours), np.asarray(ref), atol=3e-5)
    for name, a, b in zip("qkv",
                          _grads(flash_attention, q, k, v, causal=True,
                                 key_mask=km),
                          _grads(attention, q, k, v, causal=True,
                                 key_mask=km)):
        assert _rel_err(a, b) < 1e-4, (name, _rel_err(a, b))


def test_causal_bf16_parity_at_the_cells_shape():
    """bfloat16 operands at T=1024, D=64 (one head of the benchmark's
    cells): output and gradients stay within bfloat16's rounding of the
    float32 reference on the same (rounded) inputs."""
    rng = np.random.default_rng(5)
    qf, kf, vf = (jnp.asarray(rng.normal(size=(1, 2, 1024, 64)) * 0.5,
                              jnp.bfloat16).astype(jnp.float32)
                  for _ in range(3))
    q, k, v = (a.astype(jnp.bfloat16) for a in (qf, kf, vf))
    out = flash_attention(q, k, v, causal=True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(attention(qf, kf, vf, causal=True)),
                               atol=0.03)

    def loss(fn):
        return lambda q, k, v: jnp.sum(
            fn(q, k, v, causal=True).astype(jnp.float32) ** 2)
    got = jax.grad(loss(flash_attention), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(attention), argnums=(0, 1, 2))(qf, kf, vf)
    for name, a, b in zip("qkv", got, want):
        assert a.dtype == jnp.bfloat16
        assert _rel_err(a.astype(jnp.float32), b) < 0.03, name


@pytest.mark.parametrize("first,last", [(256, 512), (0, 256)])
def test_key_mask_over_a_whole_diagonal_tile(first, last):
    """A padded batch's key mask that blanks every key of one diagonal
    tile: the mask applies on the tiles under it too (parity with the
    reference, gradients included), and where it leaves a query no key at
    all (the first tile's own queries) the row stays finite: uniform over
    what it visited, not NaN."""
    t = 1024
    rng = np.random.default_rng(first)
    q, k, v = (jnp.asarray(rng.normal(size=(1, 2, t, 64)), jnp.float32)
               for _ in range(3))
    keep = np.ones((1, t), np.float32)
    keep[:, first:last] = 0.0
    km = jnp.asarray(keep)
    ours = np.asarray(flash_attention(q, k, v, causal=True, key_mask=km))
    ref = np.asarray(attention(q, k, v, causal=True, key_mask=km))
    assert np.isfinite(ours).all()
    defined = slice(last if first == 0 else 0, t)   # queries with a key left
    np.testing.assert_allclose(ours[:, :, defined], ref[:, :, defined],
                               atol=3e-5)
    if first:
        for name, a, b in zip("qkv",
                              _grads(flash_attention, q, k, v, causal=True,
                                     key_mask=km),
                              _grads(attention, q, k, v, causal=True,
                                     key_mask=km)):
            assert _rel_err(a, b) < 1e-4, name


def test_tile_schedule_at_the_cells_context():
    """T=1024 causal: at most 0.65 of the square is visited, only tiles
    the diagonal crosses pay the mask, every visited tile strictly under
    the diagonal goes unmasked; a non-causal call visits every tile of
    today's blocks and masks none."""
    visited, masked, total = pa.tile_schedule(1024, True)
    assert (visited, masked, total) == (10, 4, 16)
    assert visited / total <= 0.65
    bq, bk = pa._blocks(1024, True)
    for r in range(0, 1024, bq):
        for c in range(0, 1024, bk):
            kind = pa.tile_kind(r, bq, c, bk)
            crossed = c <= r + bq - 1 and c + bk - 1 > r
            assert (kind == pa.DIAG) == crossed, (r, c, kind)
            if c + bk - 1 <= r:
                assert kind == pa.FULL, (r, c, kind)
    assert pa._blocks(1024, False) == (512, 1024)
    assert pa.tile_schedule(1024, False) == (2, 0, 2)
    assert pa.tile_schedule(2048, False) == (8, 0, 8)
    assert pa.tile_schedule(768, True) == (6, 3, 9)


def _walk(T, bq, bk):
    """What the kernels do with a [T, T] causal square, position by
    position: 0 not computed, 1 computed without a mask, 2 computed under
    the mask. The grid's decision (``_tile_rule`` on resident blocks,
    ``_for_block``) and the static walk inside a block (``_visits``),
    replayed with the same functions on plain ints."""
    rq, rk = pa._resident(T, bq, bk, True)
    seen = np.zeros((T, T), np.int8)
    for i in range(T // rq):
        for j in range(T // rk):
            skip, full = pa._tile_rule(i * rq, rq, j * rk, rk)
            if skip:
                continue
            assert j <= pa._last_col_block(i, rq, rk)      # the clamps
            assert i >= pa._first_row_block(j, rq, rk)     # fetch it
            for c0, r_lo, masked in pa._visits(not full, rq, rk, bq, bk):
                rows = slice(i * rq + r_lo, (i + 1) * rq)
                cols = slice(j * rk + c0, j * rk + c0 + bk)
                assert (seen[rows, cols] == 0).all()        # once only
                seen[rows, cols] = 1
                seen[i * rq + r_lo:i * rq + r_lo + masked, cols] = 2
    return seen


@pytest.mark.parametrize("T,bq,bk", [
    (T, bq, bk) for T in (128, 256, 384, 512, 768, 1024, 1280, 1536, 2048,
                          4096)
    for bq in (128, 256, 512, 1024) for bk in (128, 256, 512, 1024)
    if T % bq == 0 and T % bk == 0
    and ((bq, bk) == pa._blocks(T, True) or T in (1024, 2048))])
def test_no_needed_tile_is_skipped(T, bq, bk):
    """Brute force over every (T, BQ, BK) ``_blocks`` can return (its
    defaults at each T; at T = 1024 and 2048 every pair an override or a
    cached decision could name): no position with col <= row is left out,
    and no position with col > row is computed without the mask."""
    seen = _walk(T, bq, bk)
    row, col = np.indices((T, T))
    assert (seen[col <= row] > 0).all()
    assert (seen[col > row] != 1).all()
    if (bq, bk) == pa._blocks(T, True):
        visited, masked, total = pa.tile_schedule(T, True)
        assert (seen > 0).sum() == visited * bq * bk
        assert (seen == 2).sum() == masked * bq * bk
