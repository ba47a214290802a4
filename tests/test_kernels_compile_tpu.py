"""AOT-compile every registered kernel's FUSED implementation for the v5e.

The CPU suite runs the Pallas kernels in the interpreter, which accepts
block shapes the Mosaic TPU compiler refuses (PR 17's int8 matmul and
conv1x1 kernels passed every interpreter test and could not compile for
the chip). libtpu hands out a compile-only v5e topology with no device
attached, so the real compiler runs here: "the compiler refuses it" is a
tier-1 failure instead of a chip-budget discovery. This proves COMPILES,
not RUNS — ``chip_smoke.py`` runs the parity pins on the chip.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.ops import (grouped_matmul, pallas_attention,
                                    pallas_compression,
                                    pallas_linear_attention, pallas_lstm,
                                    pallas_paged_attention)
from deeplearning4j_tpu.ops import kernels
from deeplearning4j_tpu.ops.kernels import conv, quantized

f32, bf16, i8 = jnp.float32, jnp.bfloat16, jnp.int8


@pytest.fixture(scope="module")
def v5e_devices():
    """The four devices of a compile-only v5e 2x2 topology."""
    try:
        from jax.experimental import topologies
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices
    except Exception as e:      # no libtpu / no topology support here
        pytest.skip(f"libtpu gives no compile-only v5e topology: {e!r}")


@pytest.fixture(scope="module")
def v5e(v5e_devices):
    """Replicated sharding on one of those devices."""
    return NamedSharding(Mesh(np.array(v5e_devices[:1]), ("x",)), P())


@pytest.fixture(autouse=True)
def _no_interpreter(monkeypatch):
    # the modules pick the interpreter from the (CPU) default backend;
    # the lowering here targets the TPU, so take the Mosaic path
    for mod in (pallas_attention, pallas_lstm, pallas_compression,
                pallas_paged_attention, pallas_linear_attention, quantized,
                conv, grouped_matmul):
        monkeypatch.setattr(mod, "_interpret", lambda: False)


def _compiled_text(sharding, fn, *avals) -> str:
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
            for shape, dtype in avals]
    # conftest turns x64 on for the gradient checks; the chip runs with it
    # off, and under x64 the index maps' literal 0s lower as i64
    with jax.enable_x64(False):
        return jax.jit(fn).lower(*args).compile().as_text()


def _custom_calls(sharding, fn, *avals) -> int:
    return _compiled_text(sharding, fn, *avals).count(
        'custom_call_target="tpu_custom_call"')


def _instruction_names(text: str) -> set:
    """Names of the compiled program's custom-call instructions, without
    their instance numbers (what a device trace's ``XLA Ops`` line
    carries and the benchmark's ``op_family`` keys on)."""
    return {re.sub(r"[.\-_]?\d+$", "", m) for m in re.findall(
        r"%([\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"", text)}


def test_every_registered_kernel_is_compiled_here():
    assert set(kernels.names()) == {"attention", "lstm", "threshold_encode",
                                    "int8_matmul", "conv1x1_bias_relu",
                                    "paged_attention", "moe_experts"}, \
        "a kernel was registered without a TPU compile check in this file"


@pytest.mark.parametrize("M,K,N", [(64, 256, 256),       # the parity pin
                                   (32, 512, 2048), (128, 2048, 512)])
def test_int8_matmul_compiles(v5e, M, K, N):
    n = _custom_calls(v5e, quantized.int8_matmul_pallas,
                      ((M, K), i8), ((K, N), i8), ((M,), f32), ((N,), f32))
    assert n == 1


@pytest.mark.parametrize("shape,F,dtype", [
    ((2, 4, 4, 128), 128, f32),                          # the parity pin
    ((8, 14, 14, 1024), 256, bf16), ((8, 7, 7, 2048), 512, bf16),
    ((8, 56, 56, 256), 128, f32)])
def test_conv1x1_bias_relu_compiles(v5e, shape, F, dtype):
    C = shape[-1]
    n = _custom_calls(v5e, conv.conv1x1_bias_relu,
                      (shape, dtype), ((1, 1, C, F), dtype), ((F,), dtype))
    assert n == 1


@pytest.mark.parametrize("B,H,T,D,dtype,backward", [
    (1, 2, 256, 64, f32, True),                          # the parity pin
    (8, 8, 1024, 64, bf16, True),                        # chip_smoke's LM
    (8, 16, 1024, 64, bf16, True),       # the benchmark's training cell
    (4, 16, 768, 64, bf16, False),       # the long-prompt cell's prefills,
    (4, 16, 1024, 64, bf16, False),      # rung 768 and rung 1024
    (2, 4, 1024, 128, f32, True),        # the widest operands a resident
    (2, 4, 2048, 128, f32, True),        # block holds; two blocks a side
    (2, 4, 1024, 96, bf16, True)])
def test_flash_attention_fwd_bwd_compiles(v5e, B, H, T, D, dtype, backward):
    """Mosaic takes the resident blocks and the static tile walk at the
    shapes the cells run (a VMEM or lowering refusal shows here, without
    a chip), forward alone as a prefill runs it and with both backward
    kernels as ``fit`` does."""
    def forward(q, k, v):
        return pallas_attention.flash_attention(q, k, v, causal=True)

    def loss(q, k, v):
        return jnp.sum(forward(q, k, v).astype(f32))
    qkv = ((B, H, T, D), dtype)
    fn = jax.grad(loss, argnums=(0, 1, 2)) if backward else forward
    n = _custom_calls(v5e, fn, qkv, qkv, qkv)
    assert n == (3 if backward else 1)  # forward, dq pass, dk/dv pass


@pytest.mark.parametrize("T", [6144, 16384])
def test_flash_forward_with_a_value_size_of_its_own_compiles(v5e, T):
    """kanana2-serve-longdoc's prefills: scores over 192 values a head
    (128 + a rotary part of 64), values of 128, one whole prompt of its
    smallest and its largest rung, 6 and 16 resident blocks a side."""
    text = _compiled_text(
        v5e, lambda q, k, v: pallas_attention.flash_attention(
            q, k, v, causal=True, scale=192 ** -0.5),
        ((1, 32, T, 192), bf16), ((1, 32, T, 192), bf16),
        ((1, 32, T, 128), bf16))
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert pallas_attention.FWD_NAME in _instruction_names(text)


@pytest.mark.parametrize("T,H,Hkv,window", [
    (6144, 64, 8, 512), (16384, 64, 8, 512),     # laguna-serve-codebase
    (2048, 8, 8, 300), (512, 4, 2, 512)])        # odd window; one block
def test_flash_forward_under_a_sliding_window_compiles(v5e, T, H, Hkv,
                                                       window):
    """laguna-serve-codebase's sliding-window prefills: 64 query heads
    reading 8 key-value heads in place, a window of 512, one whole prompt
    of its smallest and its largest rung; under a name of its own."""
    text = _compiled_text(
        v5e, lambda q, k, v: pallas_attention.flash_attention(
            q, k, v, causal=True, window=window),
        ((1, H, T, 128), bf16), ((1, Hkv, T, 128), bf16),
        ((1, Hkv, T, 128), bf16))
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert pallas_attention.WINDOW_FWD_NAME in _instruction_names(text)
    assert pallas_attention.FWD_NAME not in _instruction_names(text)


@pytest.mark.parametrize("T", [2048, 20480, 33280])
def test_flash_forward_under_a_block_sparse_selection_compiles(v5e, T):
    """minicpm-sala-serve-longctx's sparse prefills: 32 query heads over 2
    key-value heads of 128 read in place, one list of blocks of 64 a query
    and group as a [blocks, queries] mask, the strips' flags prefetched as
    scalars; a rung of whole 1,024-row resident blocks and the capacity
    rung (33,280: blocks of 512)."""
    text = _compiled_text(
        v5e, lambda q, k, v, c: pallas_attention.flash_attention_sparse(
            q, k, v, c),
        ((1, 32, T, 128), bf16), ((1, 2, T, 128), bf16),
        ((1, 2, T, 128), bf16), ((1, 2, T // 64, T), f32))
    assert pallas_attention.SPARSE_FWD_NAME in _instruction_names(text)


def test_paged_attention_over_selected_pages_compiles(v5e):
    """The cell's selected decode: 16 slots, 32 query heads in 2 groups,
    lists of up to 128 pages of 64 a slot and group, a pool row of 2 heads
    (256 lanes) of which a group's 128 are fetched."""
    S, nb = 16, 8321
    text = _compiled_text(
        v5e, lambda q, kp, vp, pages, counts, lens:
        pallas_paged_attention.paged_attention_sparse_decode(
            q, kp, vp, 1, pages, counts, lens),
        ((S, 32, 1, 128), bf16), ((2, nb, 64, 256), bf16),
        ((2, nb, 64, 256), bf16), ((S, 2, 128), jnp.int32),
        ((S, 2), jnp.int32), ((S,), jnp.int32))
    assert pallas_paged_attention.SPARSE_KERNEL_NAME in \
        _instruction_names(text)


def test_the_selection_of_a_decode_step_and_of_a_prefill_compiles(v5e):
    """The cell's selection in XLA at its shapes: a decode step's (16 slots
    of one row against 2,080 compressed keys: scores, the choice, the
    choice LISTED: the k-th largest's loop, a sort of 520) and a prefill's
    mask over a rung (256 positions a pass). Its loops are the only ones
    these programs run (``attn.sparse_busy_pct.tput`` reads them)."""
    from deeplearning4j_tpu.ops import sparse_select as ss
    sel = ss.Selection()
    t = jnp.arange(16, dtype=jnp.int32)[:, None] * 1024 + 17000

    def lists(q, c):
        return ss.chosen_lists(ss.block_scores(q, c, t, sel, 0.088), t, sel)
    text = _compiled_text(v5e, lists, ((16, 1, 32, 128), bf16),
                          ((16, 2080, 2, 128), bf16))
    assert len(re.findall(r"\bwhile\(", text)) == 1          # the k-th largest
    assert "tpu_custom_call" not in text
    text = _compiled_text(
        v5e, lambda q, k: ss.chosen_mask(q, ss.compress_keys(k, sel), sel,
                                         0.088),
        ((1, 20480, 32, 128), bf16), ((1, 20480, 2, 128), bf16))
    assert len(re.findall(r"\bwhile\(", text)) >= 1


def test_lightning_attention_kernels_compile(v5e):
    """The cell's lightning layers: the chunked forward of 32 heads of 128
    over a rung, and the decode step over 16 slots' float32 states of 6
    layers, the pool aliased to the result."""
    sl = pallas_linear_attention.slopes(32)
    qkv = ((1, 32, 4096, 128), bf16)
    text = _compiled_text(
        v5e, lambda q, k, v: pallas_linear_attention.lightning_attention_fwd(
            q, k, v, sl, scale=0.088), qkv, qkv, qkv)
    assert pallas_linear_attention.FWD_NAME in _instruction_names(text)
    row = ((16, 32, 128), bf16)
    text = _compiled_text(
        v5e, lambda q, k, v, pool, active:
        pallas_linear_attention.lightning_decode(
            q, k, v, pool, 2, active, sl, scale=0.088),
        row, row, row, ((6, 17, 32, 128, 128), f32), ((16,), jnp.bool_))
    assert pallas_linear_attention.DECODE_NAME in _instruction_names(text)


def test_flash_attention_splits_per_device_on_a_mesh(v5e_devices):
    """Mosaic calls cannot be partitioned automatically: a program over
    four devices lowers the kernels only because flash_attention splits
    them per device under the mesh tracing context (as ParallelWrapper.fit
    and GenerationProgramSet.warm trace) — and without that context the
    refusal is JAX's loud one, not a silent fallback."""
    mesh = Mesh(np.array(v5e_devices).reshape(2, 2), ("data", "model"))

    def loss(q, k, v):
        o = pallas_attention.flash_attention(q, k, v, causal=True)
        return jnp.sum(o.astype(f32))
    grad = jax.grad(loss, argnums=(0, 1, 2))
    qkv = ((8, 8, 1024, 64), bf16)
    on_mesh = NamedSharding(mesh, P("data", "model"))
    with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
        assert _custom_calls(on_mesh, grad, qkv, qkv, qkv) == 3
    with pytest.raises(NotImplementedError, match="automatically partition"):
        _custom_calls(on_mesh, grad, qkv, qkv, qkv)


def _paged_avals(S, H, W, Dh, blk, mb, L, dtype, sharding=None,
                 q_heads=None):
    """(q, k_pool, v_pool, tables, lens) of a paged decode attention call:
    the pool holds every slot's full table plus the trash block (``H``
    key-value heads; ``q_heads`` query heads where they are more)."""
    pool = ((L, S * mb + 1, blk, H * Dh), dtype)
    return (((S, q_heads or H, W, Dh), dtype), pool, pool,
            ((S, mb), jnp.int32), ((S,), jnp.int32))


@pytest.mark.parametrize("S,H,W,Dh,blk,mb,L,dtype", [
    (5, 2, 1, 64, 16, 8, 2, f32),            # the parity pin, W = 1
    (5, 2, 3, 64, 16, 8, 2, f32),            # and its verify window
    (16, 16, 1, 64, 16, 64, 24, bf16),       # the benchmark's serving cells
    (16, 16, 5, 64, 16, 64, 24, bf16),       # a verify window of k = 4 there
    (4, 8, 1, 64, 16, 64, 12, bf16),         # chip_smoke's LM
    (32, 8, 1, 64, 16, 132, 2, bf16)])       # lfm2moe-serve-extract (q: 32)
def test_paged_attention_decode_compiles(v5e, S, H, W, Dh, blk, mb, L, dtype):
    text = _compiled_text(
        v5e, lambda q, k, v, t, n: pallas_paged_attention.
        paged_attention_decode(q, k, v, L - 1, t, n),
        *_paged_avals(S, H, W, Dh, blk, mb, L, dtype,
                      q_heads=32 if L == 2 and S == 32 else None))
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert pallas_paged_attention.KERNEL_NAME in _instruction_names(text)
    assert not pallas_paged_attention.KERNEL_NAME[-1].isdigit()


@pytest.mark.parametrize("S,Hq,W,row,lanes,blk,mb,L,dtype", [
    (4, 4, 1, 128, 64, 8, 6, 2, f32),        # the CPU tests' shape
    (40, 32, 1, 640, 512, 64, 272, 6, bf16),  # kanana2-serve-longdoc
    (48, 32, 1, 640, 512, 128, 136, 6, bf16)])
def test_paged_attention_over_a_latent_pool_compiles(v5e, S, Hq, W, row,
                                                     lanes, blk, mb, L, dtype):
    """One pool of rows with no head axis, one DMA a page, values the
    first lanes of the key rows, under its own name in the program (and so
    in a device trace)."""
    text = _compiled_text(
        v5e, lambda q, pool, t, n: pallas_paged_attention.
        paged_attention_decode(q, pool, None, L - 1, t, n, scale=192 ** -0.5,
                               value_lanes=lanes),
        ((S, Hq, W, row), dtype), ((L, S * mb + 1, blk, row), dtype),
        ((S, mb), jnp.int32), ((S,), jnp.int32))
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    name = pallas_paged_attention.LATENT_KERNEL_NAME
    assert name in _instruction_names(text) and not name[-1].isdigit()


@pytest.mark.parametrize("S,Hq,H,blk,rp,mb,L,dtype", [
    (5, 8, 2, 8, 5, 12, 2, f32),             # the CPU tests' shape
    (40, 64, 8, 64, 9, 272, 3, bf16),        # laguna-serve-codebase
    (48, 64, 8, 64, 9, 272, 3, bf16)])
def test_paged_attention_under_a_sliding_window_compiles(v5e, S, Hq, H, blk,
                                                         rp, mb, L, dtype):
    """The windowed decode over a ring a slot (``rp`` pages of ``blk``
    rows, S + 1 rings a layer viewed as one pool): a fourth prefetched
    scalar a slot, the walk from the window's first page; under a name of
    its own."""
    pool = ((L, (S + 1) * rp, blk, H * 128), dtype)
    text = _compiled_text(
        v5e, lambda q, k, v, t, n, s0: pallas_paged_attention.
        paged_attention_decode(q, k, v, L - 1, t, n, starts=s0),
        ((S, Hq, 1, 128), dtype), pool, pool, ((S, mb), jnp.int32),
        ((S,), jnp.int32), ((S,), jnp.int32))
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    name = pallas_paged_attention.WINDOW_KERNEL_NAME
    assert name in _instruction_names(text) and not name[-1].isdigit()
    assert pallas_paged_attention.KERNEL_NAME not in _instruction_names(text)


def test_paged_attention_of_48_query_heads_over_8_compiles(v5e):
    """laguna-serve-codebase's full-attention layers: 48 query heads of
    128 over 8 key-value heads, pages of 64, 40 slots of 272 pages."""
    text = _compiled_text(
        v5e, lambda q, k, v, t, n: pallas_paged_attention.
        paged_attention_decode(q, k, v, 1, t, n),
        *_paged_avals(40, 8, 1, 128, 64, 272, 2, bf16, q_heads=48))
    assert pallas_paged_attention.KERNEL_NAME in _instruction_names(text)


@pytest.mark.parametrize("N,d,F,E,k,dtype", [
    (40, 2048, 512, 256, 8, bf16),           # laguna-serve-codebase: decode
    (16384, 2048, 512, 256, 8, bf16),        # and its largest prefill
    (40, 128, 256, 8, 2, f32),               # the parity pin
    (32, 2048, 1536, 64, 4, bf16),           # lfm2moe-serve-extract: decode,
    (512, 2048, 1536, 64, 4, bf16),          # its smallest prefill
    (8192, 2048, 1536, 64, 4, bf16),         # and its largest (4 x 2048)
    (40, 2048, 768, 128, 6, bf16),           # kanana2-serve-longdoc: decode
    (16384, 2048, 768, 128, 6, bf16)])       # and its largest prefill
def test_moe_experts_compile(v5e, monkeypatch, N, d, F, E, k, dtype):
    """The grouped gated matmul's two kernels lower for the chip under
    stable names (the benchmark's roofline metric reads them by name)."""
    monkeypatch.setattr(grouped_matmul, "kernels_applicable",
                        lambda *a: True)
    text = _compiled_text(
        v5e, grouped_matmul.expert_ffn, ((N, d), dtype), ((N, k), jnp.int32),
        ((N, k), f32), ((E, d, F), dtype), ((E, d, F), dtype),
        ((E, F, d), dtype))
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert {grouped_matmul.KERNEL_GATE_UP, grouped_matmul.KERNEL_DOWN} <= \
        _instruction_names(text)


def test_paged_attention_decode_splits_heads_on_a_mesh(v5e_devices):
    """The head-sharded decode program (pools split over ``model``): the
    kernel runs per device on its own heads under the mesh tracing context
    ``GenerationProgramSet._aot`` enters, and is refused loudly without."""
    from deeplearning4j_tpu.parallel.tensor_parallel import MODEL_AXIS
    mesh = Mesh(np.array(v5e_devices[:2]).reshape(1, 2), ("data", MODEL_AXIS))
    heads = NamedSharding(mesh, P(None, MODEL_AXIS))
    pools = NamedSharding(mesh, P(None, None, None, MODEL_AXIS))
    whole = NamedSharding(mesh, P())
    avals = _paged_avals(16, 16, 1, 64, 16, 64, 24, bf16)
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=sh)
            for (shape, dtype), sh in zip(avals, (heads, pools, pools,
                                                  whole, whole))]

    def compile_it():
        with jax.enable_x64(False):
            return jax.jit(
                lambda q, k, v, t, n: pallas_paged_attention.
                paged_attention_decode(q, k, v, 3, t, n),
                out_shardings=heads).lower(*args).compile().as_text()
    with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
        text = compile_it()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "all-gather" not in text and "all-to-all" not in text
    with pytest.raises(NotImplementedError, match="automatically partition"):
        compile_it()


def test_fused_lstm_fwd_bwd_compiles(v5e):
    T, B, H = 4, 8, 128                                  # the parity pin

    def loss(xp, h0, c0, R):
        hs, (hT, cT) = pallas_lstm.fused_lstm(xp, h0, c0, R)
        return jnp.sum(hs) + jnp.sum(hT) + jnp.sum(cT)
    n = _custom_calls(v5e, jax.grad(loss, argnums=(0, 3)),
                      ((T, B, 4 * H), f32), ((B, H), f32), ((B, H), f32),
                      ((H, 4 * H), f32))
    assert n == 2           # forward + backward time loops


def test_threshold_encode_compiles(v5e):
    n = (1 << 16) + 777                                  # the parity pin
    assert _custom_calls(
        v5e, lambda r: pallas_compression.threshold_encode_pallas(r, 1e-3),
        ((n,), f32)) == 1


def _flash_grad_text(v5e):
    def loss(q, k, v):
        o = pallas_attention.flash_attention(q, k, v, causal=True)
        return jnp.sum(o.astype(f32))
    qkv = ((8, 16, 1024, 64), bf16)          # the benchmark's train cell
    return _compiled_text(v5e, jax.grad(loss, argnums=(0, 1, 2)),
                          qkv, qkv, qkv)


def _flash_carry_text(v5e):
    BH, T, D = 4, 256, 64
    return _compiled_text(
        v5e, lambda acc, m, l, q, k, v: pallas_attention.flash_block_update(
            acc, m, l, q, k, v, causal=True, scale=0.125),
        ((BH, T, D), f32), ((BH, 1, T), f32), ((BH, 1, T), f32),
        ((BH, T, D), bf16), ((BH, T, D), bf16), ((BH, T, D), bf16))


@pytest.mark.parametrize("constant,program", [
    ("FWD_NAME", _flash_grad_text), ("DQ_NAME", _flash_grad_text),
    ("DKV_NAME", _flash_grad_text), ("FWD_CARRY_NAME", _flash_carry_text)])
def test_flash_kernels_carry_their_names_into_the_compiled_program(
        v5e, constant, program):
    """The device trace names an operation after its HLO instruction, and
    ``pallas_call(name=)`` is what names the instruction: the per-kernel
    roofline shares of the benchmark key on these four constants."""
    name = getattr(pallas_attention, constant)
    assert not name[-1].isdigit()      # op_family strips instance numbers
    names = _instruction_names(program(v5e))
    assert name in names, names
    assert not names & {"jvp__", "transpose_jvp___", "fn", "loss"}


@pytest.mark.parametrize("module,constant", [
    (conv, "KERNEL_NAME"), (quantized, "KERNEL_NAME"),
    (pallas_lstm, "FWD_NAME"), (pallas_lstm, "BWD_NAME"),
    (pallas_compression, "KERNEL_NAME")])
def test_the_other_kernels_are_named_too(v5e, module, constant):
    name = getattr(module, constant)
    assert not name[-1].isdigit()
    T, B, H = 4, 8, 128

    def lstm_loss(xp, h0, c0, R):
        hs, (hT, cT) = pallas_lstm.fused_lstm(xp, h0, c0, R)
        return jnp.sum(hs) + jnp.sum(hT) + jnp.sum(cT)
    programs = {
        conv: (conv.conv1x1_bias_relu, ((2, 4, 4, 128), f32),
               ((1, 1, 128, 128), f32), ((128,), f32)),
        quantized: (quantized.int8_matmul_pallas, ((64, 256), i8),
                    ((256, 256), i8), ((64,), f32), ((256,), f32)),
        pallas_lstm: (jax.grad(lstm_loss, argnums=(0, 3)),
                      ((T, B, 4 * H), f32), ((B, H), f32), ((B, H), f32),
                      ((H, 4 * H), f32)),
        pallas_compression: (
            lambda r: pallas_compression.threshold_encode_pallas(r, 1e-3),
            (((1 << 16) + 777,), f32)),
    }
    fn, *avals = programs[module]
    assert name in _instruction_names(_compiled_text(v5e, fn, *avals))
