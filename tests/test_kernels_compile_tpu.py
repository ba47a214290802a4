"""AOT-compile every registered kernel's FUSED implementation for the v5e.

The CPU suite runs the Pallas kernels in the interpreter, which accepts
block shapes the Mosaic TPU compiler refuses (PR 17's int8 matmul and
conv1x1 kernels passed every interpreter test and could not compile for
the chip). libtpu hands out a compile-only v5e topology with no device
attached, so the real compiler runs here: "the compiler refuses it" is a
tier-1 failure instead of a chip-budget discovery. This proves COMPILES,
not RUNS — ``chip_smoke.py`` runs the parity pins on the chip.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.ops import (pallas_attention, pallas_compression,
                                    pallas_lstm)
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
                quantized, conv):
        monkeypatch.setattr(mod, "_interpret", lambda: False)


def _custom_calls(sharding, fn, *avals) -> int:
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
            for shape, dtype in avals]
    # conftest turns x64 on for the gradient checks; the chip runs with it
    # off, and under x64 the index maps' literal 0s lower as i64
    with jax.enable_x64(False):
        text = jax.jit(fn).lower(*args).compile().as_text()
    return text.count('custom_call_target="tpu_custom_call"')


def test_every_registered_kernel_is_compiled_here():
    assert set(kernels.names()) == {"attention", "lstm", "threshold_encode",
                                    "int8_matmul", "conv1x1_bias_relu"}, \
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


@pytest.mark.parametrize("B,H,T,D,dtype", [
    (1, 2, 256, 64, f32),                                # the parity pin
    (8, 8, 1024, 64, bf16)])                             # chip_smoke's LM
def test_flash_attention_fwd_bwd_compiles(v5e, B, H, T, D, dtype):
    def loss(q, k, v):
        o = pallas_attention.flash_attention(q, k, v, causal=True)
        return jnp.sum(o.astype(f32))
    qkv = ((B, H, T, D), dtype)
    n = _custom_calls(v5e, jax.grad(loss, argnums=(0, 1, 2)), qkv, qkv, qkv)
    assert n == 3           # forward, dq pass, dk/dv pass


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
