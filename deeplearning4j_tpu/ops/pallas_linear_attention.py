"""Lightning (decayed linear) attention: a matrix-valued state a head.

    S_t = lam_h S_{t-1} + k_t^T v_t        S [Dk, Dv] float32, S_{-1} = 0
    o_t = (q_t S_t) * scale

``lam_h = exp(-slope_h)`` is a constant of head ``h``. A decode step is the
recurrence itself; a whole sequence runs in CHUNKS of ``C`` rows, which
turns all but a ``[Dk, Dv]`` carry into matmuls:

    O      = ((Q K^T) * D) V + Lam (Q S_prev)     D_ij = lam^(i-j), i >= j
                                                  Lam_i = lam^(i+1)
    S_next = lam^C S_prev + (K * lam^(C-1-j))^T V

Every power of ``lam`` here has a non-negative exponent, so nothing
overflows however fast a head decays (the factored form ``(q lam^i) (k
lam^-j)`` does); old keys underflow to exactly 0.

Three forms of the same numbers:

- ``lightning_attention_xla``: the chunked form in ``jax.numpy`` (a scan
  over chunks): training, the CPU, an initial state, the final state.
- ``lightning_attention_fwd`` (Pallas, TPU): the chunked form with the
  carry in VMEM scratch, chunks innermost on the grid; forward only, from a
  zero state. The decay matrix and the two decay vectors of a head are
  inputs made once from the slopes (constants of the program), fetched once
  a head: the kernel body is matmuls and products.
- ``lightning_decode`` (Pallas, TPU) / ``lightning_decode_xla``: ONE row a
  slot against its state, the state pool ``[layers, slots + 1, H, Dk, Dv]``
  updated IN PLACE (the pool is aliased to the result: a slot-layer's state
  is read once and written once, idle slots keep theirs).

Precision: the state, its update and ``q S`` are float32 throughout; the
intra-chunk products ``Q K^T`` and ``A V`` take the inputs' dtype on the
MXU with float32 accumulation, as the flash kernels do.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

f32 = jnp.float32

SCOPE = "lightning_attention"
FWD_NAME = "lightning_attention_fwd"
DECODE_NAME = "lightning_decode"

CHUNK = 256            # rows a chunk: two MXU tiles a side, D is 256 KB
_DECODE_HEADS = 8      # heads of one slot a decode grid step holds (512 KB)


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def slopes(n_heads: int) -> np.ndarray:
    """``slope_h = 2^(-8 (h + 1) / H)``, float32 [H]: the head's decay is
    ``lam_h = exp(-slope_h)`` (the slopes of Lightning Attention-2,
    arXiv:2401.04658; from 0.43 a step for the first head of 32 to 0.996
    for the last)."""
    h = np.arange(1, n_heads + 1, dtype=np.float64)
    return (2.0 ** (-8.0 * h / n_heads)).astype(np.float32)


def decay_tables(slope, C: int):
    """(D [H,C,C], lam^(i+1) [H,C], lam^(C-1-j) [H,C]) float32 from the
    slopes [H]: everything of a chunk that depends on the head alone."""
    s = jnp.asarray(slope, f32)[:, None]
    i = jnp.arange(C, dtype=f32)
    diff = i[:, None] - i[None, :]
    D = jnp.where(diff >= 0, jnp.exp(-s[:, :, None] * jnp.maximum(diff, 0.0)),
                  0.0)
    return D, jnp.exp(-s * (i + 1.0)), jnp.exp(-s * (C - 1.0 - i))


# ---------------------------------------------------------------- XLA forms
def _chunk_step(S, qc, kc, vc, D, qdec, kdec):
    """One chunk of the chunked form on float32 operands: S [B,H,Dk,Dv],
    qc/kc [B,H,C,Dk], vc [B,H,C,Dv], the head's tables for chunks of C rows
    -> (the state after the chunk, its outputs before the scale)."""
    a = jnp.einsum("bhik,bhjk->bhij", qc, kc) * D[None]
    o = jnp.einsum("bhij,bhjv->bhiv", a, vc) \
        + jnp.einsum("bhik,bhkv->bhiv", qc, S) * qdec[None, :, :, None]
    S = S * qdec[None, :, -1, None, None] + jnp.einsum(
        "bhjk,bhjv->bhkv", kc * kdec[None, :, :, None], vc)
    return S, o


def lightning_attention_xla(q, k, v, slope, *, scale, chunk=CHUNK,
                            initial_state=None):
    """q, k [B,H,T,Dk], v [B,H,T,Dv] -> (o [B,H,T,Dv] in v's dtype, the
    state after row T - 1 [B,H,Dk,Dv] float32). A scan over the whole
    chunks, then the remainder as a chunk of its own length."""
    B, H, T, Dk = q.shape
    Dv = v.shape[-1]
    C = min(chunk, T)
    n, r = divmod(T, C)
    S = jnp.zeros((B, H, Dk, Dv), f32) if initial_state is None \
        else initial_state.astype(f32)
    out_dtype = v.dtype
    q, k, v = (a.astype(f32) for a in (q, k, v))
    tables = decay_tables(slope, C)

    def split(a):
        return a[:, :, :n * C].reshape(B, H, n, C, a.shape[-1]).transpose(
            2, 0, 1, 3, 4)

    S, o = jax.lax.scan(lambda S, qkv: _chunk_step(S, *qkv, *tables), S,
                        (split(q), split(k), split(v)))
    o = o.transpose(1, 2, 0, 3, 4).reshape(B, H, n * C, Dv)
    if r:
        S, tail = _chunk_step(S, *(a[:, :, n * C:] for a in (q, k, v)),
                              *decay_tables(slope, r))
        o = jnp.concatenate([o, tail], axis=2)
    return (o * scale).astype(out_dtype), S


def lightning_recurrent(q, k, v, slope, *, scale, initial_state=None):
    """The recurrence row by row (a scan over T): the plain form the other
    two are held to in the tests. Same shapes as
    ``lightning_attention_xla``."""
    B, H, T, Dk = q.shape
    lam = jnp.exp(-jnp.asarray(slope, f32))[None, :, None, None]
    S0 = jnp.zeros((B, H, Dk, v.shape[-1]), f32) if initial_state is None \
        else initial_state.astype(f32)

    def step(S, qkv):
        qt, kt, vt = (a.astype(f32) for a in qkv)
        S = lam * S + kt[..., :, None] * vt[..., None, :]
        return S, jnp.einsum("bhk,bhkv->bhv", qt, S)

    S, o = jax.lax.scan(step, S0, tuple(a.transpose(2, 0, 1, 3)
                                        for a in (q, k, v)))
    return (o.transpose(1, 2, 0, 3) * scale).astype(v.dtype), S


def lightning_state_at(k, v, slope, lengths):
    """The state after ``lengths`` [B] rows of k [B,T,H,Dk], v [B,T,H,Dv]
    (rows as the projections give them): ``S_n = sum_{j<n} lam^(n-1-j)
    k_j^T v_j`` as ONE contraction over T a head, float32 [B,H,Dk,Dv]. A
    padded prompt's rows from ``lengths`` on take weight 0."""
    T = k.shape[1]
    s = jnp.asarray(slope, f32)[None, None, :]
    back = (lengths.astype(jnp.int32)[:, None] - 1
            - jnp.arange(T, dtype=jnp.int32)[None, :])          # n - 1 - j
    w = jnp.where(back[..., None] >= 0,
                  jnp.exp(-s * jnp.maximum(back, 0)[..., None].astype(f32)),
                  0.0)                                           # [B,T,H]
    return jnp.einsum("bthk,bthv->bhkv", k.astype(f32) * w[..., None],
                      v.astype(f32), preferred_element_type=f32)


def lightning_decode_xla(q, k, v, pool, layer: int, active, slope, *, scale):
    """One row a slot: q, k [S,H,Dk], v [S,H,Dv]; pool [layers, slots + 1,
    H, Dk, Dv] float32; active [S] bool. Returns (o [S,H,Dv] in v's dtype,
    the pool with the live slots' states of ``layer`` advanced)."""
    S = q.shape[0]
    lam = jnp.exp(-jnp.asarray(slope, f32))[None, :, None, None]
    old = pool[layer, :S]
    new = lam * old + k.astype(f32)[..., :, None] * v.astype(f32)[..., None, :]
    new = jnp.where(active.reshape(S, 1, 1, 1), new, old)
    o = jnp.einsum("shk,shkv->shv", q.astype(f32), new) * scale
    return o.astype(v.dtype), pool.at[layer, :S].set(new)


# ------------------------------------------------------------ chunked kernel
def _fwd_body(scale, q_ref, k_ref, v_ref, d_ref, qdec_ref, kdec_ref, o_ref,
              state):
    """Grid step (b * H + h, chunk): the chunk's rows against themselves
    under the head's decay matrix, and against the carry."""
    @pl.when(pl.program_id(1) == 0)
    def _init():
        state[:] = jnp.zeros_like(state)

    q, k, v = q_ref[0], k_ref[0], v_ref[0]
    a = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=f32) * d_ref[0]
    o = jax.lax.dot_general(a.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                            preferred_element_type=f32)
    qdec = qdec_ref[0]                                  # [C, Dv] lane copies
    S = state[:]
    o = o + jax.lax.dot_general(q.astype(f32), S, (((1,), (0,)), ((), ())),
                                preferred_element_type=f32) * qdec
    o_ref[0] = (o * scale).astype(o_ref.dtype)
    C = q.shape[0]
    kwT = (k.astype(f32) * kdec_ref[0]).T               # [Dk, C]
    state[:] = S * qdec[C - 1:C, :] + jax.lax.dot_general(
        kwT, v.astype(f32), (((1,), (0,)), ((), ())),
        preferred_element_type=f32)


@functools.partial(jax.jit, static_argnames=("scale", "chunk", "interpret"))
def _fwd_call(q3, k3, v3, slope, *, scale, chunk, interpret):
    BH, T, Dk = q3.shape
    Dv = v3.shape[2]
    H = slope.shape[0]
    C = chunk
    D, qdec, kdec = decay_tables(slope, C)
    # a row's factor on every lane of its sublane: the layout a [C, D]
    # operand multiplies without a relayout
    qdec = jnp.broadcast_to(qdec[:, :, None], (H, C, Dv))
    kdec = jnp.broadcast_to(kdec[:, :, None], (H, C, Dk))
    row = lambda D_: pl.BlockSpec((1, C, D_), lambda b, c: (b, c, 0))
    head = lambda n: pl.BlockSpec((1, C, n), lambda b, c: (b % H, 0, 0))
    with jax.named_scope(SCOPE):
        return pl.pallas_call(
            functools.partial(_fwd_body, scale),
            name=FWD_NAME,
            grid=(BH, T // C),
            in_specs=[row(Dk), row(Dk), row(Dv), head(C), head(Dv),
                      head(Dk)],
            out_specs=row(Dv),
            out_shape=jax.ShapeDtypeStruct((BH, T, Dv), v3.dtype),
            scratch_shapes=[pltpu.VMEM((Dk, Dv), f32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            interpret=interpret,
        )(q3, k3, v3, D, qdec, kdec)


def lightning_kernel_applicable(T: int, Dk: int, Dv: int, dtype) -> bool:
    """Whether ``lightning_attention_fwd`` takes the call: on the TPU,
    whole chunks, heads of whole lane tiles."""
    return (not _interpret() and T % CHUNK == 0
            and Dk % 128 == 0 and Dv % 128 == 0
            and jnp.dtype(dtype) in (jnp.dtype(jnp.bfloat16),
                                     jnp.dtype(jnp.float32)))


def lightning_attention_fwd(q, k, v, slope, *, scale, interpret=None):
    """The chunked form as one Pallas kernel, from a zero state: q, k
    [B,H,T,Dk], v [B,H,T,Dv] with T a multiple of ``CHUNK`` -> o
    [B,H,T,Dv]. Forward only."""
    B, H, T, Dk = q.shape
    if T % CHUNK:
        raise ValueError(f"T={T} is not whole chunks of {CHUNK}")
    o = _fwd_call(q.reshape(B * H, T, Dk), k.reshape(B * H, T, Dk),
                  v.reshape(B * H, T, v.shape[-1]), jnp.asarray(slope, f32),
                  scale=float(scale), chunk=CHUNK,
                  interpret=_interpret() if interpret is None else interpret)
    return o.reshape(B, H, T, v.shape[-1])


# ------------------------------------------------------------- decode kernel
def _decode_body(scale, HB, active_ref, lam_ref, q_ref, k_ref, v_ref, s_ref,
                 o_ref, s_out):
    """Grid step (slot, head block): ``HB`` heads of one slot, each head's
    state [Dk, Dv] read once, advanced, read by the query and written
    once. q, k, v arrive [1, HB, 8, D]: a head's row copied down a sublane
    tile, so that its transpose is a tile's."""
    live = active_ref[pl.program_id(0)] > 0
    for h in range(HB):
        old = s_ref[0, 0, h]                             # [Dk, Dv] f32
        kcol = k_ref[0, h].astype(f32).T[:, 0:1]         # [Dk, 1]
        qcol = q_ref[0, h].astype(f32).T[:, 0:1]
        vrow = v_ref[0, h, 0:1, :].astype(f32)           # [1, Dv]
        new = old * lam_ref[0, h, 0:1, :] + kcol * vrow
        new = jnp.where(live, new, old)
        s_out[0, 0, h] = new
        o_ref[0, h] = jnp.broadcast_to(
            jnp.sum(qcol * new, axis=0, keepdims=True) * scale,
            o_ref.shape[2:]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _decode_call(q, k, v, pool, layer, active, slope, *, scale, interpret):
    S, H, Dk = q.shape
    Dv = v.shape[-1]
    HB = _DECODE_HEADS if H % _DECODE_HEADS == 0 else 1
    rows = lambda a: jnp.broadcast_to(a[:, :, None, :],
                                      a.shape[:2] + (8, a.shape[-1]))
    lam = jnp.broadcast_to(jnp.exp(-slope)[None, :, None, None],
                           (1, H, 8, Dv)).astype(f32)
    vec = lambda D_: pl.BlockSpec((1, HB, 8, D_),
                                  lambda s, h, *_: (s, h, 0, 0))
    state = pl.BlockSpec((1, 1, HB, Dk, Dv),
                         lambda s, h, lay, *_: (lay[0], s, h, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                    # layer, active
        grid=(S, H // HB),
        in_specs=[pl.BlockSpec((1, HB, 8, Dv), lambda s, h, *_: (0, h, 0, 0)),
                  vec(Dk), vec(Dk), vec(Dv), state],
        out_specs=[vec(Dv), state])

    def body(lay_ref, active_ref, *refs):
        _decode_body(scale, HB, active_ref, *refs)

    with jax.named_scope(SCOPE):
        o, pool = pl.pallas_call(
            body, name=DECODE_NAME, grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct((S, H, 8, Dv), v.dtype),
                       jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
            # operands: layer, active, lam, q, k, v, pool -> pool is result 1
            input_output_aliases={6: 1},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary")),
            interpret=interpret,
        )(layer.reshape(1), active.astype(jnp.int32), lam,
          rows(q.astype(f32)), rows(k.astype(f32)), rows(v.astype(f32)),
          pool)
    return o[:, :, 0, :], pool


def lightning_decode(q, k, v, pool, layer: int, active, slope, *, scale,
                     interpret=None):
    """``lightning_decode_xla`` as one Pallas kernel over the pool in
    place: the pool is aliased to the result (rebind it: never reuse the
    array handed in), only the ``S`` slots' states of ``layer`` are
    touched."""
    return _decode_call(q, k, v, pool, jnp.asarray(layer, jnp.int32), active,
                        jnp.asarray(slope, f32), scale=float(scale),
                        interpret=_interpret() if interpret is None
                        else interpret)
