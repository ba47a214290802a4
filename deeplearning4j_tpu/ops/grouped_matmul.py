"""Grouped gated matmul: the experts of a mixture-of-experts layer over the
token-expert PAIRS routed to them.

    out[n] = sum_j  w[n, j] * W2[e] (silu(x[n] W1[e]) * (x[n] W3[e])),  e = idx[n, j]

The work is proportional to the pairs routed (``N * k``), never to
``tokens x experts``: the pairs are sorted by expert, each expert's run of
rows is padded to whole row tiles, and two Pallas kernels walk the row
tiles with the tile's expert read from a prefetched table, so an expert's
weights are fetched once (consecutive tiles of one expert keep the block
index and Pallas skips the copy) and an expert with no pair is never
touched. That makes one function serve both ends of serving: a prefill of
8,192 tokens (512 rows an expert, compute bound) and a decode step of 32
slots (2 rows an expert, a stream of the touched experts' weights).

Off the TPU the same sorted rows go through ``jax.lax.ragged_dot`` (plain
XLA, what the CPU tests run); ``DL4J_TPU_KERNEL_MOE_EXPERTS=0`` picks that
path on the TPU too, ``DL4J_TPU_KERNEL_MOE_EXPERTS_INTERPRET=1`` runs the
kernels in the Pallas interpreter on the CPU (the parity pin).

``expert_ffn`` is jitted: a program of many expert layers traces it once
and lowers the kernels once, not once a layer (the lesson of
``pallas_paged_attention._one_device``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .kernels import envutil as kenv

f32 = jnp.float32

# names in the compiled program and in a device trace (see
# pallas_attention.SCOPE for why a call sits in two scopes)
SCOPE = "moe_experts"
KERNEL_GATE_UP = "moe_experts_gate_up"
KERNEL_DOWN = "moe_experts_down"

_VMEM_LIMIT = 64 * 1024 * 1024


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def kernels_applicable(d: int, f: int, dtype) -> bool:
    """Whether the Pallas kernels take this call: lane-dense widths, a
    dtype the MXU takes, the kernel not killed, a backend that admits it."""
    if not kenv.fused_enabled("moe_experts"):
        return False
    if jnp.dtype(dtype) not in (jnp.dtype(f32), jnp.dtype(jnp.bfloat16)):
        return False
    if d % 128 or f % 128:
        return False
    return kenv.backend_admits("moe_experts", jax.default_backend())


def row_tile(pairs: int, experts: int) -> int:
    """Rows of one tile: near the mean run of an expert, a power of two in
    16..256 (16 rows are one bfloat16 sublane tile; past 256 rows the
    padding of 64 runs to whole tiles costs more than the MXU gains)."""
    mean = max(1, pairs // max(1, experts))
    t = 16
    while t < 256 and t < mean:
        t *= 2
    return t


def _col_tile(n: int) -> int:
    for t in (512, 384, 256, 128):
        if n % t == 0:
            return t
    return n


def _gate_up_body(te_ref, na_ref, x_ref, w1_ref, w3_ref, h_ref):
    @pl.when(pl.program_id(1) < na_ref[0])
    def _():
        x = x_ref[...]
        a = jnp.dot(x, w1_ref[0], preferred_element_type=f32)
        b = jnp.dot(x, w3_ref[0], preferred_element_type=f32)
        h_ref[...] = (a * jax.nn.sigmoid(a) * b).astype(h_ref.dtype)


def _down_body(te_ref, na_ref, h_ref, w2_ref, y_ref):
    @pl.when(pl.program_id(1) < na_ref[0])
    def _():
        y_ref[...] = jnp.dot(h_ref[...], w2_ref[0],
                             preferred_element_type=f32).astype(y_ref.dtype)


def _kernels(xs, tile_expert, n_active, W1, W3, W2, tm, interpret):
    """xs [M, d] rows sorted by expert, every expert's run padded to whole
    tiles of ``tm``; tile_expert [M / tm] the expert of each tile (tiles
    past ``n_active`` repeat the last live tile's expert, so nothing new
    is fetched for them, and are not computed). Returns y [M, d]."""
    M, d = xs.shape
    F = W1.shape[2]
    tiles = M // tm
    tf, tn = _col_tile(F), _col_tile(d)
    last = lambda t, na: jnp.minimum(t, jnp.maximum(na[0] - 1, 0))
    params = pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)
    with jax.named_scope(SCOPE):
        h = pl.pallas_call(
            _gate_up_body, name=KERNEL_GATE_UP,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2, grid=(F // tf, tiles),
                in_specs=[
                    pl.BlockSpec((tm, d), lambda j, t, te, na: (last(t, na), 0)),
                    pl.BlockSpec((1, d, tf), lambda j, t, te, na: (te[t], 0, j)),
                    pl.BlockSpec((1, d, tf), lambda j, t, te, na: (te[t], 0, j))],
                out_specs=pl.BlockSpec(
                    (tm, tf), lambda j, t, te, na: (last(t, na), j))),
            out_shape=jax.ShapeDtypeStruct((M, F), xs.dtype),
            compiler_params=params, interpret=interpret,
        )(tile_expert, n_active, xs, W1, W3)
        y = pl.pallas_call(
            _down_body, name=KERNEL_DOWN,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2, grid=(d // tn, tiles),
                in_specs=[
                    pl.BlockSpec((tm, F), lambda j, t, te, na: (last(t, na), 0)),
                    pl.BlockSpec((1, F, tn), lambda j, t, te, na: (te[t], 0, j))],
                out_specs=pl.BlockSpec(
                    (tm, tn), lambda j, t, te, na: (last(t, na), j))),
            out_shape=jax.ShapeDtypeStruct((M, d), xs.dtype),
            compiler_params=params, interpret=interpret,
        )(tile_expert, n_active, h, W2)
    return y


def _sorted_pairs(idx, first: int, held: int):
    """Pairs in expert order. idx [N, k] global expert ids; the experts
    held here are ``first .. first + held - 1`` and a pair of any other
    expert sorts behind them all. Returns (order [P] pair ids sorted,
    local [P] the sorted pairs' local expert (``held`` = not held), sizes
    [held] pairs an expert got)."""
    N, k = idx.shape
    local = idx.reshape(N * k).astype(jnp.int32) - first
    local = jnp.where((local >= 0) & (local < held), local, held)
    order = jnp.argsort(local, stable=True).astype(jnp.int32)
    sizes = jnp.zeros((held + 1,), jnp.int32).at[local].add(1)[:held]
    return order, local[order], sizes


def _ffn_kernels(x, idx, w, W1, W3, W2, first, tm, interpret):
    N, k = idx.shape
    E = W1.shape[0]
    P = N * k
    order, local, sizes = _sorted_pairs(idx, first, E)
    padded = -(-sizes // tm) * tm
    pstart = jnp.cumsum(padded) - padded                    # [E]
    start = jnp.cumsum(sizes) - sizes
    M = (-(-P // tm) + E) * tm              # every run rounded up, at most
    held = local < E
    e = jnp.minimum(local, E - 1)
    dest = jnp.where(held, pstart[e] + jnp.arange(P, dtype=jnp.int32)
                     - start[e], M)         # M: dropped by the scatter
    src = jnp.full((M,), N, jnp.int32).at[dest].set(order // k, mode="drop")
    xs = jnp.concatenate([x, jnp.zeros((1, x.shape[1]), x.dtype)])[src]
    ends = jnp.cumsum(padded)
    n_active = (ends[-1] // tm).astype(jnp.int32)
    t0 = jnp.minimum(jnp.arange(M // tm, dtype=jnp.int32),
                     jnp.maximum(n_active - 1, 0)) * tm
    tile_expert = jnp.minimum(
        jnp.searchsorted(ends, t0, side="right"), E - 1).astype(jnp.int32)
    y = _kernels(xs, tile_expert, n_active.reshape(1), W1, W3, W2, tm,
                 interpret)
    # back to pair order: pair p's row, weight 0 where its expert is not held
    row = jnp.zeros((P,), jnp.int32).at[order].set(jnp.minimum(dest, M - 1))
    keep = jnp.zeros((P,), bool).at[order].set(held)
    yp = jnp.where(keep[:, None], y[row].astype(f32), 0.0).reshape(N, k, -1)
    return jnp.einsum("nkd,nk->nd", yp, w.astype(f32)).astype(x.dtype)


def _ffn_ragged(x, idx, w, W1, W3, W2, first):
    N, k = idx.shape
    E = W1.shape[0]
    order, local, sizes = _sorted_pairs(idx, first, E)
    xs = x[order // k]
    # rows past the held experts' runs belong to no group: ragged_dot
    # leaves them zero
    a = jax.lax.ragged_dot(xs, W1, sizes, preferred_element_type=f32)
    b = jax.lax.ragged_dot(xs, W3, sizes, preferred_element_type=f32)
    h = (a * jax.nn.sigmoid(a) * b).astype(x.dtype)
    y = jax.lax.ragged_dot(h, W2, sizes, preferred_element_type=f32)
    wk = jnp.where(local < E, w.reshape(N * k)[order].astype(f32), 0.0)
    out = jnp.zeros((N, x.shape[1]), f32).at[order // k].add(y * wk[:, None])
    return out.astype(x.dtype)


@functools.partial(jax.jit, static_argnames=("first", "fused", "interpret"))
def _expert_ffn(x, idx, w, W1, W3, W2, *, first, fused, interpret):
    if fused:
        tm = row_tile(idx.shape[0] * idx.shape[1], W1.shape[0])
        return _ffn_kernels(x, idx, w, W1, W3, W2, first, tm, interpret)
    return _ffn_ragged(x, idx, w, W1, W3, W2, first)


def expert_ffn(x, idx, w, W1, W3, W2, *, first: int = 0,
               kernels: bool = True):
    """The held experts' part of a mixture-of-experts layer.

    x    [N, d] tokens
    idx  [N, k] int: the experts each token was routed to (global ids)
    w    [N, k]: the weight of each choice
    W1, W3 [E, d, F], W2 [E, F, d]: the experts held here, global ids
         ``first .. first + E - 1``; a pair routed elsewhere adds nothing

    Returns [N, d] in x's dtype. Every held pair is computed: no capacity,
    no dropped token. ``kernels=False`` keeps to ``ragged_dot``, which has
    a gradient (the Pallas kernels have none: a training step asks so)."""
    fused = kernels and kernels_applicable(x.shape[1], W1.shape[2], x.dtype)
    return _expert_ffn(x, idx, w, W1, W3, W2, first=int(first), fused=fused,
                       interpret=fused and _interpret())


def expert_ffn_reference(x, idx, w, W1, W3, W2, *, first: int = 0):
    """The same sum the plain way: every held expert over every token,
    masked to the pairs routed to it. Work ``tokens x experts``: the
    parity pin, never the serving path."""
    E = W1.shape[0]
    xf = x.astype(f32)
    a = jnp.einsum("nd,edf->enf", xf, W1.astype(f32))
    b = jnp.einsum("nd,edf->enf", xf, W3.astype(f32))
    y = jnp.einsum("enf,efd->end", a * jax.nn.sigmoid(a) * b, W2.astype(f32))
    hit = (idx[None] - first) == jnp.arange(E)[:, None, None]     # [E,N,k]
    we = jnp.sum(jnp.where(hit, w.astype(f32)[None], 0.0), -1)    # [E,N]
    return jnp.einsum("end,en->nd", y, we).astype(x.dtype)
