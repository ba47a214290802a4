"""Paged decode attention (TPU Pallas): attention over a paged KV cache
that reads the pages where they lie.

The decode step's XLA path first copied every slot's whole block table out
of the pool into a dense ``[S, H, ctx, Dh]`` array and then attended to it
under a mask: for 16 slots of 1024 positions that is the whole table
written and read again per layer per token, whatever the live lengths.
This kernel walks each slot's block table instead (scalar prefetch) and
DMAs only the pages that hold live keys from the pool in HBM into VMEM,
folding them into an online softmax: HBM traffic is the live keys and
values, once.

Layout. A pool is ``[n_layers, num_blocks, block_len, H * Dh]``: one page
of one layer is ``block_len`` rows of all heads side by side, contiguous
and lane-dense (a ``[.., H, Dh]`` minor pair with ``Dh`` = 64 fills half
of each 128-lane row in VMEM, and cost the prefill's scatter a copy of
the pool, PERF.md PR 27). The layer is picked by index inside the DMA, so
no slice of the pool is ever materialised.

Heads are folded into ONE matmul per page group rather than looped over:
the queries enter as a block-diagonal ``[W * H, H * Dh]`` matrix (row
``w * H + h`` carries head ``h``'s query in its own ``Dh`` lanes, zeros
elsewhere), so ``q_bd @ K^T`` is every head's score row at once and
``p @ V`` every head's output in its own lanes (the off-diagonal blocks are
discarded). That spends H times the FLOPs the algorithm needs on an MXU
that a one-row decode leaves idle anyway, and needs no per-head slice of a
page, which the tiling would make a strided gather.

A LATENT cache (multi-head latent attention) is ONE pool of rows with no
head axis, ``[n_layers, num_blocks, block_len, row]``: every query head
reads the same row as its key (all lanes) and as its value (the first
``value_lanes`` lanes: the latent). ``paged_attention_decode`` takes it
with ``v_pool=None``: the kernel fetches each page once and takes the
values as a slice of the key rows it holds, under the name
``paged_attention_latent_decode``. The queries arrive absorbed into the
rows' space, so the softmax scale is the caller's (the layer's 1 /
sqrt(192), not 1 / sqrt(row)).

A SLIDING WINDOW (``starts``: the first key position a slot sees) walks
the pages from that position's page on and masks the keys before it, under
the name ``paged_attention_window_decode``; the tables stay logical, so a
cache that keeps a window's rows in a ring maps page j to ring page ``j
mod ring_pages`` in the table it hands in
(``serving/generation/kvcache.py``).

Precision: scores, the softmax recurrence (running max and sum) and both
accumulations are float32; ``p`` is cast to the pool's dtype for ``p @ V``
and the output is the pool's dtype, as on the XLA path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from .pallas_attention import NEG, _device_split

f32 = jnp.float32

# names in the compiled program and in a device trace (see
# pallas_attention.SCOPE for why a call sits in two scopes)
SCOPE = "paged_attention"
KERNEL_NAME = "paged_attention_decode"
LATENT_KERNEL_NAME = "paged_attention_latent_decode"
WINDOW_KERNEL_NAME = "paged_attention_window_decode"
SPARSE_KERNEL_NAME = "paged_attention_sparse_decode"

# keys folded into the online softmax per step: 16 pages of 16, the DMAs of
# one step in flight while the previous step's pages are computed on. On
# the v5e at the serving cells' shape 256 beat 128 and 512 at long
# contexts (16 x 750 keys x 24 layers: 2.18 against 2.40 and 2.31 ms) and
# tied them at short ones (PERF.md, PR 27)
_KEYS_PER_STEP = 256
# the same for a latent pool, whose rows are 640 lanes wide: on the v5e, 40
# slots of 4k-17k rows x 6 layers took 7.48 ms at 256 keys a step and 5.57
# at 512, in pages of 64 (5.56 in pages of 128, 5.83 in pages of 32;
# PERF.md, PR 40): one step's matmuls are 32 query rows against the keys,
# and longer steps amortize more of each
_LATENT_KEYS_PER_STEP = 512


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _body(W, H, Dh, blk, G, cap, scale, Gq, Dv, *refs, windowed=False):
    """``Dv`` None: K and V pools, H heads of Dh side by side. ``Dv`` an
    int: one latent pool (H = 1), the values are the first Dv lanes of
    the key rows. ``windowed``: a fourth prefetched scalar a slot, the
    first key position it sees; the walk starts at that position's page
    and the keys before it in that page are masked."""
    if windowed:
        starts_ref, refs = refs[3], refs[:3] + refs[4:]
    if Dv is None:
        (layer_ref, tables_ref, lens_ref, q_ref, k_hbm, v_hbm, o_ref,
         kbuf, vbuf, sem, m_s, l_s, acc_s) = refs
        pools = ((k_hbm, kbuf), (v_hbm, vbuf))
    else:
        (layer_ref, tables_ref, lens_ref, q_ref, k_hbm, o_ref,
         kbuf, sem, m_s, l_s, acc_s) = refs
        pools = ((k_hbm, kbuf),)
    T = G * blk
    s = pl.program_id(0)
    layer = layer_ref[0]
    n0 = lens_ref[s]                      # keys row 0 sees; 0 = idle slot
    last = jnp.where(n0 > 0, jnp.minimum(n0 + (W - 1), cap), 0)
    npages = (last + (blk - 1)) // blk
    if windowed:
        # groups count from the page that holds the first key seen: the
        # pages before it are never fetched
        start = jnp.minimum(starts_ref[s], last)
        page0 = start // blk
        ngroups = (npages - page0 + (G - 1)) // G
    else:
        ngroups = (npages + (G - 1)) // G

    def each_copy(group, slot, act):
        """``act`` ("start" or "wait") on the DMA of every live page of
        ``group``: page ``tables[s, j]`` of this layer into rows
        ``g * blk`` of the group's buffer. Pages past the slot's length
        are not fetched."""
        for g in range(G):
            page = group * G + g
            if windowed:
                page = page + page0

            @pl.when(page < npages)
            def _():
                bid = tables_ref[s, page]
                for c, (pool, buf) in enumerate(pools):
                    getattr(pltpu.make_async_copy(
                        pool.at[layer, bid],
                        buf.at[slot, pl.ds(g * blk, blk)],
                        sem.at[c, slot]), act)()

    m_s[:] = jnp.full_like(m_s, NEG)
    l_s[:] = jnp.zeros_like(l_s)
    acc_s[:] = jnp.zeros_like(acc_s)

    @pl.when(ngroups > 0)
    def _first():
        each_copy(0, 0, "start")

    rows = jax.lax.broadcasted_iota(jnp.int32, (acc_s.shape[0], T), 0)
    # row (w * Gq + g) * H + h belongs to window position w, which sees
    # n0 + w keys (Gq query heads read one key-value head; 1 without groups)
    limit = jnp.minimum(n0 + rows // (Gq * H), last)

    def step(gi, carry):
        slot = gi % 2

        @pl.when(gi + 1 < ngroups)
        def _next():
            each_copy(gi + 1, 1 - slot, "start")

        each_copy(gi, slot, "wait")
        k = kbuf[slot]
        v = vbuf[slot] if Dv is None else k[:, :Dv]
        sc = jax.lax.dot_general(q_ref[0], k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=f32) * scale
        kpos = gi * T + jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
        if windowed:
            kpos = kpos + page0 * blk
            sc = jnp.where((kpos < limit) & (kpos >= start), sc, NEG)
        else:
            sc = jnp.where(kpos < limit, sc, NEG)
        m_prev = m_s[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
        p = jnp.exp(sc - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_s[:] = jnp.broadcast_to(
            l_s[:, :1] * corr + p.sum(1, keepdims=True), l_s.shape)
        # rows of the buffer past the slot's length were never fetched (or
        # are the unwritten tail of its last page): whatever they hold,
        # 0 * it must stay 0
        vpos = gi * T + jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
        if windowed:
            vpos = vpos + page0 * blk
        v = jnp.where(vpos < last, v, jnp.zeros_like(v))
        acc_s[:] = acc_s[:] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=f32)
        m_s[:] = jnp.broadcast_to(m_new, m_s.shape)
        return carry

    jax.lax.fori_loop(0, ngroups, step, 0)

    l = l_s[:, :1]
    out = acc_s[:] * jnp.where(l > 0, 1.0 / l, 0.0)     # idle slot: zeros
    if Dv is not None:                 # one "head": every row is its own
        o_ref[0] = out[:W * Gq].astype(o_ref.dtype)
        return
    r = jax.lax.broadcasted_iota(jnp.int32, out.shape, 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, out.shape, 1)
    out = jnp.where(lane // Dh == r % H, out, 0.0)      # head h, own lanes
    for w in range(W * Gq):
        o_ref[0, w:w + 1, :] = out[w * H:(w + 1) * H].sum(
            axis=0, keepdims=True).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames="interpret")
def _one_device(layer, q, k_pool, v_pool, tables, lens, starts=None, *,
                interpret):
    """One device's heads: q [S,Hq,W,Dh], pools [L,nb,blk,H*Dh] with
    ``Hq = Gq * H`` (query head ``h * Gq + g`` reads key-value head ``h``;
    Gq = 1 is plain multi-head attention); ``layer`` an int32 scalar. Jitted with the layer an OPERAND, so that a program
    of 24 layers traces this and lowers the kernel to Mosaic once, not 24
    times (7 s of every process's warm-up at the serving cells' shape).
    ``starts`` [S]: the windowed case, the first key position a slot sees
    (``paged_attention_window_decode``)."""
    windowed = starts is not None
    S, Hq, W, Dh = q.shape
    HD = k_pool.shape[3]
    H = HD // Dh                          # key-value heads
    Gq = Hq // H
    blk = k_pool.shape[2]
    mb = tables.shape[1]
    G = max(1, min(mb, _KEYS_PER_STEP // blk))
    T = G * blk
    R = W * Hq
    Rp = -(-R // 16) * 16                 # whole sublane tiles, bf16 too
    # block-diagonal queries [S, W*Gq*H, H*Dh]: row (w*Gq+g)*H+h holds
    # q[s, h*Gq+g, w] in lanes h*Dh..(h+1)*Dh
    qt = q.reshape(S, H, Gq, W, Dh).transpose(0, 3, 2, 1, 4)  # [S,W,Gq,H,Dh]
    qt = qt.reshape(S, W * Gq, H, 1, Dh)
    own = jnp.eye(H, dtype=bool)[None, None, :, :, None]
    q_bd = jnp.where(own, qt, jnp.zeros((), q.dtype)).reshape(S, R, HD)
    q_bd = jnp.pad(q_bd, ((0, 0), (0, Rp - R), (0, 0)))
    scalars = (layer.reshape(1), tables.astype(jnp.int32),
               lens.astype(jnp.int32))
    if windowed:
        scalars += (starts.astype(jnp.int32),)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),  # layer, tables, lens (, starts)
        grid=(S,),
        in_specs=[pl.BlockSpec((1, Rp, HD), lambda s, *_: (s, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, W * Gq, HD), lambda s, *_: (s, 0, 0)),
        scratch_shapes=[pltpu.VMEM((2, T, HD), k_pool.dtype),
                        pltpu.VMEM((2, T, HD), v_pool.dtype),
                        pltpu.SemaphoreType.DMA((2, 2)),
                        pltpu.VMEM((Rp, 128), f32),
                        pltpu.VMEM((Rp, 128), f32),
                        pltpu.VMEM((Rp, HD), f32)])
    with jax.named_scope(SCOPE):
        o = pl.pallas_call(
            functools.partial(_body, W, H, Dh, blk, G, mb * blk,
                              1.0 / float(np.sqrt(Dh)), Gq, None,
                              windowed=windowed),
            name=WINDOW_KERNEL_NAME if windowed else KERNEL_NAME,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((S, W * Gq, HD), q.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=interpret,
        )(*scalars, q_bd, k_pool, v_pool)
    # row w*Gq+g, lanes of head h -> query head h*Gq+g
    return o.reshape(S, W, Gq, H, Dh).transpose(0, 3, 2, 1, 4).reshape(
        S, Hq, W, Dh)


@functools.partial(jax.jit, static_argnames=("scale", "value_lanes",
                                             "interpret"))
def _one_device_latent(layer, q, pool, tables, lens, *, scale, value_lanes,
                       interpret):
    """The latent pool's case: q [S,Hq,W,row] absorbed queries, pool
    [L,nb,blk,row] with no head axis (every query head reads the whole
    row as its key and its first ``value_lanes`` lanes as its value).
    Returns [S,Hq,W,value_lanes]. One DMA a page."""
    S, Hq, W, row = q.shape
    blk = pool.shape[2]
    mb = tables.shape[1]
    G = max(1, min(mb, _LATENT_KEYS_PER_STEP // blk))
    T = G * blk
    R = W * Hq
    Rp = -(-R // 16) * 16
    # row w * Hq + g is query head g of window position w
    qr = jnp.pad(q.transpose(0, 2, 1, 3).reshape(S, R, row),
                 ((0, 0), (0, Rp - R), (0, 0)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,            # layer, tables, lens
        grid=(S,),
        in_specs=[pl.BlockSpec((1, Rp, row), lambda s, *_: (s, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, R, value_lanes), lambda s, *_: (s, 0, 0)),
        scratch_shapes=[pltpu.VMEM((2, T, row), pool.dtype),
                        pltpu.SemaphoreType.DMA((1, 2)),
                        pltpu.VMEM((Rp, 128), f32),
                        pltpu.VMEM((Rp, 128), f32),
                        pltpu.VMEM((Rp, value_lanes), f32)])
    with jax.named_scope(SCOPE):
        o = pl.pallas_call(
            functools.partial(_body, W, 1, row, blk, G, mb * blk, scale, Hq,
                              value_lanes),
            name=LATENT_KERNEL_NAME,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((S, R, value_lanes), q.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=interpret,
        )(layer.reshape(1), tables.astype(jnp.int32), lens.astype(jnp.int32),
          qr, pool)
    return o.reshape(S, W, Hq, value_lanes).transpose(0, 2, 1, 3)


def _sparse_body(Hkv, Dh, blk, G, L, scale, layer_ref, pages_ref, nkeys_ref,
                 q_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sem, m_s, l_s,
                 acc_s):
    """Grid step (slot, key-value group): the group's query heads against
    the pages its list names, ``pages_ref[(s * Hkv + g) * L + j]`` the j-th
    of them, ``nkeys_ref[s * Hkv + g]`` the keys they hold up to the slot's
    own position (whole pages but the last). Of a page's rows only the
    group's own lanes are fetched: a row of the pool holds every key-value
    head side by side."""
    s, g = pl.program_id(0), pl.program_id(1)
    sg = s * Hkv + g
    layer = layer_ref[0]
    n = nkeys_ref[sg]                     # 0: an idle slot
    npages = (n + (blk - 1)) // blk
    ngroups = (npages + (G - 1)) // G
    T = G * blk
    lanes = pl.ds(pl.multiple_of(g * Dh, 128), Dh)

    def each_copy(group, slot, act):
        for p in range(G):
            page = group * G + p

            @pl.when(page < npages)
            def _():
                bid = pages_ref[sg * L + page]
                for c, (pool, buf) in enumerate(((k_hbm, kbuf),
                                                 (v_hbm, vbuf))):
                    getattr(pltpu.make_async_copy(
                        pool.at[layer, bid, :, lanes],
                        buf.at[slot, pl.ds(p * blk, blk)],
                        sem.at[c, slot]), act)()

    m_s[:] = jnp.full_like(m_s, NEG)
    l_s[:] = jnp.zeros_like(l_s)
    acc_s[:] = jnp.zeros_like(acc_s)

    @pl.when(ngroups > 0)
    def _first():
        each_copy(0, 0, "start")

    def step(gi, carry):
        slot = gi % 2

        @pl.when(gi + 1 < ngroups)
        def _next():
            each_copy(gi + 1, 1 - slot, "start")

        each_copy(gi, slot, "wait")
        k, v = kbuf[slot], vbuf[slot]
        sc = jax.lax.dot_general(q_ref[0, 0], k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=f32) * scale
        kpos = gi * T + jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
        sc = jnp.where(kpos < n, sc, NEG)
        m_prev = m_s[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
        p = jnp.exp(sc - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_s[:] = jnp.broadcast_to(
            l_s[:, :1] * corr + p.sum(1, keepdims=True), l_s.shape)
        # rows never fetched, or the unwritten tail of the last page:
        # whatever they hold, 0 * it must stay 0
        vpos = gi * T + jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
        v = jnp.where(vpos < n, v, jnp.zeros_like(v))
        acc_s[:] = acc_s[:] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=f32)
        m_s[:] = jnp.broadcast_to(m_new, m_s.shape)
        return carry

    jax.lax.fori_loop(0, ngroups, step, 0)
    l = l_s[:, :1]
    o_ref[0, 0] = (acc_s[:] * jnp.where(l > 0, 1.0 / l, 0.0)).astype(
        o_ref.dtype)


def _listed_keys(counts, lens, blk):
    """Keys a (slot, group)'s list holds: whole pages but the last listed,
    which is the slot's own, its rows up to the slot's position; 0 for an
    idle slot. counts [S,Hkv], lens [S] -> [S,Hkv]."""
    return jnp.where(lens[:, None] > 0,
                     (counts - 1) * blk + (lens[:, None] - 1) % blk + 1, 0)


@functools.partial(jax.jit, static_argnames="interpret")
def _one_device_sparse(layer, q, k_pool, v_pool, pages, counts, lens, *,
                       interpret):
    S, Hq, _, Dh = q.shape
    blk = k_pool.shape[2]
    Hkv = k_pool.shape[3] // Dh
    Gq = Hq // Hkv
    L = pages.shape[2]
    G = max(1, min(L, _KEYS_PER_STEP // blk))
    T = G * blk
    Rp = -(-Gq // 16) * 16
    qg = jnp.pad(q.reshape(S, Hkv, Gq, Dh),
                 ((0, 0), (0, 0), (0, Rp - Gq), (0, 0)))
    nkeys = _listed_keys(counts, lens, blk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,            # layer, pages, keys a (slot, group)
        grid=(S, Hkv),
        in_specs=[pl.BlockSpec((1, 1, Rp, Dh), lambda s, g, *_: (s, g, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, 1, Rp, Dh), lambda s, g, *_: (s, g, 0, 0)),
        scratch_shapes=[pltpu.VMEM((2, T, Dh), k_pool.dtype),
                        pltpu.VMEM((2, T, Dh), v_pool.dtype),
                        pltpu.SemaphoreType.DMA((2, 2)),
                        pltpu.VMEM((Rp, 128), f32),
                        pltpu.VMEM((Rp, 128), f32),
                        pltpu.VMEM((Rp, Dh), f32)])
    with jax.named_scope(SCOPE):
        o = pl.pallas_call(
            functools.partial(_sparse_body, Hkv, Dh, blk, G, L,
                              1.0 / float(np.sqrt(Dh))),
            name=SPARSE_KERNEL_NAME, grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((S, Hkv, Rp, Dh), q.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary")),
            interpret=interpret,
        )(layer.reshape(1), pages.astype(jnp.int32).reshape(-1),
          nkeys.astype(jnp.int32).reshape(-1), qg, k_pool, v_pool)
    return o[:, :, :Gq].reshape(S, Hq, 1, Dh)


def paged_attention_sparse_decode(q, k_pool, v_pool, layer: int, pages,
                                  counts, lens):
    """Softmax attention of ONE query row a slot over the pages its
    SELECTION names (``ops/sparse_select.py``), a list a slot and
    key-value group:

    q       [S, Hq, 1, Dh]; query head i reads key-value head
            ``i // (Hq // Hkv)`` and its group's list
    k_pool, v_pool  [n_layers, num_blocks, block_len, Hkv * Dh]; a page is
            one block of the selection
    pages   [S, Hkv, L] int32: the POOL's pages of the chosen blocks, in
            the sequence's order, the slot's own (last, partly filled)
            page last
    counts  [S, Hkv] int32: how many of them are listed
    lens    [S] int32: ``pos + 1``; 0 marks an idle slot

    Every listed page but the last is whole, so a list reads as a short
    sequence of its own: the kernel is the paged walk over ``pages`` in
    place of the slot's table, fetching the group's lanes of each page.
    Returns [S, Hq, 1, Dh]. One device."""
    if _device_split(q.shape[0], 1) is not None:
        raise ValueError("a selected decode reads its pools on one device")
    return _one_device_sparse(jnp.asarray(layer, jnp.int32), q, k_pool,
                              v_pool, pages, counts, lens,
                              interpret=_interpret())


def paged_attention_sparse_reference(q, k_pool, v_pool, layer: int, pages,
                                     counts, lens):
    """The same the plain way: gather the listed pages and attend under a
    mask. The kernel's parity pin, and the path off the TPU."""
    S, Hq, _, Dh = q.shape
    blk = k_pool.shape[2]
    Hkv = k_pool.shape[3] // Dh
    L = pages.shape[2]

    def listed(pool):                    # [S, Hkv, L * blk, Dh]
        rows = pool[layer][pages].reshape(S, Hkv, L * blk, Hkv, Dh)
        return jnp.stack([rows[:, g, :, g] for g in range(Hkv)], axis=1)

    nkeys = _listed_keys(counts, lens, blk)
    qg = q.reshape(S, Hkv, Hq // Hkv, Dh)
    s = jnp.einsum("sghd,sgkd->sghk", qg, listed(k_pool),
                   preferred_element_type=f32) / np.sqrt(Dh)
    seen = jnp.arange(L * blk)[None, None, :] < nkeys[:, :, None]
    p = jax.nn.softmax(jnp.where(seen[:, :, None], s, NEG), axis=-1)
    p = jnp.where(seen[:, :, None], p, 0.0)
    o = jnp.einsum("sghk,sgkd->sghd", p.astype(q.dtype), listed(v_pool),
                   preferred_element_type=f32)
    return o.reshape(S, Hq, 1, Dh).astype(q.dtype)


def paged_attention_decode(q, k_pool, v_pool, layer: int, tables, lens, *,
                           scale=None, value_lanes=None, starts=None):
    """Softmax attention of a decode window over a paged cache.

    q       [S, Hq, W, Dh]: W query rows a slot (1 in the decode step,
            k + 1 in a speculative verify)
    k_pool, v_pool  [n_layers, num_blocks, block_len, H * Dh]; ``layer``
            picks the layer inside the kernel's DMAs. ``Hq`` is ``H`` or a
            multiple of it: query head i reads key-value head
            ``i // (Hq // H)`` (grouped-query attention)
    tables  [S, max_blocks] int32: position p of slot s lies in page
            ``tables[s, p // block_len]``
    lens    [S] int32: keys row 0 of the slot sees (``pos + 1``, the
            step's own token already written); row w sees ``lens + w``.
            0 marks an idle slot: it fetches nothing and returns zeros.

    A latent cache: ``v_pool`` None, ``k_pool`` [n_layers, num_blocks,
    block_len, row] with no head axis, q [S, Hq, W, row] the queries
    absorbed into the rows' space, ``scale`` the softmax scale (required:
    it is not 1 / sqrt(row)) and ``value_lanes`` the leading lanes of a row
    that are its value. Returns [S, Hq, W, value_lanes]. A latent pool has
    no heads to split over a mesh.

    A sliding window: ``starts`` [S] int32, the first key position a slot
    sees (``max(0, pos + 1 - window)`` in a decode step); the walk begins
    at the page ``tables[s, starts // block_len]``, the pages before it
    are not fetched and the keys before ``starts`` in that page are
    masked. The tables stay LOGICAL (page j holds positions j * block_len
    ...): a cache that keeps a window's rows in a ring hands in a table
    that maps page j to ring page ``j mod ring_pages``. Runs under the
    name ``paged_attention_window_decode``; K/V pools on one device only.

    Returns [S, H, W, Dh] in q's dtype. Under a mesh tracing context the
    heads split over the model axis with ``shard_map`` (a Mosaic call is
    not partitioned automatically); attention is head-local."""
    if starts is not None:
        if v_pool is None or _device_split(q.shape[0], 1) is not None:
            raise ValueError("a windowed decode reads K/V pools on one "
                             "device")
        return _one_device(jnp.asarray(layer, jnp.int32), q, k_pool, v_pool,
                           tables, lens, starts, interpret=_interpret())
    if v_pool is None:
        if scale is None or value_lanes is None:
            raise ValueError("a latent pool needs scale and value_lanes")
        return _one_device_latent(
            jnp.asarray(layer, jnp.int32), q, k_pool, tables, lens,
            scale=float(scale), value_lanes=int(value_lanes),
            interpret=_interpret())
    S = q.shape[0]
    H = k_pool.shape[3] // q.shape[3]
    layer = jnp.asarray(layer, jnp.int32)
    kernel = functools.partial(_one_device, interpret=_interpret())
    split = _device_split(S, H)
    if split is None:
        return kernel(layer, q, k_pool, v_pool, tables, lens)
    spec, axis_names = split
    heads = spec[1]                        # slots stay whole: S is small
    return jax.shard_map(
        kernel,
        in_specs=(P(), P(None, heads), P(None, None, None, heads),
                  P(None, None, None, heads), P(), P()),
        out_specs=P(None, heads), axis_names=axis_names,
        check_vma=False)(layer, q, k_pool, v_pool, tables, lens)


def paged_attention_reference(q, k_pool, v_pool, layer: int, tables, lens,
                              *, scale=None, value_lanes=None, starts=None):
    """The same attention the plain way: gather every slot's whole table
    into a dense context and attend under a mask. The parity pin of the
    kernel, and what the decode step did before it."""
    from ..models.decode import window_attention
    S, Hq, W, Dh = q.shape
    if v_pool is None:                 # a latent pool: one row, all heads
        ctx = k_pool[layer][tables].reshape(S, 1, -1, Dh)
        limit = lens[:, None] + jnp.arange(W)[None, :]
        mask = jnp.arange(ctx.shape[2])[None, None, :] < limit[:, :, None]
        # window_attention scales by 1 / sqrt(Dh): fold the rest into q
        qs = (q.astype(f32) * (scale * np.sqrt(Dh))).astype(q.dtype)
        ctx = jnp.broadcast_to(ctx, (S, Hq) + ctx.shape[2:])
        out = window_attention(qs, ctx, ctx[..., :value_lanes], mask)
        return jnp.where((lens > 0)[:, None, None, None], out,
                         jnp.zeros((), out.dtype))
    H = k_pool.shape[3] // Dh
    ctx = tables.shape[1] * k_pool.shape[2]

    def dense(pool):
        d = pool[layer][tables].reshape(S, ctx, H, Dh).transpose(0, 2, 1, 3)
        return jnp.repeat(d, Hq // H, axis=1)

    limit = lens[:, None] + jnp.arange(W)[None, :]                # [S,W]
    mask = jnp.arange(ctx)[None, None, :] < limit[:, :, None]
    if starts is not None:
        mask = mask & (jnp.arange(ctx)[None, None, :]
                       >= starts[:, None, None])
    out = window_attention(q, dense(k_pool), dense(v_pool), mask)
    return jnp.where((lens > 0)[:, None, None, None], out,
                     jnp.zeros((), out.dtype))
