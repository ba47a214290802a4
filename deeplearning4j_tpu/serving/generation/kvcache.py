"""Paged KV cache: fixed-size block pool + per-sequence block tables.

The central shape discipline of the decode subsystem: the cache is ONE pair
of pool arrays per model —

    k_pool / v_pool : [n_layers, num_blocks, block_len, n_heads * head_dim]

(``n_layers`` the model's ATTENTION layers and ``n_heads`` its key-value
heads: with grouped-query attention fewer than its query heads; one page of one layer is ``block_len`` rows of all heads side by side:
contiguous, lane-dense, one DMA for the attention kernel) — and a
sequence's cache is the set of pool blocks its (host-side) block table
points at. "Growing" a sequence's context is block *allocation*, a
bookkeeping edit to an int32 table; no device array ever changes shape, so
nothing ever recompiles (the vLLM PagedAttention idea fused with the
repo's AOT-warmed-program discipline).

A cache has a KIND. ``kv``: the pair above. ``latent`` (multi-head latent
attention): ONE pool ``[n_layers, num_blocks, block_len, row]`` whose rows
carry no head axis: a token's normed latent and the one rotated key part
all heads share (576 values, laid out on 640 lanes), read by every query
head as its key and, in its leading lanes, as its value. A latent cache is
the 1-tuple ``(pool,)``; every helper below takes either kind.

A model may also have attention layers with a SLIDING WINDOW beside those
that keep the whole context. Such a layer's rows are not paged: each decode
slot owns a RING of ``ring_pages = window / block_len + 1`` pages a window
layer (``make_rings``: ``[n_window_layers, slots + 1, ring_pages,
block_len, H * Dh]`` for K and for V, the last slot the prefill's trash
row), position p of a sequence lying in ring page ``(p // block_len) mod
ring_pages`` at row ``p mod block_len``. A span of ``window`` positions
touches at most ``ring_pages`` whole pages, so the ring always holds the
window and a write never lands on a row the window still needs. Nothing is
allocated or freed as a sequence grows and no table is kept on the host:
the table that maps a sequence's logical pages onto its ring is a constant
(``ring_tables``), and the windowed decode kernel walks it from the page
of the window's first key. The full-context layers keep the pools, the
tables and the allocator below as they are; the rings ride behind them in
the same cache pytree.

A full-context layer may READ a block-sparse SELECTION of its pages
(``ops/sparse_select.py``; a page is one block of the selection). Its keys
and values are paged like any other's; beside them each decode slot keeps
the layer's COMPRESSED keys, one row of ``H * Dh`` every ``stride``
positions (``make_compressed``: ``[selecting layers, slots + 1, capacity /
stride, H * Dh]``, the last slot the trash row; a thirty-second of the
keys' bytes at the published sizes). A prefill fills a slot's rows from the
prompt's keys; a decode step that completes a window of ``kernel`` keys
reads them back from the pages and appends their mean; every step scores
its query against the slot's rows, chooses, and reads the chosen pages
alone (``PagedWindowStore._attend_selected``).

Block 0 is the reserved TRASH block: inactive decode slots and the unused
tail of a prefill's table all point at it, so the fixed-shape scatter always
has a legal destination and garbage lands where nothing ever reads it
(decode attention walks a slot's table only as far as its length).

Host side: ``BlockAllocator`` — a free-list over block ids 1..num_blocks-1.
Device side: pure scatter helpers used inside the jitted prefill and decode
programs; ``PagedStore`` / ``PagedWindowStore`` adapt them to the
``models.decode.KVStore`` protocol: scatter the step's K/V, then attend to
the pages IN PLACE (``ops.pallas_paged_attention``: the kernel reads page
``tables[s, j]`` of the layer straight from the pool, live pages only).
The int8 tier alone still gathers a dense context (``_pool_gather``) and
dequantizes it for XLA attention.
"""
from __future__ import annotations

from typing import List, NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp

from ...models.decode import window_attention
from ...ops import pallas_paged_attention as _paged
from ...ops.pallas_paged_attention import paged_attention_decode
from ..errors import BlockPoolExhaustedError


class BlockAllocator:
    """Free-list allocator over the pool's usable blocks (ids 1..n-1; block
    0 is the trash block). Not thread-safe by itself — the scheduler owns
    it from its single dispatch thread.

    Hardened bookkeeping (ISSUE 14): an explicit allocated set plus
    per-block refcounts (the prefix cache's sharing currency). Freeing a
    block that was never allocated, double-freeing, or freeing a block
    whose refcount is still nonzero all raise — a leak or double-free
    corrupts EVERY sequence sharing the pool, so it must die loudly at the
    first bad call, not surface later as silently-wrong tokens."""

    def __init__(self, num_blocks: int):
        if num_blocks < 2:
            raise ValueError("need >= 2 blocks (block 0 is reserved trash)")
        self.num_blocks = int(num_blocks)
        self._free: List[int] = list(range(self.num_blocks - 1, 0, -1))
        self._allocated: set = set()
        self._refcount: dict = {}

    @property
    def total_usable(self) -> int:
        return self.num_blocks - 1

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def used_blocks(self) -> int:
        return self.total_usable - len(self._free)

    @property
    def allocated(self) -> frozenset:
        return frozenset(self._allocated)

    def alloc(self, n: int) -> List[int]:
        if n > len(self._free):
            raise BlockPoolExhaustedError(
                f"block pool exhausted: need {n} blocks, "
                f"{len(self._free)}/{self.total_usable} free — retry after "
                f"in-flight generations release their blocks")
        got = [self._free.pop() for _ in range(n)]
        self._allocated.update(got)
        return got

    def free(self, ids: Sequence[int]) -> None:
        for b in ids:
            if not 1 <= b < self.num_blocks:
                raise ValueError(f"free of invalid block id {b}")
            if b not in self._allocated:
                raise ValueError(
                    f"free of unallocated block {b} (double free, or an id "
                    f"this allocator never handed out)")
            if self._refcount.get(b, 0):
                raise ValueError(
                    f"free of block {b} with refcount "
                    f"{self._refcount[b]} — shared blocks must be "
                    f"released through the prefix cache, not freed")
            self._allocated.discard(b)
            self._free.append(int(b))

    # ------------------------------------------------------------ refcounts
    def incref(self, b: int) -> int:
        if b not in self._allocated:
            raise ValueError(f"incref of unallocated block {b}")
        self._refcount[b] = self._refcount.get(b, 0) + 1
        return self._refcount[b]

    def decref(self, b: int) -> int:
        n = self._refcount.get(b, 0)
        if n < 1:
            raise ValueError(f"decref of block {b} below zero")
        n -= 1
        if n:
            self._refcount[b] = n
        else:
            del self._refcount[b]
        return n

    def refcount(self, b: int) -> int:
        return self._refcount.get(b, 0)


class QuantizedPool(NamedTuple):
    """int8-quantized block pool (ISSUE 17): the plain pool's blocks with
    the head axis kept apart, [n_layers, num_blocks, block_len, n_heads,
    *], each (token, head) vector stored as int8 codes plus ONE f32 scale —
    2*(Dh+4) bytes per token/layer/head instead of f32's 8*Dh, so the
    same ``num_blocks`` holds ~2-3.5x the tokens per byte (and every
    prefix-cache hit shares the smaller blocks). A NamedTuple is a pytree,
    so the cache stays the 2-tuple ``(k_entry, v_entry)`` the warmed
    programs, donation, and ``_cache_spec`` already handle."""
    q: jnp.ndarray        # int8 [n_layers, nb, blk, H, Dh]
    scale: jnp.ndarray    # f32  [n_layers, nb, blk, H]


def kv_quantize(x) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """[..., Dh] → (int8 codes [..., Dh], f32 scales [...]) — symmetric
    per-(token, head) scales. DETERMINISTIC: prefill, decode, replay and
    verify all quantize through this exact expression, which is what makes
    quantized greedy decode self-consistent token-for-token across the
    hit/miss/speculative paths."""
    amax = jnp.max(jnp.abs(x), axis=-1)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0).astype(jnp.float32)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def kv_dequantize(q, scale, dtype):
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


def make_pools(n_layers: int, num_blocks: int, block_len: int,
               n_heads: int, head_dim: int, dtype,
               quantized: bool = False, latent: bool = False) -> Tuple:
    """Zero-filled (k_pool, v_pool) — plain arrays, or ``QuantizedPool``
    pairs when ``quantized`` (the kv_cache_dtype="int8" tier); ``latent``:
    the one pool ``(pool,)`` of rows ``head_dim`` wide (``n_heads`` 1)."""
    shape = (n_layers, num_blocks, block_len, n_heads, head_dim)
    if latent:
        if quantized or n_heads != 1:
            raise ValueError("a latent pool is plain and has no head axis")
        return (jnp.zeros(shape[:3] + (head_dim,), dtype),)
    if quantized:
        def qp():
            return QuantizedPool(jnp.zeros(shape, jnp.int8),
                                 jnp.zeros(shape[:-1], jnp.float32))
        return qp(), qp()
    shape = shape[:3] + (n_heads * head_dim,)
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)


def ring_pages(window: int, block_len: int) -> int:
    """Pages of a slot's ring for a sliding window: the most whole pages a
    span of ``window`` positions can touch."""
    return -(-window // block_len) + 1


def make_rings(n_layers: int, slots: int, window: int, block_len: int,
               n_heads: int, head_dim: int, dtype) -> Tuple:
    """Zero-filled (k_ring, v_ring) for a model's sliding-window layers:
    ``[n_layers, slots + 1, ring_pages, block_len, n_heads * head_dim]``;
    row ``slots`` of the slot axis is the trash ring idle slots and a
    prefill's padding rows write to."""
    shape = (n_layers, slots + 1, ring_pages(window, block_len), block_len,
             n_heads * head_dim)
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)


def ring_as_pool(ring):
    """A ring as the attention kernel takes a pool: ``[n_layers, (slots +
    1) * ring_pages, block_len, H * Dh]`` (a reshape of contiguous axes: no
    copy)."""
    L, S1, rp, blk, hd = ring.shape
    return ring.reshape(L, S1 * rp, blk, hd)


def ring_tables(slots: int, pages: int, max_blocks: int):
    """[slots, max_blocks] int32: logical page j of slot s lies in ring
    page ``s * pages + j mod pages`` of ``ring_as_pool``'s view. A
    constant: a ring needs no table kept anywhere."""
    return (jnp.arange(slots, dtype=jnp.int32)[:, None] * pages
            + jnp.arange(max_blocks, dtype=jnp.int32)[None, :] % pages)


def ring_prefill_fill(ring, layer_kv, lengths, slots):
    """Leave each prompt's LAST rows in its slot's ring after a prefill:
    ring row r of a sequence of true length n takes the newest position p
    < n with ``p mod rows == r`` (``rows`` the ring's rows: its pages x
    block_len). Rows no position of the prompt maps to keep whatever the
    gather finds; they lie outside [first key, length) of every later
    step and are masked there.

    ring      [n_layers, slots + 1, ring_pages, blk, H*Dh]
    layer_kv  list of [P, L, H, Dh] a window layer (the rung's L rows)
    lengths   [P] the prompts' true lengths (not the rung)
    slots     [P] the slots the prompts were admitted to (padding rows
              carry the trash slot)"""
    _, _, rp, blk, hd = ring.shape
    rows = rp * blk
    r = jnp.arange(rows, dtype=jnp.int32)[None, :]
    n = lengths.astype(jnp.int32)[:, None]
    src = r + rows * ((n - 1 - r) // rows)                    # [P, rows]
    for i, kv in enumerate(layer_kv):
        P, L, H, Dh = kv.shape
        took = jnp.take_along_axis(
            kv.reshape(P, L, H * Dh), jnp.clip(src, 0, L - 1)[:, :, None],
            axis=1)
        ring = ring.at[i, slots].set(took.reshape(P, rp, blk, hd))
    return ring


def make_compressed(n_layers: int, slots: int, capacity: int, stride: int,
                    n_heads: int, head_dim: int, dtype):
    """Zero-filled compressed-key rows of a model's block-sparse layers:
    ``[n_layers, slots + 1, capacity / stride, n_heads * head_dim]``; row
    ``slots`` of the slot axis is the trash row idle slots and a prefill's
    padding rows write to."""
    return jnp.zeros((n_layers, slots + 1, capacity // stride,
                      n_heads * head_dim), dtype)


def compressed_prefill_fill(comp, layer_rows, slots):
    """Leave each prompt's compressed keys in its slot's rows after a
    prefill: ``layer_rows`` a list of [P, L / stride, H * Dh] a selecting
    layer (``GraphDecodeSpec.compressed_rows``), ``slots`` [P] (padding
    rows carry the trash slot). Entries whose window reaches past a
    prompt's true length hold padding: no position sees one before the
    decode step that completes its window has written it anew."""
    for i, rows in enumerate(layer_rows):
        comp = comp.at[i, slots, :rows.shape[1]].set(rows.astype(comp.dtype))
    return comp


def pool_bytes(pool) -> int:
    """Total device bytes of one pool entry (plain array or QuantizedPool)."""
    return sum(leaf.size * leaf.dtype.itemsize
               for leaf in jax.tree.leaves(pool))


def cow_copy(pools, src, dst):
    """Copy one block's content (every layer of every pool in ``pools``: K
    and V, or the one latent pool) from ``src`` to ``dst`` — the
    copy-on-write primitive for prefix sharing. ``src``/``dst`` are runtime
    int32 scalars, so ONE compiled program serves every copy; functional
    update keeps the read-before-write ordering a data dependency. Generic
    over plain and quantized pools (a quantized COW copies codes AND
    scales — bit-exact sharing). Returns the pools as a tuple."""
    copy = lambda p: p.at[:, dst].set(p[:, src])
    return tuple(jax.tree.map(copy, pool) for pool in pools)


def prefill_scatter(pool, layer_kv, tables):
    """Write a prefill's K or V for one layer into the pool.

    pool      [n_layers, nb, blk, H*Dh] (functional update), or a
              ``QuantizedPool`` (quantize-on-write)
    layer_kv  list of [P, L, H, Dh] per layer (L % blk == 0)
    tables    [P, max_blocks] int32 — first L//blk entries are the
              sequence's blocks (rest point at trash block 0).
    """
    P, L, H, Dh = layer_kv[0].shape
    if isinstance(pool, QuantizedPool):
        blk = pool.q.shape[2]
        nblk = L // blk
        qp, sp = pool
        for i, kv in enumerate(layer_kv):
            q, s = kv_quantize(kv)
            qp = qp.at[i, tables[:, :nblk]].set(
                q.reshape(P, nblk, blk, H, Dh))
            sp = sp.at[i, tables[:, :nblk]].set(
                s.reshape(P, nblk, blk, H))
        return QuantizedPool(qp, sp)
    blk = pool.shape[2]
    nblk = L // blk
    for i, kv in enumerate(layer_kv):
        pool = pool.at[i, tables[:, :nblk]].set(
            kv.reshape(P, nblk, blk, H * Dh))
    return pool


def _pool_write(pool, i, bid, off, tok):
    """Scatter one layer's token (or window) K/V at (bid, off) — the
    quantize-on-write seam. ``tok`` [..., H, Dh] with leading [S] or
    [S, W] index shape matching bid/off."""
    if isinstance(pool, QuantizedPool):
        q, s = kv_quantize(tok)
        return QuantizedPool(pool.q.at[i, bid, off].set(q),
                             pool.scale.at[i, bid, off].set(s))
    return pool.at[i, bid, off].set(tok.reshape(tok.shape[:-2] + (-1,)))


def _pool_gather(pool: QuantizedPool, i, tables, dtype):
    """Gather and dequantize one layer's full context, every slot's whole
    table → [S, H, ctx, Dh]: the int8 tier's dequantize-in-attention seam
    (plain pools are read in place by the attention kernel)."""
    S = tables.shape[0]
    H, Dh = pool.q.shape[-2:]
    ctx = pool.q[i][tables].reshape(S, -1, H, Dh)
    sc = pool.scale[i][tables].reshape(S, -1, H)
    return kv_dequantize(ctx, sc, dtype).transpose(0, 2, 1, 3)


def repeat_heads(x, group: int):
    """[S, Hkv, ...] -> [S, Hkv * group, ...]: each key-value head once for
    every query head that reads it (grouped-query attention on the XLA
    paths; the paged kernel needs no copy)."""
    return x if group == 1 else jnp.repeat(x, group, axis=1)


class QuantSimStore:
    """Full-prompt window store for the int8-KV PREFILL: records each
    layer's raw K/V (for the quantize-on-write scatter afterwards) and
    serves attention the FAKE-QUANTIZED context — dequantize(quantize(k))
    — with the causal row mask.

    Why it exists: a prefix-cache hit skips prefill and replays the
    unmatched suffix through the one-token decode program, whose
    attention sees dequantized int8 K/V. If prefill computed its logits
    from full-precision K/V, hit and miss paths would diverge token-for-
    token. Running the prefill through ``decode_window`` with this store
    makes row ``i`` see exactly what a decode step at position ``i``
    would read back from the quantized pool (quantization is
    deterministic, so the scatter stores the identical codes) — the
    quantized engine is self-consistent across prefill / decode / replay
    / speculative verify."""

    def __init__(self, n_layers: int):
        self.ks: List = [None] * n_layers
        self.vs: List = [None] * n_layers

    def attend(self, i: int, q, k_win, v_win):
        """q [B,H,W,Dh]; k_win/v_win [B,W,H,Dh]. Returns [B,H,W,Dh]."""
        self.ks[i] = k_win
        self.vs[i] = v_win
        B, W = k_win.shape[:2]

        def fakeq(x):
            codes, scale = kv_quantize(x)
            return kv_dequantize(codes, scale, x.dtype).transpose(0, 2, 1, 3)

        mask = (jnp.arange(W)[None, None, :]
                <= jnp.arange(W)[None, :, None])
        mask = jnp.broadcast_to(mask, (B, W, W))
        group = q.shape[1] // k_win.shape[2]
        return window_attention(q, repeat_heads(fakeq(k_win), group),
                                repeat_heads(fakeq(v_win), group), mask)


class PagedWindowStore:
    """``models.decode`` window store over the paged pools for ONE pass of
    W fed tokens per slot (W = k+1 in a speculative verify): they land at
    positions ``pos .. pos+W-1`` (crossing block boundaries via
    per-position (block, offset) indices), then row ``i`` attends to the
    keys at positions ``<= pos+i`` — exactly the visibility the one-token
    ``PagedStore`` gives position ``pos+i``, through the same kernel and
    the same arithmetic, which is what keeps a batched verify
    token-for-token identical to W sequential decode steps.

    Scatter, then attend in place: the window's K/V is written first, so
    the kernel finds it in the pool like any other key. Plain pools are
    never gathered: ``paged_attention_decode`` reads each slot's live pages
    through the block table. The int8 tier (``QuantizedPool``) gathers and
    dequantizes every slot's whole table for XLA attention, as both tiers
    did before the kernel. Idle slots scatter to the trash block, read
    nothing and get zeros."""

    def __init__(self, k_pool, v_pool, tables, pos, active, block_len: int,
                 window: int, rec=None, rings=None, comp=None):
        self.k_pool = k_pool
        self.v_pool = v_pool              # None: a latent cache, one pool
        # (k_ring, v_ring) of the model's sliding-window layers, or None
        self.rings = rings
        # compressed keys of the model's block-sparse layers, [layers,
        # slots + 1, capacity / stride, H*Dh], or None
        self.comp = comp
        self._pos, self._blk = pos, block_len
        # per-slot state of the model's recurrent mixers, one pool a KIND
        # of state: a tuple of [layers of the kind, slots + 1, ...] (row
        # ``slots`` is the prefill's trash row), or None for a model that
        # keeps K/V alone
        self.rec = None if rec is None else tuple(rec)
        self._active = active
        self.tables = tables              # [S, max_blocks] int32
        mb = tables.shape[1]
        ctx_len = mb * block_len
        w_pos = pos[:, None] + jnp.arange(window)[None, :]       # [S, W]
        bidx = jnp.clip(w_pos // block_len, 0, mb - 1)
        bid = jnp.take_along_axis(tables, bidx, axis=1)          # [S, W]
        # idle slots AND window positions past capacity (a verify window is
        # always W wide even when < W tokens of budget remain) go to trash —
        # a clipped in-range write would corrupt the last real block
        ok = active[:, None] & (w_pos < ctx_len)
        self._bid = jnp.where(ok, bid, 0)
        self._off = jnp.where(ok, w_pos % block_len, 0)
        # keys row 0 sees, this pass's first token included; 0 = idle
        self._lens = jnp.where(active, pos + 1, 0).astype(jnp.int32)
        if isinstance(k_pool, QuantizedPool):
            # row i of a slot's mask: keys at positions <= pos+i are visible
            self._mask = (jnp.arange(ctx_len)[None, None, :]
                          <= w_pos[:, :, None])                  # [S, W, ctx]

    def _attend_ring(self, i: int, q, k_tok, v_tok, window: int):
        """A sliding-window layer's step: write the token at its place in
        the slot's ring (idle slots write to the trash ring), then attend
        to the ring's pages from the window's first key on."""
        k_ring, v_ring = self.rings
        S = self.tables.shape[0]
        rp = k_ring.shape[2]
        slot = jnp.where(self._active, jnp.arange(S), S)
        page = (self._pos // self._blk) % rp
        off = self._pos % self._blk
        flat = lambda t: t.reshape(S, -1)
        k_ring = k_ring.at[i, slot, page, off].set(flat(k_tok))
        v_ring = v_ring.at[i, slot, page, off].set(flat(v_tok))
        self.rings = (k_ring, v_ring)
        starts = jnp.maximum(self._lens - window, 0)
        return paged_attention_decode(
            q, ring_as_pool(k_ring), ring_as_pool(v_ring), i,
            ring_tables(S, rp, self.tables.shape[1]), self._lens,
            starts=starts)

    def _attend_selected(self, i: int, q, sel, si: int):
        """A block-sparse layer's step, its token already in the pages:
        append the compressed key of a window this position completes,
        score the query against the slot's compressed keys, choose, and
        attend to the chosen pages alone."""
        from ...ops import sparse_select as ss
        S, mb = self.tables.shape
        pos, blk = self._pos, self._blk
        if blk != sel.block:
            raise ValueError(f"a page of {blk} rows is not the selection's "
                             f"block of {sel.block}")
        HD = self.k_pool.shape[3]
        # the window that ends here, if one does: entry j of the slot's rows
        back = pos - (sel.kernel - 1)
        done = self._active & (back >= 0) & (back % sel.stride == 0)
        w_pos = jnp.maximum(back, 0)[:, None] + jnp.arange(sel.kernel)[None, :]
        bid = jnp.take_along_axis(self.tables,
                                  jnp.clip(w_pos // blk, 0, mb - 1), axis=1)
        rows = self.k_pool[i, bid, w_pos % blk]                # [S,kernel,HD]
        mean = (rows.astype(jnp.float32).sum(axis=1) / sel.kernel).astype(
            self.comp.dtype)
        slot = jnp.where(done, jnp.arange(S), S)       # others: trash row
        self.comp = self.comp.at[si, slot, jnp.maximum(back, 0) // sel.stride
                                 ].set(mean)
        Dh = q.shape[-1]
        c = self.comp[si, :S].reshape(S, -1, HD // Dh, Dh)
        t = pos[:, None]
        R = ss.block_scores(q.transpose(0, 2, 1, 3), c, t, sel,
                            float(Dh) ** -0.5)
        blocks, counts = ss.chosen_lists(R, t, sel)            # [S,1,Hkv,L]
        pages = jnp.take_along_axis(
            self.tables[:, None, :], jnp.minimum(blocks[:, 0], mb - 1),
            axis=2)
        attend = _paged.paged_attention_sparse_reference \
            if _paged._interpret() else _paged.paged_attention_sparse_decode
        return attend(q, self.k_pool, self.v_pool, i, pages, counts[:, 0],
                      self._lens)

    def attend(self, i: int, q, k_win, v_win, window=None, select=None,
               select_index=None, **latent):
        """q [S,H,W,Dh]; k_win/v_win [S,W,H,Dh] for the window. Returns
        the attention output [S,H,W,Dh]. Over a latent cache: q
        [S,H,W,row] absorbed, k_win [S,W,1,row] the tokens' cache rows,
        v_win None, ``scale`` and ``value_lanes`` as
        ``paged_attention_decode`` takes them; returns
        [S,H,W,value_lanes]. ``window``: layer ``i`` of the sliding-window
        layers, one token a slot, through the slot's ring."""
        if window is not None:
            if k_win.shape[1] != 1 or self.rings is None:
                raise ValueError("a sliding-window layer takes one token "
                                 "a slot and a store that carries rings")
            return self._attend_ring(i, q, k_win[:, 0], v_win[:, 0], window)
        self.k_pool = _pool_write(self.k_pool, i, self._bid, self._off, k_win)
        if self.v_pool is None:
            return paged_attention_decode(q, self.k_pool, None, i,
                                          self.tables, self._lens, **latent)
        self.v_pool = _pool_write(self.v_pool, i, self._bid, self._off, v_win)
        if select is not None:
            if k_win.shape[1] != 1 or self.comp is None:
                raise ValueError("a block-sparse layer takes one token a "
                                 "slot and a store that carries its "
                                 "compressed keys")
            return self._attend_selected(i, q, select, select_index)
        if isinstance(self.k_pool, QuantizedPool):
            group = q.shape[1] // k_win.shape[2]
            K = _pool_gather(self.k_pool, i, self.tables, k_win.dtype)
            V = _pool_gather(self.v_pool, i, self.tables, v_win.dtype)
            return window_attention(q, repeat_heads(K, group),
                                    repeat_heads(V, group), self._mask)
        return paged_attention_decode(q, self.k_pool, self.v_pool, i,
                                      self.tables, self._lens)

    def state(self, j):
        """Recurrent mixer ``j`` = (kind, place in its kind): its state
        for the step's slots [S, ...]."""
        g, n = j
        return self.rec[g][n, :self.tables.shape[0]]

    def set_state(self, j, new) -> None:
        """Leave mixer ``j``'s state after the step; idle slots keep
        theirs."""
        g, n = j
        S = self.tables.shape[0]
        rec = self.rec[g]
        keep = self._active.reshape((S,) + (1,) * (new.ndim - 1))
        self.set_state_pool(g, rec.at[n, :S].set(
            jnp.where(keep, new.astype(rec.dtype), rec[n, :S])))

    @property
    def active(self):
        """[S] bool: the slots that are real this step."""
        return self._active

    def state_pool(self, g: int):
        """The whole pool of recurrent states of kind ``g``."""
        return self.rec[g]

    def set_state_pool(self, g: int, pool) -> None:
        self.rec = self.rec[:g] + (pool,) + self.rec[g + 1:]

    @property
    def pools(self):
        return (self.k_pool,) if self.v_pool is None \
            else (self.k_pool, self.v_pool)

    @property
    def cache(self):
        """The cache pytree a program hands back: the pools, and the
        recurrent state behind them where the model has one."""
        return self.pools + (() if self.rec is None else self.rec) \
            + (() if self.comp is None else (self.comp,)) \
            + (() if self.rings is None else tuple(self.rings))


class PagedStore(PagedWindowStore):
    """``models.decode.KVStore`` over the paged pools for ONE decode step:
    a window of one token a slot."""

    def __init__(self, k_pool, v_pool, tables, pos, active, block_len: int,
                 rec=None, rings=None, comp=None):
        super().__init__(k_pool, v_pool, tables, pos, active, block_len, 1,
                         rec, rings, comp)

    def attend(self, i: int, q, k_tok, v_tok, **kw):
        """q [S,H,1,Dh]; k_tok/v_tok [S,H,Dh] (v_tok None and the latent
        keywords over a latent cache; ``window`` for a sliding-window
        layer)."""
        return super().attend(i, q, k_tok[:, None],
                              None if v_tok is None else v_tok[:, None],
                              **kw)
