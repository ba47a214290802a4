"""Block-sparse attention's SELECTION (InfLLM v2, arXiv:2506.07900): which
blocks of keys a query attends to, chosen per query position and per
key-value group from scores against COMPRESSED keys.

    c_j        = mean(k[stride j : stride j + kernel])   one a ``stride``
                 visible to position t when the window is complete:
                 stride j + kernel - 1 <= t
    p[t, h, :] = softmax_j(q[t, h] . c_j * scale) over the visible j
    r[t, g, j] = sum of p over the group's query heads
    R[t, g, b] = max of r[t, g, j] over the windows that overlap block b
    chosen     = the first ``init_blocks`` blocks, the ``local_blocks``
                 blocks ending at t's own, and of the others those with the
                 largest R until ``topk`` are chosen in all; every block
                 up to t's own when there are at most ``topk`` of them or
                 t < ``dense_len``

Plain ``jax.numpy`` (XLA): the scoring is a small matmul against a sixteenth
of the keys, and the choice is made once (``choose_blocks``: the k-th
largest score found without a sort, ties to the earlier block) for a
prefill's mask and a decode step's lists alike. The attention over the
chosen blocks is ``ops.pallas_attention.flash_attention_sparse``
(a whole sequence, from a mask of chosen blocks) and
``ops.pallas_paged_attention.paged_attention_sparse_decode`` (one row a
slot, from a list of chosen pages); ``sparse_attention_xla`` is the plain
form both are held to.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

f32 = jnp.float32
NEG = -1e30
CHUNK = 256          # query positions a pass of ``chosen_mask`` scores


class Selection(NamedTuple):
    """The selection's sizes. ``block`` and ``kernel`` are whole multiples
    of ``stride``; a serving cache's page is one ``block``."""
    block: int = 64
    kernel: int = 32
    stride: int = 16
    topk: int = 64
    init_blocks: int = 1
    local_blocks: int = 32
    dense_len: int = 8192

    @classmethod
    def of(cls, fields) -> "Selection":
        sel = cls(**{k: int(v) for k, v in dict(fields).items()})
        if sel.block % sel.stride or sel.kernel % sel.stride \
                or min(sel) < 1:
            raise ValueError(f"{sel}: block and kernel are positive "
                             "multiples of stride")
        if sel.init_blocks + sel.local_blocks > sel.topk:
            raise ValueError(f"{sel}: the forced blocks are counted inside "
                             "topk")
        return sel

    @property
    def per_block(self) -> int:
        """Compressed keys that START in one block."""
        return self.block // self.stride

    def list_len(self, n_blocks: int) -> int:
        """Entries a list of chosen blocks needs over a context of
        ``n_blocks`` blocks: ``topk``, or every block of a context that
        is still read densely."""
        return min(n_blocks, max(self.topk, -(-self.dense_len // self.block)))


def compress_keys(k, sel: Selection):
    """k [B,T,Hkv,Dh] (T whole strides) -> [B, T / stride, Hkv, Dh] in k's
    dtype: entry j the mean of rows ``stride j .. stride j + kernel - 1``,
    summed in float32. The last ``kernel / stride - 1`` entries reach past
    T and are made with zeros: their windows are not complete, so no
    position sees them before a decode step has written them anew."""
    B, T, Hkv, Dh = k.shape
    J = T // sel.stride
    s = k.astype(f32).reshape(B, J, sel.stride, Hkv, Dh).sum(axis=2)
    total = s
    for e in range(1, sel.kernel // sel.stride):
        total = total + jnp.pad(s[:, e:], ((0, 0), (0, e), (0, 0), (0, 0)))
    return (total / sel.kernel).astype(k.dtype)


def visible_entries(t, sel: Selection):
    """Compressed keys position t sees: those whose window is complete."""
    return jnp.maximum((t - sel.kernel + 1 + sel.stride) // sel.stride, 0)


def block_scores(q, c, t, sel: Selection, scale: float):
    """q [B,Tq,H,Dh] at positions t [B,Tq] against the compressed keys c
    [B,J,Hkv,Dh] -> R [B,Tq,Hkv,nb] float32, ``nb = J / per_block``."""
    B, Tq, H, Dh = q.shape
    J, Hkv = c.shape[1], c.shape[2]
    qg = q.reshape(B, Tq, Hkv, H // Hkv, Dh)
    s = jnp.einsum("bqghd,bjgd->bqghj", qg, c,
                   preferred_element_type=f32) * scale
    seen = (jnp.arange(J)[None, None, :]
            < visible_entries(t, sel)[:, :, None])[:, :, None, None, :]
    p = jax.nn.softmax(jnp.where(seen, s, NEG), axis=-1)
    r = jnp.where(seen, p, 0.0).sum(axis=3)                  # [B,Tq,Hkv,J]
    per, back = sel.per_block, sel.kernel // sel.stride - 1
    nb = J // per
    R = r[..., :nb * per].reshape(B, Tq, Hkv, nb, per).max(axis=-1)
    for e in range(1, back + 1):
        # the windows that START in the block before and reach into this one
        prev = r[..., per - e:nb * per:per][..., :nb - 1]
        R = R.at[..., 1:].max(prev)
    return R


def _ranked(R, t, sel: Selection):
    """(score [.., nb] with the forced blocks at +inf and the blocks after
    t's own at -inf, tb [.., 1] t's own block)."""
    nb = R.shape[-1]
    b = jnp.arange(nb)
    tb = (t // sel.block)[..., None, None]
    forced = (b < sel.init_blocks) | (b > tb - sel.local_blocks)
    score = jnp.where(forced, jnp.inf, R)
    return jnp.where(b <= tb, score, -jnp.inf), tb


def _dense(t, tb, sel: Selection):
    return (t[..., None, None] < sel.dense_len) | (tb + 1 <= sel.topk)


def choose_blocks(R, t, sel: Selection):
    """R [B,Tq,Hkv,nb], t [B,Tq] -> chosen [B,Tq,Hkv,nb] bool: THE choice,
    which ``chosen_lists`` only lists. Made without a sort (a prefill
    chooses for every position: tens of thousands of rows): the
    ``topk``-th largest score of a row is found exactly (``kth_largest``);
    everything above it is chosen, and of the blocks that EQUAL it (a
    window that straddles two blocks gives both its score) the earliest,
    as many as the budget has left."""
    from .kth_largest import kth_largest
    nb = R.shape[-1]
    score, tb = _ranked(R, t, sel)
    k = min(sel.topk, nb)
    flat = score.reshape(-1, nb).astype(f32)
    kth = kth_largest(flat, jnp.full(flat.shape[:1], k, jnp.int32))
    above, equal = flat > kth, flat == kth
    left = k - above.sum(axis=-1, keepdims=True)
    # the place of each block among its row's equals: a count over the
    # earlier blocks as one matmul with a triangle of ones
    place = jnp.matmul(equal.astype(f32),
                       jnp.triu(jnp.ones((nb, nb), f32)),
                       precision=jax.lax.Precision.HIGHEST)
    listed = (above | (equal & (place <= left))).reshape(score.shape)
    return (jnp.arange(nb) <= tb) & (listed | _dense(t, tb, sel))


def chosen_lists(R, t, sel: Selection):
    """``choose_blocks`` as sorted lists (a decode step's form: a list of
    pages a slot and group): (blocks [B,Tq,Hkv,L] int32 ascending, counts
    [B,Tq,Hkv] int32), ``L = sel.list_len(nb)``; entries from ``counts``
    on are not to be read. t's own block is always the last one listed."""
    nb = R.shape[-1]
    chosen = choose_blocks(R, t, sel)
    blocks = jnp.sort(jnp.where(chosen, jnp.arange(nb, dtype=jnp.int32), nb),
                      axis=-1)[..., :sel.list_len(nb)]
    return jnp.minimum(blocks, nb - 1), chosen.sum(axis=-1, dtype=jnp.int32)


def chosen_mask(q, c, sel: Selection, scale: float):
    """The choice of every position of whole sequences: q [B,T,H,Dh], c
    [B,J,Hkv,Dh] -> [B,Hkv,nb,T] float32 (1 chosen, 0 not), blocks on the
    second-last axis and queries on the last, as the flash kernel lays its
    scores out. Scored ``CHUNK`` queries at a time: a chunk's softmax over
    32 heads and 2,048 compressed keys is 67 MB, a 32k sequence's 8.6 GB."""
    B, T, H, Dh = q.shape
    C = CHUNK if T % CHUNK == 0 else T
    n = T // C

    def one(args):
        qc, t0 = args
        t = jnp.broadcast_to(t0 + jnp.arange(C)[None, :], (B, C))
        return choose_blocks(block_scores(qc, c, t, sel, scale), t, sel)

    qs = q.reshape(B, n, C, H, Dh).transpose(1, 0, 2, 3, 4)
    ch = jax.lax.map(one, (qs, jnp.arange(n) * C))      # [n,B,C,Hkv,nb]
    return ch.transpose(1, 3, 4, 0, 2).reshape(
        B, ch.shape[3], ch.shape[4], T).astype(f32)


def sparse_attention_xla(q, k, v, chosen, sel: Selection, scale: float,
                         key_mask=None):
    """Causal attention over the chosen blocks the plain way: q [B,H,T,Dh],
    k/v [B,Hkv,T,Dh], chosen [B,Hkv,nb,T] -> [B,H,T,Dh]. The whole score
    square: tests, training, the CPU."""
    B, H, T, Dh = q.shape
    Hkv = k.shape[1]
    qg = q.reshape(B, Hkv, H // Hkv, T, Dh)
    s = jnp.einsum("bghqd,bgkd->bghqk", qg, k,
                   preferred_element_type=f32) * scale
    listed = jnp.repeat(chosen, sel.block, axis=2)[:, :, :T]   # [B,Hkv,T(k),T(q)]
    seen = (listed.transpose(0, 1, 3, 2) > 0) & \
        (jnp.arange(T)[None, :] <= jnp.arange(T)[:, None])
    if key_mask is not None:
        seen = seen & (key_mask[:, None, None, :] > 0)
    p = jax.nn.softmax(jnp.where(seen[:, :, None], s, NEG), axis=-1)
    o = jnp.einsum("bghqk,bgkd->bghqd", p.astype(v.dtype), v,
                   preferred_element_type=f32)
    return o.reshape(B, H, T, Dh).astype(q.dtype)
