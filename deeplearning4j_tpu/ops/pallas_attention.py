"""Fused flash-attention Pallas kernels (TPU).

Net-new beyond the reference (its sequence story is LSTM-only — SURVEY.md
§5.7): the O(T) HBM-traffic attention primitive that makes long contexts
first-class. The XLA path (parallel/ring_attention.attention) materializes
the [B,H,T,T] score tensor in HBM; these kernels keep each [BQ,BK] score
block in VMEM with the online-softmax recurrence, so HBM traffic is
O(B*H*T*D) regardless of T.

Design (same helper-probe-with-fallback seam as ops/pallas_lstm.py):
  - scores are held KEYS ON SUBLANES, QUERIES ON LANES ([BK, n], see
    ``_scores``): a query's running max, sum, logsumexp and delta are rows
    [1, n], the softmax reductions run down the sublanes, and no kernel
    transposes a score tile. lse and delta travel as [BH, 1, T] float32.
  - forward: grid (B*H, T/RQ, T/RK) over RESIDENT blocks, k-blocks
    innermost ("arbitrary" semantics) with the (acc, m, l) carry in VMEM
    scratch; inside a grid step a static loop (``_visits``) walks the
    resident block one key tile a pass; saves the logsumexp for the
    backward.
  - backward (FlashAttention-2 style, custom_vjp): one kernel accumulates
    dq over k-blocks, a second accumulates (dk, dv) over q-blocks; softmax
    probabilities are recomputed from the saved logsumexp, never stored.
  - ONE rule (``_tile_rule``) says which [BQ,BK] score tiles causal
    attention visits: a tile with no position at or under the diagonal is
    never computed (and its operands never fetched), a tile wholly under
    it is computed without a mask, and only a tile the diagonal crosses
    pays the iota/compare/select. ``tile_schedule`` counts what that comes
    to for the tiles ``_blocks`` picks (T=1024: 10 of 16 tiles, 4 masked).
  - a sliding WINDOW (``flash_attention(..., window=W)``) is a case of the
    same rule: tiles wholly older than the window are skipped, tiles its
    trailing edge crosses are masked. It runs a forward kernel of its own
    (``flash_attention_window_fwd``) whose grid spans, per row block, only
    the resident key blocks the window reaches, and which reads grouped
    key-value heads in place. Forward only.
  - ``scale`` is folded into a [rows, D] operand (q; dq's accumulator),
    never multiplied over a score tile.
  - masking uses a large negative (-1e30) everywhere, matching the XLA
    fallback: a fully-masked query row degrades to uniform attention
    instead of NaN.
  - bf16 i/o supported; matmul ACCUMULATION and the online-softmax
    recurrence (s, m, l, lse) are f32; with bf16 inputs the dot operands
    (q/k/v/do and the p/ds tiles) run in bf16 for full MXU rate — the
    standard flash-kernel precision recipe.
"""
from __future__ import annotations

import functools
import math
import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from .kernels import envutil as kenv

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

f32 = jnp.float32
NEG = -1e30


def _kernel_eligible(D: int, dtype) -> bool:
    """The eligibility policy SHARED by every fused-attention probe
    (single-device and ring): not env-disabled, dtype,
    head-dim, and backend rules. Per-probe sequence-length rules layer on
    top."""
    if not kenv.fused_enabled("attention", ("DL4J_TPU_FUSED_ATTENTION",)):
        return False
    dt = jnp.dtype(dtype)
    if dt not in (jnp.float32, jnp.dtype(jnp.bfloat16)):
        return False
    if D % 128 != 0 and D not in (64, 96, 192):
        # D is the lane dimension: multiples of the 128-lane tile are
        # native; 64/96 (GPT-2-class head dims) and 192 (latent
        # attention's score size: 128 + a rotary part of 64) ride Mosaic's
        # minor-dim padding — the MXU pads the QK^T contraction to whole
        # tiles either way, so the only cost is padded q/k/v/o tiles in VMEM
        return False
    return kenv.backend_admits("attention", jax.default_backend(),
                               ("DL4J_TPU_FUSED_ATTN_INTERPRET",))


def fused_attention_applicable(B: int, H: int, T: int, D: int, dtype,
                               Dv: Optional[int] = None) -> bool:
    """Probe: can the fused kernels handle this call? (helper seam —
    callers fall back to the XLA path when False). ``Dv`` is the values'
    head size where it differs from the scores' ``D`` (forward only)."""
    # tiny T isn't worth the pallas_call overhead vs one fused XLA softmax
    return _kernel_eligible(D, dtype) and T % 128 == 0 and T >= 256 \
        and (Dv is None or Dv == D or _kernel_eligible(Dv, dtype))


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _blocks(T: int, causal: bool = False) -> tuple:
    """(BQ, BK): the score TILE, the [BQ,BK] block of scores one pass of
    the online-softmax recurrence takes. Resolution order: explicit env
    override (DL4J_TPU_ATTN_BQ / DL4J_TPU_ATTN_BK, the sweep's handles) →
    a cached autotune decision for this (T, causal, backend) from
    ops/kernels/autotune.py → the defaults below, chosen from what the
    call shows: T and ``causal``."""
    def pick(env, pref):
        v = os.environ.get(env)
        if v:
            b = int(v)
            if T % b:
                raise ValueError(f"{env}={b} does not divide T={T}")
            return b
        for b in pref:
            if T % b == 0:
                return b
        raise ValueError(f"T={T} not a multiple of 128")
    if causal:
        # v5e sweep at the shape both benchmark cells run (B8 H16 T1024 D64
        # bfloat16, tools/autotune_attention.py; table in PERF.md §5):
        # forward + backward 1,322 us at 256/256 (10 of 16 tiles), 1,334
        # at 128/128 (36 of 64: fewer elements, twice the passes), 1,396
        # at 512/512 (3 of 4); the old 512/1024 visited the whole square
        pref_q = pref_k = (256, 128)
    else:
        # every tile is needed: big k-tiles amortize the carry updates and
        # feed the MXU longer contractions (v5e, T=1024 at the same shape:
        # 512/1024 1,859 us forward + backward, 512/512 2,122)
        pref_q = (512, 256, 128)
        pref_k = (1024, 512, 256, 128)
    if os.environ.get("DL4J_TPU_ATTN_BQ") is None and \
            os.environ.get("DL4J_TPU_ATTN_BK") is None:
        from .kernels import autotune   # lazy: avoids an import cycle
        cached = autotune.cached_decision(
            "attention", f"T{T}causal" if causal else f"T{T}")
        if cached is not None:
            bq, bk = int(cached[0]), int(cached[1])
            if T % bq == 0 and T % bk == 0:
                return bq, bk
    return pick("DL4J_TPU_ATTN_BQ", pref_q), pick("DL4J_TPU_ATTN_BK", pref_k)


# a causal resident block is at most this square: one head's q, k, v at the
# benchmark's context stay in VMEM for a whole grid step (under 1 MiB in
# bfloat16). On the v5e a grid of 256 blocks took 3,167 us where this takes
# 1,322 (a grid step per tile, and narrow passes); 2048 at T=2048 was 14%
# faster again than 1024 but leaves float32 inputs no room in VMEM
_RESIDENT_MAX = 1024


def _resident(T: int, BQ: int, BK: int, causal: bool) -> tuple:
    """(RQ, RK): the rows of q and of k/v one grid step holds in VMEM; the
    kernels walk its tiles in a static loop (``_visits``). Non-causal:
    the tile itself (one tile a step, as ever). Causal: a SQUARE block of
    whole tiles, the whole sequence up to ``_RESIDENT_MAX``, so that a
    tile the schedule skips costs no grid step, and the blocks on the
    diagonal have i == j, which makes every tile's place static."""
    if not causal:
        return BQ, BK
    step = BQ * BK // math.gcd(BQ, BK)
    if T <= _RESIDENT_MAX:
        return T, T
    for r in (1024, 512, 256, 128):
        if r <= _RESIDENT_MAX and T % r == 0 and r % step == 0:
            return r, r
    return step, step


# ------------------------------------------------------- the tile schedule
SKIP, FULL, DIAG = "skip", "full", "diag"


def _tile_rule(row0, rows, col0, cols, window=None, listed=None):
    """THE rule of which score tiles causal attention visits, for the tile
    of query rows [row0, row0 + rows) and key columns [col0, col0 + cols):
    ``(skip, full)``. skip: every column lies after every row, the tile
    holds no position with col <= row. full: every column is at or before
    every row, no position is masked. Neither: the diagonal crosses it.
    Python ints give bools (the static tile loops, ``tile_schedule``);
    program ids give traced predicates (the grid).

    A sliding ``window`` (row t sees the columns s with t - window < s <=
    t, itself included) adds its trailing edge: skip also where every
    column lies at or before ``row0 - window`` (not even the tile's first
    row sees its last column), full only where the tile's last row still
    sees its first column; a tile either edge crosses, or both, is masked.
    A block-sparse SELECTION (every row brings its own list of key
    blocks: ``ops/sparse_select.py``) is the case position alone does not
    decide: ``listed`` = (some row of the tile lists some block among its
    columns, every row lists all of them), bools or traced predicates. A
    tile nobody lists is skipped; one not everybody lists whole is masked
    (by the rows' lists, under the name DIAG like every masked tile).
    A segment mask would change this function and ``_scores``' mask,
    nothing else."""
    skip, full = col0 > row0 + rows - 1, col0 + cols - 1 <= row0
    if window is not None:
        skip = skip | (col0 + cols - 1 <= row0 - window)
        full = full & (col0 > row0 + rows - 1 - window)
    if listed is not None:
        some, every = listed
        none = (not some) if isinstance(some, bool) else jnp.logical_not(some)
        skip, full = skip | none, full & every
    return skip, full


def tile_kind(row0: int, rows: int, col0: int, cols: int,
              causal: bool = True, window: Optional[int] = None,
              listed=None) -> str:
    """SKIP, FULL or DIAG for one tile at static offsets (DIAG: masked, by
    the diagonal, by a window's trailing edge, by the rows' block lists,
    or by several)."""
    if not causal:
        return FULL
    skip, full = _tile_rule(row0, rows, col0, cols, window, listed)
    return SKIP if skip else FULL if full else DIAG


def tile_schedule(T: int, causal: bool, window: Optional[int] = None,
                  chosen=None) -> tuple:
    """(visited, masked, total) score tiles a head's [T,T] square costs
    with the tiles ``_blocks`` picks: how many the kernels compute, how
    many of those pay a mask (the causal one, a window's, or both), how
    many the square has. A count from the same rule the kernels run, so it
    can be tested on a CPU. ``chosen`` [T / block, T] (numpy bools: key
    blocks down, query positions across) is one head's block-sparse
    selection: a tile counts where some query of its rows lists some block
    of its columns."""
    BQ, BK = _blocks(T, causal)

    def listed(r, c):
        if chosen is None:
            return None
        block = T // chosen.shape[0]
        tile = np.asarray(chosen)[c // block:-(-(c + BK) // block), r:r + BQ]
        return bool(tile.any()), bool(tile.all())
    kinds = [tile_kind(r, BQ, c, BK, causal, window, listed(r, c))
             for r in range(0, T, BQ) for c in range(0, T, BK)]
    return (sum(k != SKIP for k in kinds), sum(k == DIAG for k in kinds),
            len(kinds))


def _last_col_block(i, RQ, RK):
    """Last k/v block the row block i needs (the index maps clamp to it: a
    skipped grid step asks for the block already resident, no copy)."""
    return ((i + 1) * RQ - 1) // RK


def _first_row_block(j, RQ, RK):
    """First q block the column block j needs (the dk/dv pass's clamp)."""
    return (j * RK) // RQ


def _for_block(causal, i, j, RQ, RK, emit):
    """Emit the work of the resident block (row block i, column block j)
    of the grid: ``emit(diagonal)`` traces the static tile loop, once for
    a block wholly under the diagonal (no mask anywhere) and once for a
    block on it; a block the rule skips runs neither."""
    if not causal:
        return emit(False)
    assert RQ == RK, "causal resident blocks are square"
    skip, full = _tile_rule(i * RQ, RQ, j * RK, RK)
    pl.when(full)(lambda: emit(False))
    pl.when(jnp.logical_not(skip | full))(lambda: emit(True))


def _visits(diagonal, RQ, RK, BQ, BK):
    """The walk all four kernels make over a resident block, one pass a
    key tile: yields ``(c0, r_lo, masked)`` for the BK keys at c0, visited
    by the queries [r_lo, RQ) (causal attention looks back, so the tiles
    the rule does not skip are the END of a tile column), the first
    ``masked`` of which sit in tiles the diagonal crosses. Offsets are
    relative to the block: on a diagonal block i == j, so relative and
    absolute offsets differ by the same amount. One pass takes a whole
    column of tiles at once: the queries lie on the lanes (``_scores``),
    and wide passes keep every MXU fed."""
    for c0 in range(0, RK, BK):
        kinds = [tile_kind(r0, BQ, c0, BK, diagonal)
                 for r0 in range(0, RQ, BQ)]
        visited = [kind for kind in kinds if kind != SKIP]
        n_diag = visited.count(DIAG)
        # skipped tiles first, then the diagonal's, then the full ones
        assert kinds == ([SKIP] * (len(kinds) - len(visited))
                         + [DIAG] * n_diag
                         + [FULL] * (len(visited) - n_diag)), kinds
        if visited:
            yield c0, RQ - len(visited) * BQ, n_diag * BQ


def _scaled(x, scale):
    """x * scale in float32, back in x's dtype: the softmax scale applied
    over a [rows, D] operand, once, instead of over every score tile."""
    return (x.astype(f32) * scale).astype(x.dtype)


def _scores(k, q, r_lo, c_lo, masked, key_mask):
    """Scores of the keys k [nk, D] under the queries q [nq, D] (already
    scaled), float32, KEYS ON SUBLANES and queries on lanes: [nk, nq]. A
    query's running max, sum, logsumexp and delta are then rows [1, nq]
    that broadcast down the sublanes for nothing, and the softmax
    reductions run down the sublanes, elementwise between vector
    registers, where the other way round every row of every tile pays a
    cross-lane reduction. Only the first ``masked`` queries, those of the
    tiles the diagonal crosses, build the iotas of the causal mask (key
    c_lo + a is kept under query r_lo + b when a - b <= r_lo - c_lo);
    ``key_mask`` [nk, 128], a padded batch's mask replicated over the
    lanes, applies on every tile it touches."""
    # dots take the refs' NATIVE dtype with f32 accumulation: bf16 inputs
    # run the MXU at full rate (upcasting first would halve it); the
    # softmax recurrence stays f32 throughout
    s = jax.lax.dot_general(k, q, (((1,), (1,)), ((), ())),
                            preferred_element_type=f32)
    if masked:
        head = s[:, :masked]
        a_minus_b = (jax.lax.broadcasted_iota(jnp.int32, head.shape, 0)
                     - jax.lax.broadcasted_iota(jnp.int32, head.shape, 1))
        head = jnp.where(a_minus_b <= r_lo - c_lo, head, NEG)
        s = head if masked == s.shape[1] else jnp.concatenate(
            [head, s[:, masked:]], axis=1)
    if key_mask is not None:
        s = jnp.where(jnp.tile(key_mask, (1, s.shape[1] // 128)) > 0, s, NEG)
    return s


# ------------------------------------------------------------------ forward
# The kernels' names in the compiled program and in a device trace: the
# HLO instruction is named after the innermost scope around the call, and
# ``pallas_call(name=)`` opens one. A transformation wraps the FIRST scope
# opened under it (``jvp(name)``), so every call also sits in the outer
# scope ``SCOPE``, which takes that wrapping and leaves the name bare.
SCOPE = "flash_attention"
FWD_NAME = "flash_attention_fwd"


def _fold_scores(s, vT, at, acc, m, l):
    """One pass of the online-softmax recurrence: the scores s [nk, n] of a
    key tile under the queries ``at`` and the tile's values vT [D, nk]
    folded into the carry (acc [D, RQ], m and l [1, RQ])."""
    m_prev = m[:, at]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l[:, at] = l[:, at] * corr + jnp.sum(p, axis=0, keepdims=True)
    acc[:, at] = acc[:, at] * corr + jax.lax.dot_general(
        vT, p.astype(vT.dtype), (((1,), (0,)), ((), ())),
        preferred_element_type=f32)
    m[:, at] = m_new


def _softmax_block(diagonal, scale, BQ, BK, q_ref, k_ref, v_ref, mask_ref,
                   acc, m, l):
    """Fold one resident block into the online-softmax carry in VMEM
    scratch (acc [D, RQ] transposed like the scores, m and l [1, RQ]):
    one pass of the recurrence a key tile, over all the queries that
    visit it."""
    RQ, RK = q_ref.shape[1], k_ref.shape[1]
    q = _scaled(q_ref[0], scale)
    vT = v_ref[0].T                       # [D, RK]: p^T contracts its keys
    for c0, r_lo, masked in _visits(diagonal, RQ, RK, BQ, BK):
        at = slice(r_lo, RQ)
        s = _scores(k_ref[0, c0:c0 + BK, :], q[at], r_lo, c0, masked,
                    None if mask_ref is None else mask_ref[0, c0:c0 + BK, :])
        _fold_scores(s, vT[:, c0:c0 + BK], at, acc, m, l)


def _fwd_body(causal, masked, scale, BQ, BK, *refs):
    if masked:
        q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref, acc, m, l = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m, l = refs
        mask_ref = None
    i = pl.program_id(1)
    j = pl.program_id(2)
    nj = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m[:] = jnp.full_like(m, NEG)
        l[:] = jnp.zeros_like(l)

    _for_block(causal, i, j, q_ref.shape[1], k_ref.shape[1],
               lambda diagonal: _softmax_block(
                   diagonal, scale, BQ, BK, q_ref, k_ref, v_ref, mask_ref,
                   acc, m, l))

    @pl.when(j == nj - 1)
    def _finalize():
        o_ref[0] = (acc[:] / l[:]).T.astype(o_ref.dtype)
        lse_ref[0] = m[:] + jnp.log(l[:])


def _row_major_specs(causal, RQ, RK, D, mask_heads):
    """BlockSpecs of a (b, i, j) grid, k-blocks innermost: (q-side rows,
    k-side rows, per-query scalars [., 1, T], key mask [., T, 128] or
    None). Under ``causal`` the k side stops at the last block row block
    i needs. ``D`` is the rows' width: the forward pass asks once for the
    scores' size (q, k) and once for the values' (v, o)."""
    def col(i, j):
        return jnp.minimum(j, _last_col_block(i, RQ, RK)) if causal else j
    qspec = pl.BlockSpec((1, RQ, D), lambda b, i, j: (b, i, 0))
    kspec = pl.BlockSpec((1, RK, D), lambda b, i, j: (b, col(i, j), 0))
    lspec = pl.BlockSpec((1, 1, RQ), lambda b, i, j: (b, 0, i))
    mspec = None if mask_heads is None else pl.BlockSpec(
        (1, RK, 128), lambda b, i, j: (b // mask_heads, col(i, j), 0))
    return qspec, kspec, lspec, mspec


def _lane_replicated(mask2):
    """[B, T] key mask -> [B, T, 128] float32: a key's flag on every lane
    of its sublane, the layout ``_scores`` tiles across its queries."""
    return jnp.broadcast_to(mask2.astype(f32)[:, :, None],
                            mask2.shape + (128,))


def _tiles(T, causal):
    """(BQ, BK, RQ, RK) of a call: resolved where the call is traced, and
    handed to the jitted kernels below as a static argument."""
    BQ, BK = _blocks(T, causal)
    return (BQ, BK) + _resident(T, BQ, BK, causal)


@functools.partial(jax.jit, static_argnames=("causal", "scale", "tiles",
                                             "interpret"))
def _fwd_call(q3, k3, v3, mask2, *, causal, scale, tiles, interpret):
    """Jitted, so that a program of 24 layers traces the kernel body and
    lowers it to Mosaic once, not once a layer: the static tile loop makes
    the body several times longer than a one-tile kernel's, and every
    process pays its tracing before it can even look in the compile cache
    (PR 27's lesson with the paged kernel, PERF.md §6)."""
    BH, T, D = q3.shape
    Dv = v3.shape[2]                  # the values' size; D is the scores'
    BQ, BK, RQ, RK = tiles
    masked = mask2 is not None
    mask_heads = BH // mask2.shape[0] if masked else None
    qspec, kspec, lspec, mspec = _row_major_specs(causal, RQ, RK, D,
                                                  mask_heads)
    ospec, vspec = (qspec, kspec) if Dv == D else _row_major_specs(
        causal, RQ, RK, Dv, mask_heads)[:2]
    in_specs = [qspec, kspec, vspec]
    args = [q3, k3, v3]
    if masked:
        in_specs.append(mspec)
        args.append(_lane_replicated(mask2))
    with jax.named_scope(SCOPE):
        o, lse = pl.pallas_call(
            functools.partial(_fwd_body, causal, masked, scale, BQ, BK),
            name=FWD_NAME,
            grid=(BH, T // RQ, T // RK),
            in_specs=in_specs,
            out_specs=[ospec, lspec],
            out_shape=[jax.ShapeDtypeStruct((BH, T, Dv), q3.dtype),
                       jax.ShapeDtypeStruct((BH, 1, T), f32)],
            scratch_shapes=[pltpu.VMEM((Dv, RQ), f32),
                            pltpu.VMEM((1, RQ), f32),
                            pltpu.VMEM((1, RQ), f32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=interpret,
        )(*args)
    return o, lse


def _fwd(q3, k3, v3, mask2, causal, scale):
    """q3/k3: [BH, T, D], v3: [BH, T, Dv]; mask2: [B, T] or None. Returns
    (o [BH, T, Dv], lse), lse [BH, 1, T] float32."""
    return _fwd_call(q3, k3, v3, mask2, causal=causal, scale=scale,
                     tiles=_tiles(q3.shape[1], causal),
                     interpret=_interpret())


# ------------------------------------------------- windowed forward
WINDOW_FWD_NAME = "flash_attention_window_fwd"


def _window_visits(behind, RQ, RK, BQ, BK, window):
    """The walk of a resident block whose rows begin ``behind`` positions
    after its columns (0 on the diagonal, a multiple of the block behind
    it), one pass a key tile: yields ``(c0, r_lo, r_hi, masked)`` for the
    BK keys at c0, visited by the queries [r_lo, r_hi) (the window cuts
    the visitors off at BOTH ends: queries before r_lo lie before the keys,
    queries from r_hi on no longer reach back to them); ``masked``: some
    tile of the strip is crossed by the diagonal or by the window's edge."""
    for c0 in range(0, RK, BK):
        kinds = [tile_kind(behind + r0, BQ, c0, BK, True, window)
                 for r0 in range(0, RQ, BQ)]
        seen = [n for n, kind in enumerate(kinds) if kind != SKIP]
        if seen:
            lo, hi = seen[0], seen[-1] + 1
            assert SKIP not in kinds[lo:hi], kinds
            yield c0, lo * BQ, hi * BQ, DIAG in kinds[lo:hi]


def _softmax_block_window(behind, window, scale, BQ, BK, q_ref, k_ref, v_ref,
                          acc, m, l):
    """``_softmax_block`` for a sliding window: the same recurrence over
    the strips ``_window_visits`` names. A query whose strip is all masked
    (the last row of a tile the edge crosses) folds in uniform weights
    under a running max of NEG; the first real key it meets rescales them
    to exactly 0, and every query meets its own position."""
    RQ, RK = q_ref.shape[1], k_ref.shape[1]
    q = _scaled(q_ref[0], scale)
    vT = v_ref[0].T                       # [Dv, RK]
    for c0, r_lo, r_hi, masked in _window_visits(behind, RQ, RK, BQ, BK,
                                                 window):
        at = slice(r_lo, r_hi)
        s = jax.lax.dot_general(k_ref[0, c0:c0 + BK, :], q[at],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=f32)
        if masked:
            # key c0 + a under query behind + r_lo + b: b - a + their
            # distance is how far back the key lies, kept in [0, window)
            back = ((behind + r_lo - c0)
                    + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
                    - jax.lax.broadcasted_iota(jnp.int32, s.shape, 0))
            s = jnp.where((back >= 0) & (back < window), s, NEG)
        _fold_scores(s, vT[:, c0:c0 + BK], at, acc, m, l)


def _fwd_window_body(window, blocks_back, scale, BQ, BK, q_ref, k_ref, v_ref,
                     o_ref, acc, m, l):
    """Grid step (b, i, j): row block i against the resident key block
    ``i - blocks_back + j``. How far a block lies behind the diagonal is
    static per j, so every tile's place in the window is too."""
    i = pl.program_id(1)
    j = pl.program_id(2)
    RQ = q_ref.shape[1]

    @pl.when(j == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m[:] = jnp.full_like(m, NEG)
        l[:] = jnp.zeros_like(l)

    for jp in range(blocks_back + 1):
        behind = blocks_back - jp         # blocks between this and row block i

        @pl.when((j == jp) & (i >= behind))   # a block before the sequence
        def _block(behind=behind):            # began is not computed
            _softmax_block_window(behind * RQ, window, scale, BQ, BK, q_ref,
                                  k_ref, v_ref, acc, m, l)

    @pl.when(j == blocks_back)
    def _finalize():
        o_ref[0] = (acc[:] / l[:]).T.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "scale", "tiles",
                                             "group", "interpret"))
def _fwd_window_call(q3, k3, v3, *, window, scale, tiles, group, interpret):
    """Causal attention under a sliding window, forward only: q3 [BH, T,
    D], k3 / v3 [BH / group, T, D]: query head b reads key-value head ``b
    // group`` through the index map (no repeated copy). The grid's key
    axis spans only the resident blocks a row block's window reaches
    (``blocks_back`` behind the diagonal's and that one), so the blocks
    older than the window cost no grid step and no copy: at T = 16,384 and
    a window of 512 a head runs 32 steps, not the 136 of its causal half.
    Jitted for the reason ``_fwd_call`` is."""
    BH, T, D = q3.shape
    Dv = v3.shape[2]
    BQ, BK, RQ, RK = tiles
    assert RQ == RK, "causal resident blocks are square"
    blocks_back = min(-(-(window - 1) // RK), T // RK - 1)

    def col(i, j):                 # clamped: a skipped step copies nothing
        return jnp.maximum(i - blocks_back + j, 0)

    def kv(b):
        return b if group == 1 else b // group
    with jax.named_scope(SCOPE):
        return pl.pallas_call(
            functools.partial(_fwd_window_body, window, blocks_back, scale,
                              BQ, BK),
            name=WINDOW_FWD_NAME,
            grid=(BH, T // RQ, blocks_back + 1),
            in_specs=[
                pl.BlockSpec((1, RQ, D), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((1, RK, D), lambda b, i, j: (kv(b), col(i, j), 0)),
                pl.BlockSpec((1, RK, Dv),
                             lambda b, i, j: (kv(b), col(i, j), 0))],
            out_specs=pl.BlockSpec((1, RQ, Dv), lambda b, i, j: (b, i, 0)),
            out_shape=jax.ShapeDtypeStruct((BH, T, Dv), q3.dtype),
            scratch_shapes=[pltpu.VMEM((Dv, RQ), f32),
                            pltpu.VMEM((1, RQ), f32),
                            pltpu.VMEM((1, RQ), f32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=interpret,
        )(q3, k3, v3)


# ---------------------------------------- block-sparse (selected) forward
SPARSE_FWD_NAME = "flash_attention_sparse_fwd"


def _softmax_block_sparse(diagonal, scale, BQ, BK, block, strip0, some_ref,
                          q_ref, k_ref, v_ref, ch_ref, acc, m, l):
    """``_softmax_block`` under a block-sparse selection: ``ch_ref`` [1,
    RK / block, RQ] holds, for every key block of the resident columns
    (down) and every query of the resident rows (across, as the scores lie),
    whether the query's list names the block. A strip (one key tile under
    all the queries that reach it) nobody lists is not computed:
    ``some_ref[strip0 + c0 / BK]`` is the rule's ``listed`` for it, made
    with the lists. A query meets key 0 in its first strip (the first block
    is on every list) and its own block in its last, so no row of the carry
    is ever left at the mask's value."""
    RQ, RK = q_ref.shape[1], k_ref.shape[1]
    q = _scaled(q_ref[0], scale)
    vT = v_ref[0].T
    for c0, r_lo, masked in _visits(diagonal, RQ, RK, BQ, BK):
        @pl.when(some_ref[strip0 + c0 // BK] > 0)
        def _strip(c0=c0, r_lo=r_lo, masked=masked):
            at = slice(r_lo, RQ)
            s = _scores(k_ref[0, c0:c0 + BK, :], q[at], r_lo, c0, masked,
                        None)
            s = jnp.concatenate([
                jnp.where(ch_ref[0, c0 // block + b:c0 // block + b + 1, at]
                          > 0, s[b * block:(b + 1) * block], NEG)
                for b in range(BK // block)], axis=0)
            _fold_scores(s, vT[:, c0:c0 + BK], at, acc, m, l)


def _fwd_sparse_body(scale, BQ, BK, block, group, some_ref, q_ref, k_ref,
                     v_ref, ch_ref, o_ref, acc, m, l):
    b, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nj = pl.num_programs(2)
    RK = k_ref.shape[1]
    strips = RK // BK
    # strips of this head's key-value group, this row block, this column
    # block: [Hkv, T / RQ, T / BK] flattened
    strip0 = ((b // group) * pl.num_programs(1) + i) * (nj * strips) \
        + j * strips

    @pl.when(j == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m[:] = jnp.full_like(m, NEG)
        l[:] = jnp.zeros_like(l)

    _for_block(True, i, j, q_ref.shape[1], RK,
               lambda diagonal: _softmax_block_sparse(
                   diagonal, scale, BQ, BK, block, strip0, some_ref, q_ref,
                   k_ref, v_ref, ch_ref, acc, m, l))

    @pl.when(j == nj - 1)
    def _finalize():
        o_ref[0] = (acc[:] / l[:]).T.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "tiles", "group",
                                             "interpret"))
def _fwd_sparse_call(q3, k3, v3, chosen, *, scale, tiles, group, interpret):
    """Causal attention in which every query reads the key blocks its own
    list names, forward only: q3 [BH, T, D], k3 / v3 [BH / group, T, D]
    (read in place through the index map), chosen [BH / group, T / block,
    T] float32 (1: the query, across, lists the block, down). The walk is
    the causal one; a strip of score tiles is computed where some query
    lists some of its blocks (``_tile_rule``'s ``listed``, reduced from
    ``chosen`` here and prefetched as scalars) and masked by the lists."""
    BH, T, D = q3.shape
    BQ, BK, RQ, RK = tiles
    assert RQ == RK, "causal resident blocks are square"
    nb = chosen.shape[1]
    block = T // nb
    if BK % block or RK % block:
        raise ValueError(f"key tiles of {BK} are not whole blocks of {block}")
    # [Hkv, T / RQ, T / BK]: some query of the row block lists some block
    # of the key tile
    some = chosen.reshape(chosen.shape[0], T // BK, BK // block, T // RQ,
                          RQ).max(axis=(2, 4)).transpose(0, 2, 1)
    some = (some > 0).astype(jnp.int32).reshape(-1)

    def col(i, j):
        return jnp.minimum(j, _last_col_block(i, RQ, RK))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(BH, T // RQ, T // RK),
        in_specs=[
            pl.BlockSpec((1, RQ, D), lambda b, i, j, *_: (b, i, 0)),
            pl.BlockSpec((1, RK, D),
                         lambda b, i, j, *_: (b // group, col(i, j), 0)),
            pl.BlockSpec((1, RK, D),
                         lambda b, i, j, *_: (b // group, col(i, j), 0)),
            pl.BlockSpec((1, RK // block, RQ),
                         lambda b, i, j, *_: (b // group, col(i, j), i))],
        out_specs=pl.BlockSpec((1, RQ, D), lambda b, i, j, *_: (b, i, 0)),
        scratch_shapes=[pltpu.VMEM((D, RQ), f32), pltpu.VMEM((1, RQ), f32),
                        pltpu.VMEM((1, RQ), f32)])
    with jax.named_scope(SCOPE):
        return pl.pallas_call(
            functools.partial(_fwd_sparse_body, scale, BQ, BK, block, group),
            name=SPARSE_FWD_NAME, grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((BH, T, D), q3.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=interpret,
        )(some, q3, k3, v3, chosen)


def flash_attention_sparse(q, k, v, chosen, *, scale: Optional[float] = None):
    """Causal attention over the key blocks each query's list names
    (``flash_attention_sparse_fwd``): q [B,H,T,D], k / v [B,Hkv,T,D],
    ``chosen`` [B,Hkv,T / block,T] (``ops.sparse_select.chosen_mask``: one
    list a query position and key-value group, shared by the group's query
    heads) -> [B,H,T,D]. Forward only; one device."""
    B, H, T, D = q.shape
    Hkv = k.shape[1]
    scale = float(scale) if scale is not None else 1.0 / float(np.sqrt(D))
    if _device_split(B, H) is not None:
        raise ValueError("block-sparse attention runs on one device")
    o = _fwd_sparse_call(
        q.reshape(B * H, T, D), k.reshape(B * Hkv, T, D),
        v.reshape(B * Hkv, T, D),
        chosen.reshape(B * Hkv, chosen.shape[2], T).astype(f32), scale=scale,
        tiles=_tiles(T, True), group=H // Hkv, interpret=_interpret())
    return o.reshape(B, H, T, D)


# ------------------------------------------------------------------ dq pass
DQ_NAME = "flash_attention_bwd_dq"


def _dq_body(causal, masked, scale, BQ, BK, *refs):
    if masked:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, mask_ref,
         dq_ref, dq_acc) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dq_ref, dq_acc) = refs
        mask_ref = None
    i = pl.program_id(1)
    j = pl.program_id(2)
    nj = pl.num_programs(2)
    RQ, RK = q_ref.shape[1], k_ref.shape[1]

    @pl.when(j == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def block(diagonal):
        q = _scaled(q_ref[0], scale)
        kT = k_ref[0].T                   # [D, RK]: ds^T contracts its keys
        for c0, r_lo, masked in _visits(diagonal, RQ, RK, BQ, BK):
            at = slice(r_lo, RQ)
            keys = slice(c0, c0 + BK)
            s = _scores(k_ref[0, keys, :], q[at], r_lo, c0, masked,
                        None if mask_ref is None else mask_ref[0, keys, :])
            p = jnp.exp(s - lse_ref[0, :, at])
            dp = jax.lax.dot_general(v_ref[0, keys, :], do_ref[0, at, :],
                                     (((1,), (1,)), ((), ())),
                                     preferred_element_type=f32)
            ds = p * (dp - delta_ref[0, :, at])
            dq_acc[:, at] += jax.lax.dot_general(
                kT[:, keys], ds.astype(kT.dtype), (((1,), (0,)), ((), ())),
                preferred_element_type=f32)

    _for_block(causal, i, j, RQ, RK, block)

    @pl.when(j == nj - 1)
    def _finalize():
        # ds carries no scale: it multiplies the [D, RQ] sum once
        dq_ref[0] = (dq_acc[:] * scale).T.astype(dq_ref.dtype)


# ---------------------------------------------------------------- dkv pass
DKV_NAME = "flash_attention_bwd_dkv"


def _dkv_body(causal, masked, scale, BQ, BK, *refs):
    if masked:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, mask_ref,
         dk_ref, dv_ref, dk_acc, dv_acc) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_acc, dv_acc) = refs
        mask_ref = None
    jk = pl.program_id(1)          # k-block (outer)
    i = pl.program_id(2)           # q-block (inner, "arbitrary")
    ni = pl.num_programs(2)
    RQ, RK = q_ref.shape[1], k_ref.shape[1]

    @pl.when(i == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def block(diagonal):
        # scaled q serves the scores AND dk = ds (q * scale)
        qs = _scaled(q_ref[0], scale)
        for c0, r_lo, masked in _visits(diagonal, RQ, RK, BQ, BK):
            at = slice(r_lo, RQ)
            keys = slice(c0, c0 + BK)
            q = qs[at]
            do = do_ref[0, at, :]
            s = _scores(k_ref[0, keys, :], q, r_lo, c0, masked,
                        None if mask_ref is None else mask_ref[0, keys, :])
            p = jnp.exp(s - lse_ref[0, :, at])                   # [BK, n]
            dv_acc[keys, :] += jax.lax.dot_general(
                p.astype(do.dtype), do, (((1,), (0,)), ((), ())),
                preferred_element_type=f32)
            dp = jax.lax.dot_general(v_ref[0, keys, :], do,
                                     (((1,), (1,)), ((), ())),
                                     preferred_element_type=f32)
            ds = p * (dp - delta_ref[0, :, at])
            dk_acc[keys, :] += jax.lax.dot_general(
                ds.astype(q.dtype), q, (((1,), (0,)), ((), ())),
                preferred_element_type=f32)

    _for_block(causal, i, jk, RQ, RK, block)

    @pl.when(i == ni - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "scale", "tiles",
                                             "interpret"))
def _bwd_call(q3, k3, v3, mask2, o3, lse, do3, *, causal, scale, tiles,
              interpret):
    """Both backward kernels; jitted for the reason ``_fwd_call`` is."""
    BH, T, D = q3.shape
    BQ, BK, RQ, RK = tiles
    masked = mask2 is not None
    H = BH // mask2.shape[0] if masked else None
    # delta = rowsum(dO * O), a row of per-query scalars like lse
    delta = jnp.sum(do3.astype(f32) * o3.astype(f32), axis=-1)[:, None, :]
    args = [q3, k3, v3, do3, lse, delta]
    if masked:
        args.append(_lane_replicated(mask2))

    # dq grid (b, i, j): q-indexed rows use i, k-blocks innermost
    qspec, kspec, lspec, mspec = _row_major_specs(causal, RQ, RK, D, H)
    in_specs = [qspec, kspec, kspec, qspec, lspec, lspec]
    with jax.named_scope(SCOPE):
        dq = pl.pallas_call(
            functools.partial(_dq_body, causal, masked, scale, BQ, BK),
            name=DQ_NAME,
            grid=(BH, T // RQ, T // RK),
            in_specs=in_specs + ([mspec] if masked else []),
            out_specs=[qspec],
            out_shape=[jax.ShapeDtypeStruct((BH, T, D), q3.dtype)],
            scratch_shapes=[pltpu.VMEM((D, RQ), f32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=interpret,
        )(*args)[0]

    # dkv grid is (b, jk, i): q-indexed rows use the INNER index i, and
    # under ``causal`` start at the first q block the k block jk needs
    def row(jk, i):
        return jnp.maximum(i, _first_row_block(jk, RQ, RK)) if causal else i
    qspec = pl.BlockSpec((1, RQ, D), lambda b, jk, i: (b, row(jk, i), 0))
    lspec = pl.BlockSpec((1, 1, RQ), lambda b, jk, i: (b, 0, row(jk, i)))
    kvspec = pl.BlockSpec((1, RK, D), lambda b, jk, i: (b, jk, 0))
    in_specs = [qspec, kvspec, kvspec, qspec, lspec, lspec]
    if masked:
        in_specs.append(pl.BlockSpec(
            (1, RK, 128), lambda b, jk, i: (b // H, jk, 0)))
    with jax.named_scope(SCOPE):
        dk, dv = pl.pallas_call(
            functools.partial(_dkv_body, causal, masked, scale, BQ, BK),
            name=DKV_NAME,
            grid=(BH, T // RK, T // RQ),
            in_specs=in_specs,
            out_specs=[kvspec, kvspec],
            out_shape=[jax.ShapeDtypeStruct((BH, T, D), k3.dtype),
                       jax.ShapeDtypeStruct((BH, T, D), v3.dtype)],
            scratch_shapes=[pltpu.VMEM((RK, D), f32),
                            pltpu.VMEM((RK, D), f32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=interpret,
        )(*args)
    return dq, dk, dv


def _bwd(q3, k3, v3, mask2, causal, scale, o3, lse, do3):
    """(dq, dk, dv); lse [BH, 1, T] as ``_fwd`` returns it."""
    return _bwd_call(q3, k3, v3, mask2, o3, lse, do3, causal=causal,
                     scale=scale, tiles=_tiles(q3.shape[1], causal),
                     interpret=_interpret())


# ------------------------------------------------- ring-hop carry kernel
FWD_CARRY_NAME = "flash_attention_fwd_carry"


def _fwd_carry_body(causal, scale, BQ, BK, *refs):
    """One ring hop's local block, CARRY-EMITTING: the online-softmax
    state (acc, m, l) enters as kernel inputs and leaves raw (no
    normalize) so the ring can keep folding hops in. The recurrence is
    _fwd_body's own (``_softmax_block``); m/l ride between hops as rows
    [., 1, Tq], acc as [., Tq, D]."""
    (q_ref, k_ref, v_ref, acc_in, m_in, l_in,
     acc_out, m_out, l_out, accs, ms, ls) = refs
    i = pl.program_id(1)
    j = pl.program_id(2)
    nj = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        accs[:] = acc_in[0].T
        ms[:] = m_in[0]
        ls[:] = l_in[0]

    _for_block(causal, i, j, q_ref.shape[1], k_ref.shape[1],
               lambda diagonal: _softmax_block(
                   diagonal, scale, BQ, BK, q_ref, k_ref, v_ref, None,
                   accs, ms, ls))

    @pl.when(j == nj - 1)
    def _finalize():
        acc_out[0] = accs[:].T
        m_out[0] = ms[:]
        l_out[0] = ls[:]


def flash_block_update(acc, m, l, q3, k3, v3, *, causal: bool,
                       scale: float):
    """Fused one-hop update for ring attention: fold the local [BH,Tq,D] x
    [BH,Tk,D] block into the running online-softmax carry WITHOUT
    materializing the [Tq,Tk] scores in HBM (the XLA ring body's
    _block_update does — parallel/ring_attention.py). acc [BH,Tq,D] f32;
    m/l rows [BH,1,Tq] f32. Returns the updated carry, raw (caller
    normalizes after the last hop). A causal hop is the ring's diagonal
    one: Tq == Tk."""
    BH, Tq, D = q3.shape
    Tk = k3.shape[1]
    if causal:
        assert Tq == Tk, (Tq, Tk)
        BQ, BK, RQ, RK = _tiles(Tq, True)
    else:
        BQ, BK = RQ, RK = _blocks(Tq)[0], _blocks(Tk)[1]
    qspec, kspec, lspec, _ = _row_major_specs(causal, RQ, RK, D, None)
    with jax.named_scope(SCOPE):
        return pl.pallas_call(
            functools.partial(_fwd_carry_body, causal, scale, BQ, BK),
            name=FWD_CARRY_NAME,
            grid=(BH, Tq // RQ, Tk // RK),
            in_specs=[qspec, kspec, kspec, qspec, lspec, lspec],
            out_specs=[qspec, lspec, lspec],
            out_shape=[jax.ShapeDtypeStruct((BH, Tq, D), f32),
                       jax.ShapeDtypeStruct((BH, 1, Tq), f32),
                       jax.ShapeDtypeStruct((BH, 1, Tq), f32)],
            scratch_shapes=[pltpu.VMEM((D, RQ), f32),
                            pltpu.VMEM((1, RQ), f32),
                            pltpu.VMEM((1, RQ), f32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=_interpret(),
        )(q3, k3, v3, acc, m, l)


def flash_block_bwd(q3, k3, v3, o3, lse, do3, *, causal: bool,
                    scale: float):
    """One ring hop's backward contribution (FlashAttention-2 math with
    the GLOBAL logsumexp, so per-hop contributions sum exactly): returns
    (dq_contrib, dk, dv) for this (q, k-block) pair via the existing
    fused _dq/_dkv kernels."""
    return _bwd(q3, k3, v3, None, causal, scale, o3, lse, do3)


def fused_ring_applicable(t_local: int, D: int, dtype) -> bool:
    """Probe for the fused ring-hop kernels (helper seam): the per-device
    sequence block must tile the TPU lane dim; head-dim/dtype/backend
    rules are the shared _kernel_eligible policy. t_local = T / ring_size."""
    return _kernel_eligible(D, dtype) and t_local % 128 == 0 and t_local > 0


# --------------------------------------------------------------- custom vjp
@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _flash(q3, k3, v3, mask2, causal, scale):
    o, _ = _fwd(q3, k3, v3, mask2, causal, scale)
    return o


def _flash_fwd(q3, k3, v3, mask2, causal, scale):
    o, lse = _fwd(q3, k3, v3, mask2, causal, scale)
    return o, (q3, k3, v3, mask2, o, lse)


def _flash_bwd(causal, scale, res, do3):
    q3, k3, v3, mask2, o3, lse = res
    dq, dk, dv = _bwd(q3, k3, v3, mask2, causal, scale, o3, lse, do3)
    # mask2 is a traced array operand when present: an explicit zero
    # cotangent is version-stable, None-for-array is not
    dmask = None if mask2 is None else jnp.zeros_like(mask2)
    return dq, dk, dv, dmask


_flash.defvjp(_flash_fwd, _flash_bwd)


def _device_split(B: int, H: int):
    """How a program traced for SEVERAL devices must run the kernels.

    A Mosaic custom call cannot be partitioned automatically: under a jit
    over more than one device (ParallelWrapper's GSPMD step, a
    (data, model) mesh, the head-sharded decode prefill) it refuses to
    lower ("Mosaic kernels cannot be automatically partitioned" — first
    seen on four real chips; the CPU interpreter path is plain ops and
    never showed it). Attention is independent per batch row and per
    head, so each device runs the kernels on its own block under a
    ``shard_map``: batch over the mesh's ``data`` axis, heads over its
    ``model`` axis (the repo's axis names — parallel/mesh.py,
    parallel/tensor_parallel.py), T and D whole.

    The mesh comes from the tracing context
    (``jax.sharding.use_abstract_mesh``, which ParallelWrapper.fit and
    GenerationProgramSet.warm enter). Returns ``(spec, axis_names)`` for
    the shard_map, or None when there is nothing to split: no mesh in
    context (a one-device program), or every axis already manual (inside
    ParallelWrapper's own shard_map)."""
    from ..parallel.tensor_parallel import MODEL_AXIS
    mesh = jax.sharding.get_abstract_mesh()
    auto = frozenset(mesh.axis_names) - frozenset(mesh.manual_axes)
    if mesh.empty or not auto:
        return None

    def axis(name, n):         # an axis that is still ours to split evenly
        return name if name in auto and n % mesh.shape[name] == 0 else None
    # every remaining axis turns manual (Mosaic accepts nothing less); one
    # the spec does not name just repeats the work on its devices
    return P(axis("data", B), axis(MODEL_AXIS, H), None, None), auto


def flash_attention(q, k, v, *, causal: bool = False,
                    scale: Optional[float] = None, key_mask=None,
                    window: Optional[int] = None):
    """Fused softmax attention, [B,H,T,D] in/out — drop-in for
    parallel/ring_attention.attention when fused_attention_applicable.
    ``key_mask`` [B,T] excludes padded timesteps as keys. ``v`` (and the
    result) may have a head size ``Dv`` of its own (latent attention: 192
    for the scores, 128 for the values): that call is forward only, the
    backward kernels take one size.

    ``window``: causal attention in which position t sees the keys s with
    ``t - window < s <= t`` (``flash_attention_window_fwd``). Forward only,
    no ``key_mask``; k and v may carry fewer heads than q ([B,Hkv,T,D],
    query head i reading head ``i // (H // Hkv)``), which the kernel reads
    in place."""
    B, H, T, D = q.shape
    Dv = v.shape[-1]
    scale = float(scale) if scale is not None else 1.0 / float(np.sqrt(D))
    if window is not None:
        if not causal or key_mask is not None:
            raise ValueError("a sliding window is causal and takes no "
                             "key_mask")
        return _flash_window(q, k, v, int(window), scale)

    def kernels(q, k, v, *mask):          # one device's [b,h,T,D] block
        b, h = q.shape[:2]
        q3, k3, v3 = (a.reshape(b * h, T, a.shape[-1]) for a in (q, k, v))
        m = mask[0] if mask else None
        o = _flash(q3, k3, v3, m, causal, scale) if Dv == D else \
            _fwd(q3, k3, v3, m, causal, scale)[0]
        return o.reshape(b, h, T, Dv)

    args = (q, k, v) + (() if key_mask is None else (jnp.asarray(key_mask),))
    split = _device_split(B, H)
    if split is None:
        return kernels(*args)
    spec, axis_names = split
    return jax.shard_map(
        kernels, in_specs=(spec,) * 3 + (P(spec[0], None),) * (len(args) - 3),
        out_specs=spec, axis_names=axis_names, check_vma=False)(*args)


def _flash_window(q, k, v, window: int, scale: float):
    """``flash_attention``'s windowed case."""
    B, H, T, _ = q.shape
    if window < 1 or H % k.shape[1]:
        raise ValueError(f"window={window} must be positive and the "
                         f"{k.shape[1]} key-value heads divide the {H} "
                         "query heads")

    def kernels(q, k, v):                 # one device's heads
        b, h, hkv = q.shape[0], q.shape[1], k.shape[1]
        o = _fwd_window_call(
            q.reshape(b * h, T, q.shape[-1]),
            k.reshape(b * hkv, T, k.shape[-1]),
            v.reshape(b * hkv, T, v.shape[-1]), window=window, scale=scale,
            tiles=_tiles(T, True), group=h // hkv, interpret=_interpret())
        return o.reshape(b, h, T, v.shape[-1])

    split = _device_split(B, H)
    if split is None:
        return kernels(q, k, v)
    # over a mesh every device takes whole query heads with their own copy
    # of the key-value heads they read
    group = H // k.shape[1]
    k, v = (jnp.repeat(a, group, axis=1) for a in (k, v))
    spec, axis_names = split
    return jax.shard_map(kernels, in_specs=(spec,) * 3, out_specs=spec,
                         axis_names=axis_names, check_vma=False)(q, k, v)
