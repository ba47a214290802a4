"""Speculative decoding: draft-propose k tokens, verify in ONE target pass.

Plain continuous batching pays one full target-model program dispatch per
emitted token per slot. Speculative decoding buys several: a cheap DRAFT
model proposes ``k`` greedy continuations per slot, then the target runs a
single batched VERIFY window over ``[cur, p_1..p_k]`` (one program, W=k+1
positions via ``models.decode.decode_window`` + ``PagedWindowStore``) and
the scheduler accepts the longest prefix where the draft agreed with the
target's own greedy choice, plus the target's correction token at the
first disagreement. Because every accepted token IS the token plain greedy
decode would have produced (row ``i`` of the verify window sees exactly
the context one-token decode at ``pos+i`` sees), the output stream is
token-for-token identical to plain greedy decode — speculation changes the
SCHEDULE, never the tokens.

Two draft adapters, both AOT-warmed in ``GenerationProgramSet`` beside the
prefill/decode programs and cohort-pinned across hot-swap:

- ``dense``  — a (truncated) transformer draft with a fixed dense per-slot
  KV cache ``[layers, slots+1, capacity, H, Dh]`` (no paging: the draft is
  small, and a dense cache makes rewind FREE — rejected proposals' K/V are
  overwritten before any later mask can see them, so rollback is just not
  advancing ``pos``).
- ``state``  — an LSTM draft whose cache is the recurrent state. Recurrent
  state can't un-consume a token, so the propose scan stacks the state
  after EVERY fed token and a tiny rewind program gathers, per slot, the
  state matching what the verify actually accepted.

The draft proposes nothing when disabled or for sampling (temperature > 0)
requests — those ride the plain decode path unchanged.
"""
from __future__ import annotations

from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...parallel.ring_attention import attention
from .kvcache import repeat_heads


# ------------------------------------------------------------- dense store
class DenseDraftStore:
    """``models.decode.KVStore`` over the draft's dense per-slot cache for
    one propose step: row ``s`` writes position ``pos[s]``, inactive slots
    (and positions past capacity) write the trash row."""

    def __init__(self, k_cache, v_cache, pos, active):
        # k_cache/v_cache: [Ld, S+1, cap, H, Dh]; row S is trash
        self.k_cache = k_cache
        self.v_cache = v_cache
        S = pos.shape[0]
        cap = k_cache.shape[2]
        ok = active & (pos < cap)
        self._row = jnp.where(ok, jnp.arange(S), S)
        self._off = jnp.where(ok, pos, 0)
        self._mask = (jnp.arange(cap)[None, :] <= pos[:, None])

    def attend(self, i: int, q, k_tok, v_tok):
        self.k_cache = self.k_cache.at[i, self._row, self._off].set(k_tok)
        self.v_cache = self.v_cache.at[i, self._row, self._off].set(v_tok)
        S = k_tok.shape[0]
        K = self.k_cache[i, :S].transpose(0, 2, 1, 3)   # [S,H,cap,Dh]
        V = self.v_cache[i, :S].transpose(0, 2, 1, 3)
        group = q.shape[1] // K.shape[1]    # grouped-query drafts
        return attention(q, repeat_heads(K, group), repeat_heads(V, group),
                         causal=False, key_mask=self._mask)

    @property
    def caches(self):
        return self.k_cache, self.v_cache


def make_dense_draft_cache(draft_spec, slots: int, capacity: int):
    """Zero-filled (k_cache, v_cache) for the dense draft adapter."""
    shape = (draft_spec.n_blocks, slots + 1, capacity,
             draft_spec.kv_heads, draft_spec.head_dim)
    return (jnp.zeros(shape, draft_spec.dtype),
            jnp.zeros(shape, draft_spec.dtype))


# --------------------------------------------------------- program builders
def draft_prefill_dense_fn(draft_spec):
    """(params, state, (kc, vc), tokens [P,L], slots [P]) -> cache' —
    the draft's full-prompt prefill, rows scattered at ``slots`` (padding
    rows at the trash row)."""
    def fn(params, state, cache, tokens, slots):
        kc, vc = cache
        _, ks, vs = draft_spec.prefill_forward(params, state, tokens, None)
        L = tokens.shape[1]
        for i in range(draft_spec.n_blocks):
            kc = kc.at[i, slots, :L].set(ks[i])
            vc = vc.at[i, slots, :L].set(vs[i])
        return kc, vc
    return fn


def draft_prefill_state_fn(draft_spec):
    """(params, state, states_all, tokens [P,L], lengths [P], slots [P])
    -> states_all' — masked-scan prefill, final states landed at slots."""
    def fn(params, state, states_all, tokens, lengths, slots):
        P = tokens.shape[0]
        zero = jax.tree.map(
            lambda c: jnp.zeros((P,) + c.shape[1:], c.dtype), states_all)
        _, final = draft_spec.prefill_scan(params, state, tokens, lengths,
                                           zero)
        return jax.tree.map(lambda c, n: c.at[slots].set(n), states_all,
                            final)
    return fn


def propose_dense_fn(draft_spec, k: int):
    """(params, state, (kc, vc), cur [S], pos [S], active [S]) ->
    (proposals [S,k], cache'). Greedy chain: feed cur at pos -> p_1, feed
    p_1 -> p_2, ... The scan runs k+1 feeds (through p_k, whose K/V lands
    at pos+k) so a fully-accepted window leaves NO unwritten gap behind
    the next round's base position; rejected positions' K/V are
    overwritten next round before any mask can see them, so no rewind
    state is needed."""
    def fn(params, state, cache, cur, pos, active):
        kc, vc = cache

        def step(carry, _):
            kc, vc, tok, p = carry
            store = DenseDraftStore(kc, vc, p, active)
            logits = draft_spec.decode_step(params, state, tok, p, store)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            kc, vc = store.caches
            return (kc, vc, nxt, p + 1), nxt

        (kc, vc, _, _), toks = jax.lax.scan(
            step, (kc, vc, cur, pos), None, length=k + 1)
        return toks[:k].T, (kc, vc)                   # [S,k]
    return fn


def propose_state_fn(draft_spec, k: int):
    """(params, state, states_all, cur [S]) -> (proposals [S,k],
    states_stack). The scan feeds k+1 tokens (cur, p_1..p_k) so the stack
    s_1..s_{k+1} covers every possible rewind target — s_{j+1} is the
    state after consuming the j-th accepted proposal."""
    def fn(params, state, states_all, cur):
        S = cur.shape[0]
        st = jax.tree.map(lambda c: c[:S], states_all)

        def step(carry, _):
            st, tok = carry
            logits, st2 = draft_spec.decode_step(params, state, tok, st)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return (st2, nxt), (nxt, st2)

        _, (toks, stack) = jax.lax.scan(step, (st, cur), None, length=k + 1)
        return toks[:k].T, stack                      # [S,k], [k+1,S,...]
    return fn


def rewind_state_fn():
    """(states_all, stack, idx [S] in 1..k+1, mask [S]) -> states_all' —
    per-slot gather of the post-acceptance draft state; masked-off slots
    (finished, sampling, inactive) keep their state."""
    def fn(states_all, stack, idx, mask):
        S = idx.shape[0]
        rows = jnp.arange(S)
        sel = jax.tree.map(lambda st: st[idx - 1, rows], stack)

        def merge(all_, s):
            keep = mask.reshape((S,) + (1,) * (s.ndim - 1))
            return jnp.concatenate(
                [jnp.where(keep, s, all_[:S]), all_[S:]], axis=0)

        return jax.tree.map(merge, states_all, sel)
    return fn


def verify_fn(target_spec, block_len: int, k: int):
    """(params, state, cache, feeds [S,k+1], pos [S], tables, active) ->
    (greedy targets [S,k+1], cache'). One batched target pass over the
    verify window; row i's greedy argmax is EXACTLY what one-token decode
    at pos+i would emit."""
    from .kvcache import PagedWindowStore

    def fn(params, state, cache, feeds, pos, tables, active):
        store = PagedWindowStore(cache[0], cache[1], tables, pos, active,
                                 block_len, k + 1)
        logits = target_spec.decode_window(params, state, feeds, pos, store)
        targets = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return targets, store.pools
    return fn


# ----------------------------------------------------------- host-side rule
def accept_greedy(proposals: np.ndarray,
                  targets: np.ndarray) -> Tuple[np.ndarray, List[List[int]]]:
    """The exact-output acceptance rule. ``proposals`` [S,k] (draft),
    ``targets`` [S,k+1] (target greedy per window row). Returns
    (accepted_counts [S], emitted token lists): slot s emits its accepted
    proposals plus the target's correction token at the first disagreement
    — 1..k+1 tokens, each identical to what plain greedy decode emits."""
    S, k = proposals.shape
    agree = proposals == targets[:, :k]
    counts = np.where(agree.all(axis=1), k,
                      np.argmin(agree, axis=1)).astype(np.int64)
    emitted = [list(proposals[s, :counts[s]]) + [int(targets[s, counts[s]])]
               for s in range(S)]
    return counts, emitted
