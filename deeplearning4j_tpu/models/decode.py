"""Cache-aware autoregressive decode forwards (ISSUE 9 tentpole, models/ leg).

The training/serving forward recomputes every position's K/V each call; a
generation loop that re-ran it per emitted token would retrace O(T) work per
token. This module provides the *incremental* forward for the two generative
zoo models:

- ``TransformerDecodeSpec`` — walks a ``models.transformer_lm`` graph (the
  vertex names ``embed``/``pos``/``b{i}_*``/``ln_f``/``head`` are that
  builder's contract) and exposes:
    * ``prefill_forward`` — ONE full forward over the padded prompt that
      returns pre-activation logits for the ONE position of each prompt the
      caller names (the head runs on ``[B, 1, d]``, never on every padded
      position) **plus the per-layer K/V tensors** the serving layer
      scatters into its paged cache. Everything up to ``ln_f`` runs through
      ``ComputationGraph.apply_fn`` — the exact program the naive forward
      runs — so the row's logits are those of a plain ``net.output`` up to
      the matmul's tiling (and ride the fused Pallas attention whenever
      ``fused_attention_applicable`` says the shapes allow).
    * ``decode_step`` — one token per sequence through a ``KVStore``
      protocol object (serving/generation/kvcache.py provides the paged
      implementation). Every op but attention replays the layer objects'
      own ``apply`` math position-wise; the attention row is the store's:
      the same masked softmax over the same keys the full forward takes,
      so greedy decode through the cache is token-for-token identical to
      naive full recompute. (Not bit-for-bit: the paged store's kernel
      folds the keys page group by page group in an online softmax, which
      reorders float32 additions, as the flash kernel of a flash-eligible
      prefill does.)
- ``LSTMDecodeSpec`` — the recurrent analogue for ``text_generation_lstm``
  MultiLayerNetworks: the "cache" is the fixed-shape per-layer recurrent
  state (no paging needed), prefill is a masked ``lax.scan`` over the padded
  prompt, decode is one ``apply_fn`` step with the state carry.
- ``naive_generate`` — the cache-free reference decoder (full recompute per
  token via the public forward), the pin the bit-exactness tests compare
  against.

The reference DL4J has no analogue of any of this: its only generation
story is ``rnnTimeStep`` (reproduced as ``ComputationGraph.rnn_time_step``);
transformer decode is net-new capability.
"""
from __future__ import annotations

from typing import Any, List, Optional, Protocol, Sequence

import jax
import jax.numpy as jnp
import numpy as np


# --------------------------------------------------------------- KV protocol
class KVStore(Protocol):
    """What ``decode_step`` needs from a cache: write this step's K/V for
    layer ``i``, then attend to the context with the current position
    already visible. How the context is read is the store's business: the
    paged store attends to its pages where they lie, a dense store hands
    its array to ``parallel.ring_attention.attention`` under a key mask."""

    def attend(self, i: int, q, k_tok, v_tok) -> Any:
        """q: [B,H,1,Dh]; k_tok/v_tok: [B,H,Dh] for the current position.
        Returns the attention output [B,H,1,Dh]. (A window store, for
        ``decode_window``, takes q [B,H,W,Dh] and k/v [B,W,H,Dh].)"""
        ...


def window_attention(q, k, v, row_mask):
    """Row-masked softmax attention for a W-token decode window:
    q [B,H,W,Dh], k/v [B,H,L,Dh], row_mask [B,W,L] (True = visible).

    Mirrors ``parallel.ring_attention.attention``'s arithmetic EXACTLY
    (same scale cast, same -1e30 fill, same softmax axis) but with a
    per-query-row key mask — row ``i`` seeing keys ``<= pos+i`` computes
    the very numbers the one-token ``attention(..., key_mask=)`` row
    computes. The dense-context attention of the int8 KV tier (decode,
    verify and the fake-quantized prefill alike) and the plain reference
    the paged kernel is pinned to."""
    scale = 1.0 / np.sqrt(q.shape[-1])
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * jnp.asarray(scale, q.dtype)
    s = jnp.where(row_mask[:, None, :, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


# ---------------------------------------------------------------- transformer
class TransformerDecodeSpec:
    """Vertex map of a ``models.transformer_lm`` graph, validated for the
    incremental decode path."""

    def __init__(self, net):
        from ..nn.layers import (EmbeddingSequenceLayer, LayerNormalization,
                                 SelfAttentionLayer)
        from ..nn.layers.core import DenseLayer, RnnOutputLayer

        self.net = net
        if getattr(net.conf, "compute_dtype", None):
            raise ValueError("decode path does not support mixed "
                             "compute_dtype nets (params are served in "
                             "their stored dtype)")
        names = list(net.vertex_names)
        self._idx = {n: i for i, n in enumerate(names)}
        for required in ("embed", "pos", "ln_f", "head"):
            if required not in self._idx:
                raise ValueError(
                    f"not a models.transformer_lm graph: vertex {required!r} "
                    f"missing (got {names})")
        self.n_blocks = 0
        while f"b{self.n_blocks}_attn" in self._idx:
            self.n_blocks += 1
        if self.n_blocks == 0:
            raise ValueError("no attention blocks found (b0_attn missing)")
        v = net.vertices
        self._v = {n: v[i] for n, i in self._idx.items()}
        embed = self._v["embed"].layer_conf
        self.token_input = isinstance(embed, EmbeddingSequenceLayer)
        if not self.token_input and not isinstance(embed, DenseLayer):
            raise ValueError(f"unsupported embed layer {type(embed).__name__}")
        attn0 = self._v["b0_attn"].layer_conf
        if not isinstance(attn0, SelfAttentionLayer) or not attn0.causal:
            raise ValueError("decode requires causal SelfAttentionLayer "
                             "blocks")
        if not isinstance(self._v["head"].layer_conf, RnnOutputLayer):
            raise ValueError("decode requires an RnnOutputLayer head")
        if not isinstance(self._v["ln_f"].layer_conf, LayerNormalization):
            raise ValueError("decode requires a LayerNormalization final "
                             "norm")
        self.n_heads = attn0.n_heads
        self.d_model = attn0.n_out
        self.head_dim = self.d_model // self.n_heads
        self.vocab = self._v["head"].layer_conf.n_out
        self.max_length = self._v["pos"].layer_conf.max_length
        self.dtype = jnp.dtype(net.conf.dtype)

    def supports_head_sharding(self, m: int) -> bool:
        """Whether the paged KV pools (and the Q/K/V/O projections) can
        split their head axis ``m`` ways: attention is head-local, so an
        even head split keeps every per-head row on one shard and decode
        stays token-for-token identical to the single-chip program."""
        return m >= 1 and self.n_heads % m == 0

    # index/param helpers ---------------------------------------------------
    def vi(self, name: str) -> int:
        return self._idx[name]

    def _p(self, params, name: str):
        return params[self._idx[name]]

    def _apply(self, params, state, name: str, x):
        """Run one named LayerVertex exactly as apply_fn would (train=False,
        preprocessors honored, no mask)."""
        v = self._v[name]
        out, _ = v.apply(self._p(params, name), state[self._idx[name]], [x],
                         train=False, rng=None)
        return out

    def _heads(self, x):
        """[B,T,d] -> [B,H,T,Dh] (SelfAttentionLayer._heads layout)."""
        B, T, _ = x.shape
        return x.reshape(B, T, self.n_heads, self.head_dim).transpose(
            0, 2, 1, 3)

    def embed_tokens(self, params, tokens):
        """[B,T] int token ids -> [B,T,d] embeddings via the model's own
        embed layer (gather, or one-hot matmul for the legacy input)."""
        embed = self._v["embed"].layer_conf
        if self.token_input:
            return embed.apply(self._p(params, "embed"), {}, tokens,
                               train=False)[0]
        onehot = jax.nn.one_hot(tokens, self.vocab, dtype=self.dtype)
        return embed.apply(self._p(params, "embed"), {}, onehot,
                           train=False)[0]

    # ---------------------------------------------------------------- head
    def head_logits(self, params, y):
        """Pre-activation logits of the head over ``ln_f``'s output:
        y [B,T,d] -> [B,T,V]."""
        head_v = self._v["head"]
        if head_v.preprocessor is not None:
            y = head_v.preprocessor.apply(y)
        return head_v.layer_conf.pre_output(self._p(params, "head"), y)

    def logits_at(self, params, y, rows):
        """The head on ONE position of each sequence: y [B,T,d] is
        ``ln_f``'s output, rows [B] the position to read -> [B,V]. The
        rows are selected BEFORE the head, so no [B,T,V] value exists."""
        y = jnp.take_along_axis(y, rows[:, None, None], axis=1)  # [B,1,d]
        return self.head_logits(params, y)[:, 0]

    # ------------------------------------------------------------- prefill
    def prefill_forward(self, params, state, tokens, rows):
        """Full forward over the padded prompt [B,L] through the graph's own
        ``apply_fn`` (everything up to ``ln_f`` is what ``net.output``
        computes), plus the per-layer K/V tensors for the cache.

        ``rows`` [B] names the position of each prompt whose logits the
        caller will read (``lengths - 1`` for the first sampled token); the
        head is applied to those rows alone. None asks for no logits (the
        draft's prefill keeps only K/V).

        Returns (logits [B,V] pre-activation or None, ks, vs) with
        ks[i]/vs[i]: [B,L,H,Dh]."""
        x_in = tokens if self.token_input else \
            jax.nn.one_hot(tokens, self.vocab, dtype=self.dtype)
        acts, _ = self.net.apply_fn(params, state, [x_in], train=False)
        logits = None if rows is None else \
            self.logits_at(params, acts["ln_f"], rows)
        ks, vs = [], []
        for i in range(self.n_blocks):
            ap = self._p(params, f"b{i}_attn")
            y = acts[f"b{i}_ln1"]
            B, L, _ = y.shape
            ks.append((y @ ap["Wk"]).reshape(B, L, self.n_heads,
                                             self.head_dim))
            vs.append((y @ ap["Wv"]).reshape(B, L, self.n_heads,
                                             self.head_dim))
        return logits, ks, vs

    # ---------------------------------------------------------- decode step
    def decode_step(self, params, state, tokens, pos, store: KVStore):
        """One incremental step: ``tokens`` [B] int ids at positions ``pos``
        [B]. K/V for the step go through ``store`` (write, then attend), so
        attention row ``pos`` sees the keys the naive causal row sees.
        Returns pre-activation logits [B,V]."""
        x = self.embed_tokens(params, tokens[:, None])        # [B,1,d]
        P = self._p(params, "pos")["P"]
        x = x + P[pos][:, None, :]
        pos_layer = self._v["pos"].layer_conf
        x = pos_layer.act(x)
        for i in range(self.n_blocks):
            x = self._block_step(params, state, i, x, pos, store)
        y = self._apply(params, state, "ln_f", x)
        return self.head_logits(params, y)[:, 0, :]

    def _block_step(self, params, state, i, x, pos, store: KVStore):
        h = x
        y = self._apply(params, state, f"b{i}_ln1", x)        # [B,1,d]
        ap = self._p(params, f"b{i}_attn")
        attn_layer = self._v[f"b{i}_attn"].layer_conf
        B = y.shape[0]
        q = self._heads(y @ ap["Wq"])                          # [B,H,1,Dh]
        k_tok = (y @ ap["Wk"]).reshape(B, self.n_heads, self.head_dim)
        v_tok = (y @ ap["Wv"]).reshape(B, self.n_heads, self.head_dim)
        out = store.attend(i, q, k_tok, v_tok)
        out = out.transpose(0, 2, 1, 3).reshape(B, 1, self.d_model)
        if attn_layer.project_out:
            out = out @ ap["Wo"] + ap["b"]
        out = attn_layer.act(out)
        x = h + out                                            # b{i}_add1
        h2 = x
        y2 = self._apply(params, state, f"b{i}_ln2", x)
        f = self._apply(params, state, f"b{i}_ff2",
                        self._apply(params, state, f"b{i}_ff1", y2))
        return h2 + f                                          # b{i}_add2

    # --------------------------------------------------------- decode window
    def decode_window(self, params, state, tokens, pos, store):
        """W tokens per sequence in ONE pass — the speculative-verify
        forward. ``tokens`` [B,W] are fed at positions ``pos .. pos+W-1``;
        ``store`` is a window store (``attend`` takes k/v [B,W,H,Dh] and
        lets row ``i`` see the keys at positions ``<= pos+i``). Every op is
        the [B,W,·] batched form of the exact per-position ``decode_step``
        math (all non-attention ops are position-wise; attention rows
        carry per-row limits), so the
        returned logits [B,W,V] match W sequential decode steps
        token-for-token — the property the verify acceptance rule needs."""
        return self.head_logits(
            params, self.window_hidden(params, state, tokens, pos, store))

    def window_hidden(self, params, state, tokens, pos, store):
        """``decode_window`` up to ``ln_f``: [B,W,d], the head's input. A
        caller that reads one row of the window (the int8 tier's prefill)
        selects it here and applies the head to that (``logits_at``)."""
        B, W = tokens.shape
        x = self.embed_tokens(params, tokens)                  # [B,W,d]
        P = self._p(params, "pos")["P"]
        w_pos = pos[:, None] + jnp.arange(W)[None, :]          # [B,W]
        x = x + P[jnp.clip(w_pos, 0, P.shape[0] - 1)]
        pos_layer = self._v["pos"].layer_conf
        x = pos_layer.act(x)
        for i in range(self.n_blocks):
            x = self._block_window(params, state, i, x, store)
        return self._apply(params, state, "ln_f", x)

    def _block_window(self, params, state, i, x, store):
        h = x
        y = self._apply(params, state, f"b{i}_ln1", x)         # [B,W,d]
        ap = self._p(params, f"b{i}_attn")
        attn_layer = self._v[f"b{i}_attn"].layer_conf
        B, W, _ = y.shape
        q = self._heads(y @ ap["Wq"])                          # [B,H,W,Dh]
        k_win = (y @ ap["Wk"]).reshape(B, W, self.n_heads, self.head_dim)
        v_win = (y @ ap["Wv"]).reshape(B, W, self.n_heads, self.head_dim)
        out = store.attend(i, q, k_win, v_win)
        out = out.transpose(0, 2, 1, 3).reshape(B, W, self.d_model)
        if attn_layer.project_out:
            out = out @ ap["Wo"] + ap["b"]
        out = attn_layer.act(out)
        x = h + out
        h2 = x
        y2 = self._apply(params, state, f"b{i}_ln2", x)
        f = self._apply(params, state, f"b{i}_ff2",
                        self._apply(params, state, f"b{i}_ff1", y2))
        return h2 + f


# ----------------------------------------------------------------------- LSTM
class LSTMDecodeSpec:
    """Incremental decode for ``text_generation_lstm``-style
    MultiLayerNetworks (LSTM/GravesLSTM stack + RnnOutputLayer): the decode
    cache is the per-layer recurrent state — fixed shape, so it rides the
    same zero-recompile engine without paging."""

    def __init__(self, net):
        from ..nn.layers.core import RnnOutputLayer
        self.net = net
        if hasattr(net, "vertex_names"):
            raise ValueError("LSTMDecodeSpec supports MultiLayerNetwork "
                             "stacks (ComputationGraph transformers take "
                             "TransformerDecodeSpec)")
        if getattr(net.conf, "compute_dtype", None):
            raise ValueError("decode path does not support mixed "
                             "compute_dtype nets")
        last = net.layers[-1]
        if not isinstance(last, RnnOutputLayer):
            raise ValueError("LSTM decode requires an RnnOutputLayer head")
        if not any(hasattr(l, "apply_with_final_state") for l in net.layers):
            raise ValueError("no recurrent layer found")
        self.vocab = last.n_out
        self.n_in = net.layers[0].n_in
        self.dtype = jnp.dtype(net.conf.dtype)
        self.token_input = False          # char-LM contract: one-hot input

    def supports_head_sharding(self, m: int) -> bool:
        """The recurrent-state cache has no head axis to shard — only the
        degenerate m=1 'split' is supported."""
        return m == 1

    def init_states(self, batch: int):
        """Zero-filled recurrent-state carry for ``batch`` sequences, with
        the same pytree structure ``apply_fn(collect_rnn_states=True)``
        emits (None for non-recurrent layers)."""
        x0 = jnp.zeros((batch, 1, self.n_in), self.dtype)
        _, _, states = self.net.apply_fn(self.net.params, self.net.state, x0,
                                         train=False,
                                         collect_rnn_states=True)
        return jax.tree.map(jnp.zeros_like, states)

    def _step(self, params, state, x_t, rnn_states):
        """One [B,1,V] step -> (pre-activation logits [B,V], new states)."""
        acts, _, new_states = self.net.apply_fn(
            params, state, x_t, train=False, rnn_states=rnn_states,
            collect_rnn_states=True)
        head = self.net.layers[-1]
        feed = acts[-2]
        logits = head.pre_output(params[-1], feed)
        return logits[:, 0, :], new_states

    def decode_step(self, params, state, tokens, rnn_states):
        """tokens [B] int ids -> (logits [B,V], new rnn states)."""
        x = jax.nn.one_hot(tokens[:, None], self.vocab, dtype=self.dtype)
        return self._step(params, state, x, rnn_states)

    def prefill_scan(self, params, state, tokens, lengths, rnn_states):
        """Masked scan over the padded prompt [B,L]: state only advances
        while t < length, and the returned logits are the row at position
        ``length-1`` — exactly what a per-token ``rnn_time_step`` priming
        loop produces, in one fixed-shape program."""
        B, L = tokens.shape
        onehot = jax.nn.one_hot(tokens, self.vocab, dtype=self.dtype)

        def step(carry, t):
            states, logits_out = carry
            x_t = jax.lax.dynamic_slice_in_dim(onehot, t, 1, axis=1)
            logits_t, new_states = self._step(params, state, x_t, states)
            live = (t < lengths)
            states = jax.tree.map(
                lambda n, o: jnp.where(
                    live.reshape((B,) + (1,) * (n.ndim - 1)), n, o),
                new_states, states)
            logits_out = jnp.where((t == lengths - 1)[:, None], logits_t,
                                   logits_out)
            return (states, logits_out), None

        logits0 = jnp.zeros((B, self.vocab), self.dtype)
        (states, logits), _ = jax.lax.scan(step, (rnn_states, logits0),
                                           jnp.arange(L))
        return logits, states


# ------------------------------------------------------------- draft builder
def truncated_draft(net, n_blocks: int = 1):
    """Build a speculative-decoding draft by TRUNCATING a
    ``models.transformer_lm`` target: same embed/pos/head (and the first
    ``n_blocks`` transformer blocks) with the target's own weights, fewer
    blocks. A well-trained deep LM's later blocks refine a prediction the
    early blocks already carry, so the truncation is the zero-extra-training
    draft — where that residual refinement is small, greedy agreement (and
    so accepted tokens per verify) is high.

    Returns a fresh ComputationGraph sharing no mutable state with the
    target (params copied by vertex NAME, jnp arrays are immutable)."""
    from .zoo_extra import transformer_lm

    spec = TransformerDecodeSpec(net)
    if not 1 <= n_blocks <= spec.n_blocks:
        raise ValueError(f"draft n_blocks must be in 1..{spec.n_blocks}, "
                         f"got {n_blocks}")
    draft = transformer_lm(vocab_size=spec.vocab, d_model=spec.d_model,
                           n_heads=spec.n_heads, n_blocks=n_blocks,
                           max_length=spec.max_length,
                           dtype=str(net.conf.dtype),
                           token_input=spec.token_input).init()
    src = {n: p for n, p in zip(net.vertex_names, net.params)}
    draft.params = tuple(
        src.get(n, p) for n, p in zip(draft.vertex_names, draft.params))
    return draft


# ------------------------------------------------------------ naive reference
def naive_generate(net, prompt_ids: Sequence[int], max_new: int, *,
                   pad_to: int, spec: Optional[Any] = None) -> List[int]:
    """Cache-free greedy reference decode: one FULL forward (public
    ``net.output``) per emitted token over the prompt+generated-so-far,
    padded to ``pad_to`` (the serving cache capacity, so both paths mask
    attention over the same padded context). The bit-exactness pin in
    tests/test_generation.py compares the paged-cache engine against this
    token-for-token."""
    spec = spec or TransformerDecodeSpec(net)
    ids = [int(t) for t in prompt_ids]
    if len(ids) + max_new > pad_to:
        raise ValueError(f"prompt ({len(ids)}) + max_new ({max_new}) "
                         f"exceeds pad_to ({pad_to})")
    out: List[int] = []
    for _ in range(max_new):
        buf = np.zeros((1, pad_to), np.int32)
        buf[0, :len(ids)] = ids
        if getattr(spec, "token_input", False):
            x = buf
        else:
            x = np.zeros((1, pad_to, spec.vocab), np.dtype(spec.dtype))
            x[0, np.arange(len(ids)), ids] = 1.0
        probs = np.asarray(net.output(x))       # [1, pad_to, V] (softmax)
        nxt = int(np.argmax(probs[0, len(ids) - 1]))
        out.append(nxt)
        ids.append(nxt)
    return out


def naive_generate_lstm(net, prompt_ids: Sequence[int],
                        max_new: int) -> List[int]:
    """Greedy reference for the LSTM path via the public streaming
    ``rnn_time_step`` API (the reference DL4J's only generation story)."""
    vocab = net.layers[-1].n_out
    net.rnn_clear_previous_state()
    probs = None
    for t in prompt_ids:
        x = np.zeros((1, vocab), np.float32)
        x[0, int(t)] = 1.0
        probs = np.asarray(net.rnn_time_step(x))[0]
    out: List[int] = []
    for _ in range(max_new):
        nxt = int(np.argmax(probs))
        out.append(nxt)
        x = np.zeros((1, vocab), np.float32)
        x[0, nxt] = 1.0
        probs = np.asarray(net.rnn_time_step(x))[0]
    return out
