"""Cache-aware autoregressive decode forwards (ISSUE 9 tentpole, models/ leg).

The training/serving forward recomputes every position's K/V each call; a
generation loop that re-ran it per emitted token would retrace O(T) work per
token. This module provides the *incremental* forward for the two generative
zoo models:

- ``GraphDecodeSpec`` (``TransformerDecodeSpec`` is its older name) — reads
  a language-model ``ComputationGraph`` by the kinds of its layers, not by
  one builder's vertex names: causal attention mixers go through a
  ``KVStore`` (those that keep the whole context through its pages, those
  with a sliding window through its rings; the layers share one key-value
  row layout and may differ in query heads), recurrent mixers (the gated short convolution) through a
  per-slot state the store carries, norms / MLPs / expert layers / adds
  replay their own ``apply``. ``models.transformer_lm`` is one case. It
  exposes:
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

    def attend(self, i: int, q, k_tok, v_tok, **latent) -> Any:
        """q: [B,H,1,Dh]; k_tok/v_tok: [B,H,Dh] for the current position.
        Returns the attention output [B,H,1,Dh]. (A window store, for
        ``decode_window``, takes q [B,H,W,Dh] and k/v [B,W,H,Dh].) With
        grouped-query attention q has the layer's query heads and k/v its
        (fewer) key-value heads.

        A latent-attention layer writes ONE row a token and no value:
        ``k_tok`` [B,1,row] is the token's cache row (the normed latent and
        the shared rotated key part), ``v_tok`` None, q [B,H,1,row] the
        queries absorbed into the rows' space, and the keywords ``scale``
        (the softmax scale, which the row's width does not give) and
        ``value_lanes`` (the leading lanes of a row that are its value)
        say how to read the rows. Returns [B,H,1,value_lanes]. A store
        that keeps K/V pairs alone takes no such call.

        A layer with a sliding window calls with the keyword ``window``
        (its width): ``i`` then counts the WINDOW layers, whose rows the
        store keeps apart from the pages (no more than the window and a
        page a sequence), and the row sees the keys ``pos - window <
        s <= pos``. A store that keeps whole contexts alone takes no such
        call.

        A layer with a block-sparse selection calls with the keywords
        ``select`` (its ``ops.sparse_select.Selection``) and
        ``select_index`` (its place among the selecting layers): its
        pages are layer ``i``'s of the full-context pools like any other's;
        the store also keeps its COMPRESSED keys, scores the query against
        them and reads the chosen pages alone."""
        ...

    def state(self, j) -> Any:
        """The state of recurrent mixer ``j`` = (kind, place in its kind)
        for the step's sequences (a store for a model that has none needs
        no such method)."""
        ...

    def set_state(self, j, new) -> None:
        """Leave recurrent mixer ``j``'s state after the step."""
        ...

    def state_pool(self, kind: int) -> Any:
        """The whole pool ``[layers of the kind, slots + 1, ...]`` of one
        kind of recurrent state, for a mixer that advances its slots'
        states in place (``decode_step``); ``set_state_pool`` hands it
        back; ``active`` [B] says which sequences are real."""
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
class StatefulDecodeUnsupportedError(ValueError):
    """A serving feature that does not carry a recurrent mixer's per-slot
    state (a gated short convolution's last rows, a lightning-attention
    layer's float32 matrix a head) was asked of a model that has one: a
    multi-token decode window (speculative verify, the int8 tier's
    fake-quantized prefill), or pools split over a mesh. It is refused by
    name: the one outcome not allowed is a silent wrong token."""


class SparseDecodeUnsupportedError(ValueError):
    """A serving feature that reads a sequence's whole context, or keeps
    none of what a selection is scored against (the int8 tier, a
    speculative verify window or draft, the prefix cache's shared pages,
    pools split over a mesh), was asked of a model with block-sparse
    attention layers, whose cache keeps compressed keys beside the pages
    and whose rows read the pages their own lists name. Refused by name,
    as a recurrent state, a latent cache and a window's ring are."""


class LatentDecodeUnsupportedError(ValueError):
    """A serving feature that keeps keys and values by head (the int8
    tier's quantized pools, a speculative draft's dense cache, pools split
    by head over a mesh) was asked of a model whose attention caches one
    latent row a token. Refused by name, as a recurrent state is."""


class WindowDecodeUnsupportedError(ValueError):
    """A serving feature that keeps or shares a sequence's WHOLE context
    per layer (the prefix cache's shared pages, the int8 tier, a
    speculative verify window or draft, pools split over a mesh) was asked
    of a model with sliding-window attention layers, whose cache keeps no
    more than the window's rows a sequence. Refused by name, as a
    recurrent state and a latent cache are."""


class GraphDecodeSpec:
    """A language-model ``ComputationGraph`` read by the KINDS of its
    layers, validated for the incremental decode path: token embedding
    (optionally a learned position table), then any graph of blocks whose
    sequence MIXERS are causal ``SelfAttentionLayer``s (served through a
    ``KVStore``) or recurrent layers with a fixed-shape state
    (``apply_with_final_state`` + ``state_at``: served from a per-slot
    state the store carries; the gated short convolution's rows in the
    model's dtype, a lightning-attention layer's float32 matrix a head:
    one pool a KIND of state, ``recurrent_kinds``), then a final norm
    (and whatever position-wise vertex lies between it and the head) and
    an ``RnnOutputLayer`` head. Every other vertex (norms, dense and gated
    MLPs, mixture-of-experts layers, residual adds, scalings) is
    position-wise and replays its own ``apply``. ``models.transformer_lm``
    (GPT-2) is one case, the hybrid convolution / attention / expert
    models and the sparse / linear attention hybrid others.

    What the attention layers must share is the cache's ROW: all latent
    (one ``row_lanes`` and ``kv_rank``) or all K/V with one ``kv_heads`` x
    ``head_dim``. Query heads may differ by layer (a layer's own count
    shapes its projections), and so may what a layer keeps: the whole
    context (``window`` None: the paged pools, ``full_names``) or a
    sliding window's rows (``window_names``, all of one width: a ring a
    slot beside the pages). At least one layer keeps the whole context:
    the pages' tables are what admission counts. A layer that keeps the
    whole context may READ a selection of it (``sparse_names``: a
    block-sparse selection, all of one size): its pages and its admission
    are a full layer's own, and beside them the store keeps its compressed
    keys, one row every ``stride`` positions a slot."""

    def __init__(self, net):
        from ..nn.layers import (EmbeddingSequenceLayer,
                                 LatentAttentionLayer,
                                 MixtureOfExpertsLayer,
                                 PositionalEmbeddingLayer,
                                 SelfAttentionLayer)
        from ..nn.layers.core import DenseLayer, RnnOutputLayer

        self.net = net
        if getattr(net.conf, "compute_dtype", None):
            raise ValueError("decode path does not support mixed "
                             "compute_dtype nets (params are served in "
                             "their stored dtype)")
        if not hasattr(net, "vertex_names"):
            raise ValueError("GraphDecodeSpec reads a ComputationGraph "
                             "(MultiLayerNetwork recurrent stacks take "
                             "LSTMDecodeSpec)")
        names = list(net.vertex_names)
        self._idx = {n: i for i, n in enumerate(names)}
        self._v = dict(zip(names, net.vertices))
        self._inputs = {n: list(net.conf.vertex_inputs[n]) for n in names}
        conf = net.conf
        if len(conf.network_inputs) != 1 or len(conf.network_outputs) != 1:
            raise ValueError("decode requires one input and one output")
        self.input_name = conf.network_inputs[0]
        self.head_name = conf.network_outputs[0]

        def layer(n):
            return getattr(self._v[n], "layer_conf", None)

        if not isinstance(layer(self.head_name), RnnOutputLayer):
            raise ValueError("decode requires an RnnOutputLayer head")
        if len(self._inputs[self.head_name]) != 1:
            raise ValueError("the head takes one input (the final norm)")
        self.final_name = self._inputs[self.head_name][0]
        fed = [n for n in names if self.input_name in self._inputs[n]]
        if len(fed) != 1:
            raise ValueError(f"not a language-model graph: the token input "
                             f"feeds {fed} (got vertices {names})")
        self.embed_name = fed[0]
        embed = layer(self.embed_name)
        self.token_input = isinstance(embed, EmbeddingSequenceLayer)
        if not self.token_input and not isinstance(embed, DenseLayer):
            raise ValueError(f"unsupported embed layer {type(embed).__name__}")
        pos = [n for n in names
               if isinstance(layer(n), PositionalEmbeddingLayer)]
        if len(pos) > 1:
            raise ValueError(f"more than one position table: {pos}")
        self.pos_name = pos[0] if pos else None
        self.attn_names = [n for n in names if isinstance(
            layer(n), (SelfAttentionLayer, LatentAttentionLayer))]
        self.recurrent_names = [n for n in names
                                if getattr(self._v[n], "recurrent", False)]
        self.moe_names = [n for n in names
                          if isinstance(layer(n), MixtureOfExpertsLayer)]
        if not self.attn_names:
            raise ValueError("no attention layer found (a graph of "
                             "recurrent mixers alone has no pages to serve "
                             f"from; got vertices {names})")
        attn0 = layer(self.attn_names[0])
        # the cache's KIND: K/V pages by head, or one latent row a token
        self.latent = isinstance(attn0, LatentAttentionLayer)

        def row(a):
            return (a.row_lanes, a.kv_rank) if self.latent \
                else (a.kv_heads, a.head_dim)

        for n in self.attn_names:
            a = layer(n)
            if not a.causal:
                raise ValueError("decode requires causal attention "
                                 f"blocks ({n} is not)")
            if type(a) is not type(attn0) or row(a) != row(attn0):
                raise ValueError(
                    "the attention layers must share one cache row (all "
                    "latent with one row width and rank, or all K/V with "
                    f"one kv_heads x head size): {n} keeps {row(a)}, "
                    f"{self.attn_names[0]} {row(attn0)}; query heads and "
                    "windows may differ by layer")
            if self.latent and a.n_heads != attn0.n_heads:
                raise ValueError("latent attention layers must share their "
                                 "query heads")
        # what a layer KEEPS: the whole context (the paged pools) or a
        # sliding window's rows (a ring a slot)
        self.window_names = [n for n in self.attn_names
                             if getattr(layer(n), "window", None)]
        self.full_names = [n for n in self.attn_names
                           if n not in self.window_names]
        widths = sorted({layer(n).window for n in self.window_names})
        if len(widths) > 1:
            raise ValueError("the sliding-window layers must share one "
                             f"window (one ring a slot), got {widths}")
        self.window = widths[0] if widths else None
        if not self.full_names:
            raise ValueError(
                "every attention layer has a sliding window "
                f"({self.window_names}): at least one must keep the whole "
                "context (admission counts the pages of those layers)")
        # full-context layers that READ a block-sparse selection of it
        self.sparse_names = [n for n in self.full_names
                             if getattr(layer(n), "sparse", None)]
        sizes = {layer(n).selection for n in self.sparse_names}
        if len(sizes) > 1:
            raise ValueError("the block-sparse layers must share one "
                             f"selection (one compressed row), got {sizes}")
        self.selection = sizes.pop() if sizes else None
        if self.selection is not None and self.latent:
            raise ValueError("a block-sparse selection reads K/V pages")
        self._sparse_i = {n: i for i, n in enumerate(self.sparse_names)}
        for n in self.recurrent_names:
            if not hasattr(layer(n), "state_at"):
                raise ValueError(
                    f"recurrent mixer {n} ({type(layer(n)).__name__}) has "
                    f"no state_at: its state at a padded prompt's true "
                    f"length cannot be read from one batched forward")
        # a layer's index among the layers of its kind of cache
        self._attn_i = {n: i for names in (self.full_names,
                                           self.window_names)
                        for i, n in enumerate(names)}
        self.dtype = jnp.dtype(net.conf.dtype)
        # the recurrent mixers by KIND of state (one row's shape and
        # dtype): one pool a kind, in the order the kinds first appear
        self.recurrent_kinds = []      # [(shape of a row, dtype, [names])]
        self._rec_j = {}               # name -> (kind, place in the kind)
        for n in self.recurrent_names:
            z = jax.eval_shape(lambda n=n: layer(n).zero_state(1, self.dtype))
            key = (tuple(z.shape[1:]), jnp.dtype(z.dtype))
            for g, (shape, dtype, names_g) in enumerate(self.recurrent_kinds):
                if (shape, dtype) == key:
                    break
            else:
                g = len(self.recurrent_kinds)
                self.recurrent_kinds.append(key + ([],))
            self._rec_j[n] = (g, len(self.recurrent_kinds[g][2]))
            self.recurrent_kinds[g][2].append(n)
        self.n_blocks = len(self.full_names)       # layers the pools hold
        self.n_window_layers = len(self.window_names)
        # the FIRST attention layer's; a layer's own count is what its
        # projections and its attention use
        self.n_heads = attn0.n_heads
        self.d_model = attn0.n_out
        # what a pool's row holds: ``kv_heads`` heads of ``head_dim``; a
        # latent row is one "head" as wide as the row is laid out
        self.kv_heads = 1 if self.latent else attn0.kv_heads
        self.head_dim = attn0.row_lanes if self.latent else attn0.head_dim
        self.vocab = layer(self.head_name).n_out
        self.max_length = layer(self.pos_name).max_length \
            if self.pos_name else None
        self.n_moe = len(self.moe_names)
        self.moe_top_k = layer(self.moe_names[0]).top_k if self.n_moe else 0
        self.moe_experts = layer(self.moe_names[0]).n_experts \
            if self.n_moe else 0

    @property
    def stateful(self) -> bool:
        """Whether a sequence carries more than its K/V pages."""
        return bool(self.recurrent_names)

    @property
    def cache_kind(self) -> str:
        return "latent" if self.latent else "kv"

    def supports_head_sharding(self, m: int) -> bool:
        """Whether the paged KV pools (and the Q/K/V/O projections) can
        split their head axis ``m`` ways: attention is head-local, so an
        even head split keeps every per-head row on one shard and decode
        stays token-for-token identical to the single-chip program. A
        latent pool has no head axis: every head reads every row."""
        if self.latent or self.window is not None \
                or self.selection is not None:
            return m == 1
        return m >= 1 and self.kv_heads % m == 0 and all(
            self._v[n].layer_conf.n_heads % m == 0 for n in self.attn_names)

    def recurrent_state_specs(self, rows: int):
        """[((layers of the kind, rows, ...), dtype)] a kind of recurrent
        state: what a cache keeps for ``rows`` sequences, each kind in a
        pool of its own shape and dtype. Empty for a model without
        recurrent mixers."""
        return [((len(names), rows) + shape, dtype)
                for shape, dtype, names in self.recurrent_kinds]

    def recurrent_state_shape(self, rows: int):
        """``recurrent_state_specs`` for a model whose mixers are all of
        one kind: that kind's shape, or None without recurrent mixers."""
        specs = self.recurrent_state_specs(rows)
        if len(specs) > 1:
            raise ValueError("the recurrent mixers keep states of "
                             f"{len(specs)} kinds: recurrent_state_specs "
                             "lists them")
        return specs[0][0] if specs else None

    # index/param helpers ---------------------------------------------------
    def vi(self, name: str) -> int:
        return self._idx[name]

    def _p(self, params, name: str):
        return params[self._idx[name]]

    def _apply(self, params, state, name: str, xs):
        """Run one named vertex exactly as apply_fn would (train=False,
        preprocessors honored, no mask)."""
        out, _ = self._v[name].apply(self._p(params, name),
                                     state[self._idx[name]], xs,
                                     train=False, rng=None)
        return out

    def embed_tokens(self, params, tokens):
        """[B,T] int token ids -> [B,T,d] embeddings via the model's own
        embed layer (gather, or one-hot matmul for the legacy input)."""
        embed = self._v[self.embed_name].layer_conf
        if self.token_input:
            return embed.apply(self._p(params, self.embed_name), {}, tokens,
                               train=False)[0]
        onehot = jax.nn.one_hot(tokens, self.vocab, dtype=self.dtype)
        return embed.apply(self._p(params, self.embed_name), {}, onehot,
                           train=False)[0]

    # ---------------------------------------------------------------- head
    def head_logits(self, params, y):
        """Pre-activation logits of the head over the final norm's output:
        y [B,T,d] -> [B,T,V]."""
        head_v = self._v[self.head_name]
        with jax.named_scope(self.head_name):
            if head_v.preprocessor is not None:
                y = head_v.preprocessor.apply(y)
            return head_v.layer_conf.pre_output(
                self._p(params, self.head_name), y)

    def logits_at(self, params, y, rows):
        """The head on ONE position of each sequence: y [B,T,d] is the
        final norm's output, rows [B] the position to read -> [B,V]. The
        rows are selected BEFORE the head, so no [B,T,V] value exists."""
        y = jnp.take_along_axis(y, rows[:, None, None], axis=1)  # [B,1,d]
        return self.head_logits(params, y)[:, 0]

    # ----------------------------------------------------- expert counters
    def _moe_stats(self, params, name, u, live):
        """What one expert layer routed: u [B,T,d] its input, live [B,T]
        the rows that are real. Returns (pairs the fullest expert got,
        experts with at least one pair), int32 scalars."""
        layer = self._v[name].layer_conf
        idx, _ = layer.route(self._p(params, name),
                             u.reshape(-1, u.shape[-1]))
        hits = jnp.broadcast_to(live.reshape(-1, 1), idx.shape)
        load = jnp.zeros((layer.n_experts,), jnp.int32).at[idx].add(
            hits.astype(jnp.int32))
        return jnp.max(load), jnp.sum(load > 0).astype(jnp.int32)

    @staticmethod
    def _fold_stats(per_layer):
        """[(load_max, touched)] a layer -> int32 [2]: the fullest expert
        of any layer, experts touched summed over the layers."""
        if not per_layer:
            return None
        a = jnp.stack([jnp.stack(s) for s in per_layer])      # [layers, 2]
        return jnp.stack([a[:, 0].max(), a[:, 1].sum()])

    # ------------------------------------------------------------- prefill
    def prefill_full(self, params, state, tokens, rows, lengths=None):
        """Full forward over the padded prompt [B,L] through the graph's own
        ``apply_fn`` (everything up to the final norm is what ``net.output``
        computes), plus what the caches keep.

        ``rows`` [B] names the position of each prompt whose logits the
        caller will read (``lengths - 1`` for the first sampled token); the
        head is applied to those rows alone. None asks for no logits (the
        draft's prefill keeps only K/V). ``lengths`` [B] are the prompts'
        true lengths: the recurrent mixers' states are taken THERE, not at
        the rung, and the expert counters count live rows only.

        Returns (logits [B,V] pre-activation or None, ks, vs, states,
        stats): ks[i]/vs[i] [B,L,Hkv,Dh] an attention layer in
        ``attn_names``' order (``split_kinds`` parts the layers that keep
        the whole context from those that keep a window), as a cache
        keeps them (k normed and rotated where the layer does that; a
        latent layer's ks[i] is its cache rows [B,L,1,row] and vs is
        empty: one pool); states[j] a recurrent mixer's state after
        ``lengths`` rows; stats int32 [2] (fullest expert's pairs, experts
        touched) or None. A model with block-sparse layers also keeps their
        compressed keys: ``compressed_rows(ks)`` makes them from these
        ``ks``."""
        x_in = tokens if self.token_input else \
            jax.nn.one_hot(tokens, self.vocab, dtype=self.dtype)
        acts, _ = self.net.apply_fn(params, state, [x_in], train=False)
        logits = None if rows is None else \
            self.logits_at(params, acts[self.final_name], rows)
        ks, vs = [], []
        for n in self.attn_names:
            y = acts[self._inputs[n][0]]
            if self.latent:
                ks.append(self._v[n].layer_conf.cache_rows(
                    self._p(params, n), y))
                continue
            _, k, v = self._v[n].layer_conf.project_qkv(self._p(params, n), y)
            ks.append(k)
            vs.append(v)
        states, stats = [], []
        if lengths is not None:
            for n in self.recurrent_names:
                states.append(self._v[n].layer_conf.state_at(
                    self._p(params, n), acts[self._inputs[n][0]], lengths))
            live = jnp.arange(tokens.shape[1])[None, :] < lengths[:, None]
            stats = [self._moe_stats(params, n, acts[self._inputs[n][0]],
                                     live) for n in self.moe_names]
        return logits, ks, vs, states, self._fold_stats(stats)

    def compressed_rows(self, ks):
        """The block-sparse layers' compressed keys from a prefill's keys
        (``ks`` in ``attn_names``' order): [P, L / stride, Hkv * Dh] a
        selecting layer."""
        from ..ops.sparse_select import compress_keys
        by_name = dict(zip(self.attn_names, ks))
        return [compress_keys(by_name[n], self.selection).reshape(
            by_name[n].shape[0], -1, self.kv_heads * self.head_dim)
            for n in self.sparse_names]

    def states_by_kind(self, states):
        """A prefill's states (``recurrent_names``' order) stacked a kind:
        [layers of the kind, P, ...] each, in ``recurrent_kinds``' order."""
        by_name = dict(zip(self.recurrent_names, states))
        return [jnp.stack([by_name[n] for n in names])
                for _, _, names in self.recurrent_kinds]

    def split_kinds(self, per_layer):
        """A list an attention layer (``attn_names``' order) -> (the
        full-context layers', the window layers'), each in its pool's
        order."""
        by_name = dict(zip(self.attn_names, per_layer))
        return ([by_name[n] for n in self.full_names],
                [by_name[n] for n in self.window_names])

    def prefill_forward(self, params, state, tokens, rows):
        """``prefill_full`` for a model that keeps K/V alone: (logits, ks,
        vs)."""
        return self.prefill_full(params, state, tokens, rows)[:3]

    # ------------------------------------------------------------ the walk
    def _walk(self, params, state, tokens, pos, store, *, window: bool,
              live=None):
        """W fed tokens a sequence (tokens [B,W] at positions ``pos ..
        pos+W-1``) through the graph, vertex by vertex, up to the final
        norm. Attention goes through ``store.attend`` (one-token protocol
        unless ``window``), a recurrent mixer takes and leaves its state in
        the store, everything else replays its own ``apply``. Returns
        (hidden [B,W,d], expert stats or None)."""
        B, W = tokens.shape
        w_pos = pos[:, None] + jnp.arange(W)[None, :]            # [B,W]
        acts = {}
        stats = []
        for name, v in self._v.items():
            if name == self.head_name:
                continue
            ins = self._inputs[name]
            # under the vertex's name, as the graph's own forward runs it
            with jax.named_scope(name):
                if name == self.embed_name:
                    out = self.embed_tokens(params, tokens)
                elif name == self.pos_name:
                    P = self._p(params, name)["P"]
                    out = v.layer_conf.act(
                        acts[ins[0]] + P[jnp.clip(w_pos, 0, P.shape[0] - 1)])
                elif name in self._attn_i:
                    out = self._attend(params, name, acts[ins[0]], w_pos,
                                       store, window)
                elif name in self._rec_j:
                    if window:
                        raise StatefulDecodeUnsupportedError(
                            f"a decode window does not carry {name}'s state")
                    j = self._rec_j[name]
                    if hasattr(v.layer_conf, "decode_step"):
                        # a state too large to copy a step: the layer
                        # advances its slots' rows of the pool in place
                        out, pool = v.layer_conf.decode_step(
                            self._p(params, name), acts[ins[0]], w_pos,
                            store.state_pool(j[0]), j[1], store.active)
                        store.set_state_pool(j[0], pool)
                    else:
                        out, new = v.apply_with_final_state(
                            self._p(params, name), state[self._idx[name]],
                            [acts[ins[0]]], train=False, rng=None,
                            initial_state=store.state(j))
                        store.set_state(j, new)
                else:
                    out = self._apply(params, state, name,
                                      [acts[i] for i in ins])
            if live is not None and name in self.moe_names:
                stats.append(self._moe_stats(params, name, acts[ins[0]],
                                             live))
            acts[name] = out
        return acts[self.final_name], self._fold_stats(stats)

    def _attend(self, params, name, y, w_pos, store, window):
        layer = self._v[name].layer_conf
        ap = self._p(params, name)
        B, W, _ = y.shape
        i = self._attn_i[name]
        if self.latent:
            # absorbed: attention over the cached rows themselves, then
            # back to the heads' values
            q = layer.absorbed_queries(ap, y, w_pos).transpose(0, 2, 1, 3)
            rows = layer.cache_rows(ap, y, w_pos)             # [B,W,1,row]
            out = store.attend(i, q, rows if window else rows[:, 0], None,
                               scale=layer.scale, value_lanes=layer.kv_rank)
            return layer.project_output(
                ap, layer.unabsorb(ap, out.transpose(0, 2, 1, 3)))
        q, k, v = layer.project_qkv(ap, y, w_pos)
        q = q.transpose(0, 2, 1, 3)                            # [B,H,W,Dh]
        if layer.window is not None:
            if window:
                raise WindowDecodeUnsupportedError(
                    f"a decode window does not carry {name}'s ring of "
                    f"{layer.window} rows")
            out = store.attend(i, q, k[:, 0], v[:, 0], window=layer.window)
        elif name in self._sparse_i:
            if window:
                raise SparseDecodeUnsupportedError(
                    f"a decode window does not make {name}'s selection a "
                    "row")
            out = store.attend(i, q, k[:, 0], v[:, 0], select=self.selection,
                               select_index=self._sparse_i[name])
        else:
            out = store.attend(i, q, k, v) if window else \
                store.attend(i, q, k[:, 0], v[:, 0])
        # the layer's OWN heads: layers of one model may differ in them
        out = out.transpose(0, 2, 1, 3).reshape(
            B, W, layer.n_heads * layer.head_dim)
        return layer.project_output(ap, out, y)

    # ---------------------------------------------------------- decode step
    def decode_step(self, params, state, tokens, pos, store: KVStore):
        """One incremental step: ``tokens`` [B] int ids at positions ``pos``
        [B]. K/V for the step go through ``store`` (write, then attend), so
        attention row ``pos`` sees the keys the naive causal row sees; a
        recurrent mixer reads and leaves its state in the store. Returns
        pre-activation logits [B,V]."""
        return self.decode_step_stats(params, state, tokens, pos, store)[0]

    def decode_step_stats(self, params, state, tokens, pos, store: KVStore,
                          live=None):
        """``decode_step`` and, with ``live`` [B] (the slots that are real),
        what the expert layers routed for them: (logits [B,V], int32 [2]
        (fullest expert's pairs, experts touched) or None)."""
        y, stats = self._walk(params, state, tokens[:, None], pos, store,
                              window=False,
                              live=None if live is None else live[:, None])
        return self.head_logits(params, y)[:, 0, :], stats

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
        token-for-token — the property the verify acceptance rule needs.
        A model with a recurrent mixer is refused
        (``StatefulDecodeUnsupportedError``)."""
        return self.head_logits(
            params, self.window_hidden(params, state, tokens, pos, store))

    def window_hidden(self, params, state, tokens, pos, store):
        """``decode_window`` up to the final norm: [B,W,d], the head's
        input. A caller that reads one row of the window (the int8 tier's
        prefill) selects it here and applies the head to that
        (``logits_at``)."""
        return self.window_hidden_stats(params, state, tokens, pos, store)[0]

    def window_hidden_stats(self, params, state, tokens, pos, store,
                            live=None):
        """``window_hidden`` and, with ``live`` [B,W] (the rows that are
        real), the expert layers' counters over them (else None)."""
        return self._walk(params, state, tokens, pos, store, window=True,
                          live=live)


# the name the GPT-2-only specification had; callers and tests keep it
TransformerDecodeSpec = GraphDecodeSpec


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
