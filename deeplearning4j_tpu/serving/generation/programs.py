"""AOT-warmed generation programs: bucketed prefill + ONE decode step.

Extends the ``serving/programs.py`` discipline to autoregressive decode:
every program the steady-state loop can ever need is lowered and compiled at
``warm()`` —

  - one **prefill** executable per (admission-batch rung P, prompt rung L):
    padded prompt -> the graph's own ``apply_fn`` up to ``ln_f``, the head
    on each prompt's last live row alone, K/V scattered into the paged
    pools, first token sampled in-program;
  - one **decode-step** executable: one token per in-flight slot, scatter
    the step's K/V, attend to the slot's pages in place through the block
    table (``ops.pallas_paged_attention``), sample the next token — cache
    buffers donated so the pool updates in place. A row's token comes
    from the host where the host knows it and else from the step before,
    as that step left it on the device: the loop launches a step before
    it has read the last one (``launch_decode`` / ``read_decode``).

Params/state are arguments, not constants, so hot-swap reuses executables
exactly as the forward-serving ProgramSet does (``with_params_from``).
The PRNG key is carried through every program and split in-program.

What a launch brings from the HOST is one int32 array, so one transfer:
a row a slot (decode) or a prompt (prefill) holding every per-row
argument, the temperature's float32 bit for bit (``pack_decode`` /
``unpack_decode``, ``pack_prefill`` / ``unpack_prefill``). A program
unpacks it as its first act; everything else it takes (parameters,
state, cache, key, the step before's tokens) is on the device already.

Model support is adapter-based: ``models.decode.GraphDecodeSpec`` (paged
KV cache for a graph's attention layers and, beside the pools in the same
cache pytree, a fixed-shape per-slot state for its recurrent mixers) and
``models.decode.LSTMDecodeSpec`` (the cache is the fixed-shape recurrent
state; the block machinery degenerates to zero-block bookkeeping but the
program/scheduler contract is identical).

A model with expert layers hands its routing counters back BEHIND the
sampled tokens in the one array a program's caller reads back
(``split_stats``): no dispatch, no sync and no program more.
"""
from __future__ import annotations

import contextlib
import math
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.interpreters.partial_eval import dce_jaxpr
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ...models.decode import (GraphDecodeSpec, LatentDecodeUnsupportedError,
                              SparseDecodeUnsupportedError,
                              LSTMDecodeSpec, StatefulDecodeUnsupportedError,
                              WindowDecodeUnsupportedError)
from ...parallel.tensor_parallel import (MODEL_AXIS, build_param_specs,
                                         model_axis_size, per_replica_bytes,
                                         shard_params)
from ...telemetry import span
from ..programs import _arch_key, _tree_signature
from .kvcache import (PagedStore, QuantSimStore, compressed_prefill_fill,
                      cow_copy, make_compressed, make_pools,
                      make_rings, prefill_scatter, ring_pages,
                      ring_prefill_fill)
from .sampling import sample_tokens


def _ceil_to(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


# how far down the queue an admission pass looks for partners of its
# head's rung (``GenerationConfig.admission_choice``): integer compares on
# a rung stamped at submission, so the bound is on the worst case of a
# long queue of other rungs, not on the common one
ADMIT_LOOKAHEAD = 32


@dataclass
class GenerationConfig:
    """Shape/capacity plan for one generation model. Everything here is
    trace-time static — the warmed program set covers the full plan, so
    admission-time work is array fills only."""
    block_len: int = 16
    max_seq_len: int = 128            # prompt + generated tokens, per request
    decode_slots: int = 8             # in-flight sequences per decode step
    prefill_batches: Tuple[int, ...] = (1, 2, 4)
    prompt_rungs: Optional[Tuple[int, ...]] = None   # default: (capacity,)
    num_blocks: Optional[int] = None  # pool size; default: full occupancy + 1
    queue_limit: int = 256
    default_timeout_s: float = 30.0
    default_max_tokens: int = 32
    seed: int = 0
    # prefix-cache sharing (ISSUE 14): None = on for the paged adapter,
    # off for the state adapter (no blocks to share); True/False overrides
    prefix_cache: Optional[bool] = None
    # longest unmatched prompt suffix (tokens) a cache hit may REPLAY
    # through the one-token decode program; a shorter match is treated as
    # a miss — sequential replay of a long suffix would cost far more
    # than the batched prefill it "saves". None = 2 * block_len.
    prefix_max_replay: Optional[int] = None
    # speculative decoding: draft proposals per verify window; 0 with a
    # draft model attached defaults to 4 at program-set construction
    spec_k: int = 0
    # quantized KV tier (ISSUE 17): "int8" stores the paged block pool as
    # int8 codes + per-(token, head) f32 scales — quantize-on-write /
    # dequantize-in-attention inside the warmed programs, so the same
    # num_blocks holds ~2x+ the tokens per byte. None = full precision.
    kv_cache_dtype: Optional[str] = None

    def __post_init__(self):
        if self.block_len < 1 or self.decode_slots < 1:
            raise ValueError("block_len and decode_slots must be >= 1")
        if self.kv_cache_dtype not in (None, "int8"):
            raise ValueError(f"kv_cache_dtype must be None or 'int8', got "
                             f"{self.kv_cache_dtype!r}")
        if self.spec_k < 0:
            raise ValueError("spec_k must be >= 0")
        self.capacity = _ceil_to(self.max_seq_len, self.block_len)
        self.blocks_per_seq = self.capacity // self.block_len
        self.prefill_batches = tuple(sorted(set(
            int(b) for b in self.prefill_batches)))
        if not self.prefill_batches or self.prefill_batches[0] < 1:
            raise ValueError("prefill_batches must be positive")
        rungs = self.prompt_rungs or (self.capacity,)
        rungs = tuple(sorted({min(_ceil_to(int(r), self.block_len),
                                  self.capacity) for r in rungs}))
        if rungs[-1] != self.capacity:
            rungs = rungs + (self.capacity,)
        self.prompt_rungs = rungs
        if self.num_blocks is None:
            self.num_blocks = self.decode_slots * self.blocks_per_seq + 1
        if self.num_blocks < 2:
            raise ValueError("num_blocks must be >= 2 (block 0 is trash)")
        if self.prefix_max_replay is None:
            self.prefix_max_replay = 2 * self.block_len
        elif self.prefix_max_replay < 1:
            raise ValueError("prefix_max_replay must be >= 1 (the final "
                             "prompt token always replays)")

    @property
    def max_prompt_len(self) -> int:
        return self.prompt_rungs[-1]

    def blocks_needed(self, prompt_len: int, max_new: int) -> int:
        return math.ceil((prompt_len + max_new) / self.block_len)

    def prefill_rung(self, n: int) -> int:
        for b in self.prefill_batches:
            if n <= b:
                return b
        return self.prefill_batches[-1]

    def prompt_rung(self, plen: int) -> int:
        for r in self.prompt_rungs:
            if plen <= r:
                return r
        raise ValueError(f"prompt length {plen} exceeds the largest prompt "
                         f"rung {self.prompt_rungs[-1]}")

    def filled_batch(self, n: int) -> int:
        """The largest batch rung that ``n`` requests fill with no empty
        row; ``n`` itself where it fills none (fewer requests than the
        smallest rung: the rows are padded, as for any lone request)."""
        return max((b for b in self.prefill_batches if b <= n), default=n)

    def admission_choice(self, rungs: Sequence[int], waiting: int,
                         free_slots: int) -> List[int]:
        """Which waiting requests one admission pass takes, as indices
        into the queue, ascending. ``rungs`` are the prompt rungs of the
        queue's first requests in queue order (no more than
        ``ADMIT_LOOKAHEAD`` of them are read), ``waiting`` is how many
        requests wait in all.

        When everything that waits fits into the pass (``waiting`` <= the
        smaller of ``free_slots`` and the largest batch rung) the pass
        takes all of it in arrival order, whatever the rungs: holding a
        request back would add a launch to its first token on a chip that
        has time to spare. When more wait than the pass can take, the
        order among them is free and padded positions are what the chip is
        short of: the pass takes the head, then the next waiting requests
        of the HEAD'S rung, and cuts the batch back to the largest batch
        rung they fill, so that the prefill program it launches has no row
        of another rung's width and no empty row. Requests of another rung
        are never pulled in. The head always goes first, so a request is
        admitted within as many admitting passes as its place in the queue
        when it arrived."""
        room = min(free_slots, self.prefill_batches[-1])
        if room < 1 or waiting < 1:
            return []
        if waiting <= room:
            return list(range(waiting))
        chosen = [i for i, rung in enumerate(rungs[:ADMIT_LOOKAHEAD])
                  if rung == rungs[0]][:room]
        return chosen[:self.filled_batch(len(chosen))]


# Every cache-carrying program donates its cache argument (argnum 2): the
# pools update in place, and the caller must rebind the returned cache and
# never touch the one it passed in. Every backend of jax 0.9 honours
# donation, the CPU included, so the test suite runs the same aliasing the
# chip does.
_DONATE_CACHE = (2,)


def _head_rows(jaxpr, vocab: int) -> int:
    """Rows of every matmul a traced program runs whose result is ``vocab``
    wide: the positions the head is applied to. Dead code goes first, as
    XLA drops it (``apply_fn`` traces the graph's own head over every
    position, and the prefill program reads none of it); a scan's body
    counts once a step."""
    def count(j):
        rows = 0
        for eqn in j.eqns:
            shape = eqn.outvars[0].aval.shape if eqn.outvars else ()
            if eqn.primitive.name == "dot_general" \
                    and shape[-1:] == (vocab,):
                rows += math.prod(shape[:-1])
            steps = eqn.params["length"] \
                if eqn.primitive.name == "scan" else 1
            rows += steps * sum(
                count(sub) for sub in jax.core.jaxprs_in_params(eqn.params))
        return rows
    live, _ = dce_jaxpr(jaxpr, [True] * len(jaxpr.outvars))
    return count(live)


# ---- a launch's host arguments: ONE int32 array, one transfer. The column
# order is written down here and nowhere else: a ``pack_*`` on the host and
# its ``unpack_*`` as the program's first act, every caller through them.
DECODE_COLS = 6     # a row a slot; the slot's table row behind them
PREFILL_COLS = 4    # a row a prompt; its table row, then its tokens, behind


def _f32(col):
    """An int32 column as the float32 it carries bit for bit: a view on the
    host, a bitcast in a program."""
    if isinstance(col, np.ndarray):
        return col.view(np.float32)
    return lax.bitcast_convert_type(col, jnp.float32)


def pack_decode(tokens, host_known, pos, tables, active, temp,
                topk) -> np.ndarray:
    """A decode step's host arguments as one FRESH ``[S, DECODE_COLS +
    blocks_per_seq]`` int32 array (the caller may write its own arrays as
    soon as this returns)."""
    packed = np.empty((len(tokens), DECODE_COLS + tables.shape[1]), np.int32)
    for col, a in enumerate((tokens, host_known, pos, active,
                             np.asarray(temp, np.float32).view(np.int32),
                             topk)):
        packed[:, col] = a
    packed[:, DECODE_COLS:] = tables
    return packed


def unpack_decode(packed):
    """``pack_decode``'s array, in a program or on the host -> (tokens,
    host_known, pos, tables, active, temp, topk), each value what was
    packed, to the bit."""
    tokens, host_known, pos, active, temp, topk = (
        packed[:, col] for col in range(DECODE_COLS))
    return (tokens, host_known != 0, pos, packed[:, DECODE_COLS:],
            active != 0, _f32(temp), topk)


def unpack_prefill(packed, blocks_per_seq: int):
    """A prefill's ``[P, PREFILL_COLS + blocks_per_seq + L]`` int32 array,
    in a program or on the host -> (tokens [P, L], lengths, tables, slots,
    temp, topk). Of a numpy array these are writable VIEWS: the scheduler
    fills a fresh array's rows through them."""
    lengths, slots, temp, topk = (
        packed[:, col] for col in range(PREFILL_COLS))
    at = PREFILL_COLS + blocks_per_seq
    return (packed[:, at:], lengths, packed[:, PREFILL_COLS:at], slots,
            _f32(temp), topk)


def padding_prefill(P: int, L: int, blocks_per_seq: int,
                    trash_slot: int) -> np.ndarray:
    """A fresh (P, L) prefill array whose every row is padding: one token,
    the trash slot, no pages, greedy."""
    packed = np.zeros((P, PREFILL_COLS + blocks_per_seq + L), np.int32)
    _, lengths, _, slots, _, _ = unpack_prefill(packed, blocks_per_seq)
    lengths[:], slots[:] = 1, trash_slot
    return packed


def pack_prefill(tokens, lengths, tables, slots, temp, topk) -> np.ndarray:
    """A prefill's host arguments as one fresh int32 array."""
    mb = tables.shape[1]
    packed = np.empty((tokens.shape[0], PREFILL_COLS + mb + tokens.shape[1]),
                      np.int32)
    for view, a in zip(unpack_prefill(packed, mb),
                       (tokens, lengths, tables, slots, temp, topk)):
        view[...] = a
    return packed


def _host_args(args) -> Tuple[int, int]:
    """What of a call's arguments comes from the host: (numpy arrays,
    their bytes)."""
    arrays = [a for a in jax.tree.leaves(args) if isinstance(a, np.ndarray)]
    return len(arrays), sum(a.nbytes for a in arrays)


def _program_span(name: str, program: str, step: Optional[int], **attrs):
    """A launch's or a read's span. A decode step's carries the step's
    number (``scheduler._Step``), on the event and as the metadata of its
    ``TraceAnnotation``: the pair of spans of one step is found by it."""
    if step is None:
        return span(name, program=program, **attrs)
    return span(name, annotate=("step",), program=program, step=step,
                **attrs)


def _launch(program: str, exe, *args, host: Tuple[int, int],
            step: Optional[int] = None):
    """Call a compiled executable: ``generation.dispatch`` is the
    host-to-device transfer of the call's ONE host array (``host``: the
    numpy arrays among ``args`` and their bytes, counted once an
    executable, on the span as ``host_args`` / ``host_bytes``) and the
    launch. The call returns with the results still pending; the copy of
    the FIRST one to the host is asked for at once, so that ``_read``
    finds it done."""
    with _program_span("generation.dispatch", program, step,
                       host_args=host[0], host_bytes=host[1]):
        first, *rest = exe(*args)
        first.copy_to_host_async()
    return (first, *rest)


def _read(program: str, first, step: Optional[int] = None) -> np.ndarray:
    """``generation.readback``: the ``np.asarray`` that blocks until the
    device has a launch's first result."""
    with _program_span("generation.readback", program, step):
        return np.asarray(first)


def _launch_and_read(program: str, exe, *args, host: Tuple[int, int]):
    """Launch and read the first result back, the two halves of the
    blocking ``generation.prefill`` / ``verify`` span the caller holds
    open (a ``generation.decode_step`` span holds the launch of one step
    and the read of the step before: ``launch_decode``)."""
    first, *rest = _launch(program, exe, *args, host=host)
    return (_read(program, first), *rest)


class GenerationProgramSet:
    """One model version's warmed generation executables + its params.

    Immutable after ``warm()`` — the engine swaps whole sets atomically and
    the scheduler pins each in-flight cohort to the set it was admitted
    under (the hot-swap cutover rule)."""

    def __init__(self, net, *, config: GenerationConfig,
                 adapter: str = "auto", draft_net=None,
                 trace_hook: Optional[Callable[[], None]] = None,
                 cost_path: Optional[str] = None,
                 mesh: Optional[Mesh] = None):
        self.net = net
        self.config = config
        self._trace_hook = trace_hook
        self.cost_path = cost_path    # e.g. "generation.<model>": enables
        # cost-index registration of the warmed executables (perf.py)
        self.adapter = self._resolve_adapter(net, adapter)
        self.spec = (GraphDecodeSpec(net) if self.adapter == "paged"
                     else LSTMDecodeSpec(net))
        # a model whose sequences carry more than K/V pages (a recurrent
        # mixer's per-slot state): what does not carry that state refuses
        # the model by name below, and the prefix cache is skipped
        self.stateful = self.adapter == "paged" and self.spec.stateful
        # the cache's kind: K/V pools by head, or ONE pool of latent rows
        # (what keeps keys and values by head refuses such a model by name)
        self.latent = self.adapter == "paged" and self.spec.latent
        self.n_pools = 1 if self.latent else 2
        # sliding-window layers beside those that keep the whole context:
        # their rows live in a ring a slot behind the pools (what shares or
        # re-reads a sequence's whole context refuses the model by name)
        self.windowed = self.adapter == "paged" \
            and self.spec.window is not None
        # full-context layers that read a block-sparse selection: their
        # compressed keys live a slot, behind the recurrent states (what
        # shares or re-reads whole contexts refuses the model by name)
        self.sparse = self.adapter == "paged" \
            and self.spec.selection is not None
        if self.sparse and config.block_len != self.spec.selection.block:
            raise ValueError(
                f"block_len={config.block_len}: a model with block-sparse "
                f"layers ({self.spec.sparse_names}) is served in pages of "
                f"its selection's block, {self.spec.selection.block}")
        # pools of recurrent state in the cache pytree: one a KIND of state
        self.n_rec = len(self.spec.recurrent_kinds) if self.stateful else 0
        # recurrent mixers that advance their slots' states in the pool, in
        # place (lightning attention: a state too large to copy a step)
        self.in_place_names = [
            n for n in self.spec.recurrent_names
            if hasattr(self.spec._v[n].layer_conf, "decode_step")] \
            if self.stateful else []
        # int32 counters behind the tokens of a program's first result
        self.stats_len = 2 if self.adapter == "paged" and self.spec.n_moe \
            else 0
        # sharded decode (ISSUE 20): a ``(data, model)`` mesh with m > 1
        # shards the Q/K/V/O projections and the paged KV pools by HEAD
        # across the model axis — one decode step spans chips, the
        # host-side block tables / allocator / prefix cache are untouched
        # (they index blocks, and blocks keep their ids under sharding).
        self.model_shards = model_axis_size(mesh)
        self.mesh = mesh if self.model_shards > 1 else None
        if self.model_shards > 1:
            if self.adapter != "paged":
                raise ValueError(
                    "model-sharded decode requires the paged (transformer) "
                    "adapter — the recurrent-state cache has no head axis "
                    "to split")
            if self.latent:
                raise LatentDecodeUnsupportedError(
                    "model-sharded decode is refused for a model with a "
                    f"latent cache ({self.spec.attn_names}): the pools "
                    "shard by head, and a latent row has none")
            if self.windowed:
                raise WindowDecodeUnsupportedError(
                    "model-sharded decode is refused for a model with "
                    f"sliding-window layers ({self.spec.window_names}): "
                    "their rings are not split over a mesh")
            if self.sparse:
                raise SparseDecodeUnsupportedError(
                    "model-sharded decode is refused for a model with "
                    f"block-sparse layers ({self.spec.sparse_names}): a "
                    "selection over a mesh is not built (a key-value "
                    "group's list names pages of every shard)")
            if self.in_place_names:
                raise StatefulDecodeUnsupportedError(
                    "model-sharded decode is refused for a model with "
                    f"lightning-attention layers ({self.in_place_names}): their "
                    "float32 state pool is advanced in place by one "
                    "device's kernel")
            if not self.spec.supports_head_sharding(self.model_shards):
                raise ValueError(
                    f"n_heads={self.spec.n_heads} does not divide by the "
                    f"model axis ({self.model_shards}) — the paged pools "
                    f"shard whole heads")
        self.params = jax.tree.map(jnp.asarray, net.params)
        self.state = jax.tree.map(jnp.asarray, net.state)
        if self.mesh is not None:
            self.params = shard_params(
                self.mesh, self.params,
                build_param_specs(net, self.model_shards))
            rep = NamedSharding(self.mesh, PartitionSpec())
            self.state = jax.tree.map(
                lambda a: jax.device_put(a, rep), self.state)
        self.dtype = self.spec.dtype
        self.vocab = self.spec.vocab
        # prefix-cache sharing only exists where there are blocks to share,
        # and where the blocks are ALL a sequence carries: a hit would
        # resume a stateful model's suffix from pages alone, with a
        # recurrent state nobody kept (counted, never served:
        # ``prefix_skipped_stateful``)
        self.prefix_enabled = ((self.adapter == "paged"
                                if config.prefix_cache is None
                                else bool(config.prefix_cache)
                                and self.adapter == "paged")
                               and not self.stateful and not self.windowed
                               and not self.sparse)
        # (a block-sparse layer's compressed keys are kept a slot, like a
        # state: a hit's shared pages would come without them)
        self.prefix_skipped_stateful = ((self.stateful or self.sparse)
                                        and config.prefix_cache is not False)
        # the same for sliding-window layers: a hit resumes from shared
        # pages at the matched boundary and would need the window's rows
        # as they stood THERE, which a ring does not keep
        # (``prefix_skipped_windowed``)
        self.prefix_skipped_windowed = (self.windowed
                                        and config.prefix_cache is not False)
        # int8-quantized KV tier: paged pools only (the state adapter's
        # carry is recurrent state, not a token cache)
        self.kv_quantized = config.kv_cache_dtype == "int8"
        if self.kv_quantized and self.adapter != "paged":
            raise ValueError("kv_cache_dtype='int8' requires the paged "
                             "(transformer) adapter — the state adapter "
                             "has no KV block pool to quantize")
        if self.kv_quantized and self.stateful:
            raise StatefulDecodeUnsupportedError(
                "kv_cache_dtype='int8' is refused for a model with "
                f"recurrent mixers ({self.spec.recurrent_names}): its "
                "prefill runs as a decode window, which does not carry "
                "their state")
        if self.kv_quantized and self.latent:
            raise LatentDecodeUnsupportedError(
                "kv_cache_dtype='int8' is refused for a model with a latent "
                f"cache ({self.spec.attn_names}): the int8 tier quantizes "
                "keys and values by head")
        if self.kv_quantized and self.windowed:
            raise WindowDecodeUnsupportedError(
                "kv_cache_dtype='int8' is refused for a model with "
                f"sliding-window layers ({self.spec.window_names}): its "
                "prefill runs as a decode window, which does not carry "
                "their rings")
        if self.kv_quantized and self.sparse:
            raise SparseDecodeUnsupportedError(
                "kv_cache_dtype='int8' is refused for a model with "
                f"block-sparse layers ({self.spec.sparse_names}): its "
                "prefill runs as a decode window, which makes no "
                "selection, and the selected decode reads plain pages")
        # speculative decoding: active iff a draft model is attached
        self.draft_net = draft_net
        self.spec_k = 0
        self.draft_adapter: Optional[str] = None
        self.draft_spec = None
        if draft_net is not None:
            if self.stateful:
                raise StatefulDecodeUnsupportedError(
                    "speculative decoding is refused for a model with "
                    f"recurrent mixers ({self.spec.recurrent_names}): the "
                    "verify window does not carry their state, and a "
                    "rejected proposal could not be taken back out of it")
            if self.latent:
                raise LatentDecodeUnsupportedError(
                    "speculative decoding is refused for a model with a "
                    f"latent cache ({self.spec.attn_names}): the verify "
                    "program and the dense draft cache keep K/V pairs")
            if self.windowed:
                raise WindowDecodeUnsupportedError(
                    "speculative decoding is refused for a model with "
                    f"sliding-window layers ({self.spec.window_names}): "
                    "the verify window does not carry their rings, and a "
                    "rejected proposal could not be taken back out of one")
            if self.sparse:
                raise SparseDecodeUnsupportedError(
                    "speculative decoding is refused for a model with "
                    f"block-sparse layers ({self.spec.sparse_names}): the "
                    "verify window makes no selection a row, and a "
                    "rejected proposal's compressed key could not be "
                    "taken back")
            if self.adapter != "paged":
                raise ValueError(
                    "speculative decoding requires a paged (transformer) "
                    "TARGET — the verify window runs over the block tables")
            self.spec_k = int(config.spec_k) or 4
            da = self._resolve_adapter(draft_net, "auto")
            self.draft_adapter = "dense" if da == "paged" else "state"
            self.draft_spec = (GraphDecodeSpec(draft_net)
                               if da == "paged" else LSTMDecodeSpec(draft_net))
            if da == "paged" and self.draft_spec.stateful:
                raise StatefulDecodeUnsupportedError(
                    "a draft with recurrent mixers is refused: the dense "
                    "draft cache keeps K/V alone")
            if da == "paged" and self.draft_spec.window is not None:
                raise WindowDecodeUnsupportedError(
                    "a draft with sliding-window layers is refused: the "
                    "dense draft cache keeps every layer's whole context")
            if da == "paged" and self.draft_spec.selection is not None:
                raise SparseDecodeUnsupportedError(
                    "a draft with block-sparse layers is refused: the "
                    "dense draft cache keeps no compressed keys")
            if da == "paged" and self.draft_spec.latent:
                raise LatentDecodeUnsupportedError(
                    "a draft with a latent cache is refused: the dense "
                    "draft cache keeps K/V pairs")
            if self.draft_spec.vocab != self.vocab:
                raise ValueError(
                    f"draft vocab {self.draft_spec.vocab} != target vocab "
                    f"{self.vocab} — proposals must share the token space")
            self.draft_params = jax.tree.map(jnp.asarray, draft_net.params)
            self.draft_state = jax.tree.map(jnp.asarray, draft_net.state)
            if self.mesh is not None:
                # the dense-transformer draft shards exactly like the
                # target (same head recipe); a draft whose head count
                # doesn't divide (or an LSTM draft) stays replicated —
                # GSPMD keeps it correct, just not memory-split
                self._draft_sharded = (
                    self.draft_adapter == "dense"
                    and self.draft_spec.supports_head_sharding(
                        self.model_shards))
                dspecs = (build_param_specs(draft_net, self.model_shards)
                          if self._draft_sharded else
                          jax.tree.map(lambda _: PartitionSpec(),
                                       self.draft_params))
                self.draft_params = shard_params(self.mesh,
                                                 self.draft_params, dspecs)
                rep = NamedSharding(self.mesh, PartitionSpec())
                self.draft_state = jax.tree.map(
                    lambda a: jax.device_put(a, rep), self.draft_state)
            if self.draft_adapter == "state":
                self._draft_init_states = self.draft_spec.init_states(
                    config.decode_slots + 1)
        draft_sig = None if draft_net is None else (
            _tree_signature(self.draft_params),
            _tree_signature(self.draft_state), _arch_key(draft_net),
            self.draft_adapter, self.spec_k)
        mesh_sig = None if self.mesh is None else (
            tuple(self.mesh.devices.shape), tuple(self.mesh.axis_names),
            tuple(d.id for d in self.mesh.devices.flat))
        self.signature = (_tree_signature(self.params),
                          _tree_signature(self.state), _arch_key(net),
                          self.adapter, config.block_len, config.capacity,
                          config.decode_slots, config.prefill_batches,
                          config.prompt_rungs, config.num_blocks,
                          self.prefix_enabled, config.kv_cache_dtype,
                          mesh_sig, draft_sig)
        self._compiled: Dict[Any, Any] = {}
        self.kv_pool_chip_bytes: Optional[int] = None   # set by warm()
        # (P, L) -> rows the head runs on in that prefill program, read off
        # its jaxpr by warm(); the ``generation.prefill`` span carries it
        self.head_rows: Dict[Tuple[int, int], int] = {}
        # executable -> (numpy arrays among a launch's arguments, their
        # bytes), counted at its first launch, which is warm()'s touch
        self.host_args: Dict[Any, Tuple[int, int]] = {}
        if self.adapter == "state":
            self._init_states = self.spec.init_states(config.decode_slots + 1)
        # what a decode step takes for "the step before" when there is
        # none: on the device, as a step's result would be
        self._no_prev = jnp.zeros(config.decode_slots + self.stats_len,
                                  jnp.int32)

    @staticmethod
    def _resolve_adapter(net, adapter: str) -> str:
        if adapter in ("paged", "transformer"):
            return "paged"
        if adapter in ("state", "lstm"):
            return "state"
        if adapter != "auto":
            raise ValueError(f"unknown adapter {adapter!r}")
        # a ComputationGraph with attention layers vs a MultiLayerNetwork
        # recurrent stack: by the kind of the layers, not by their names
        from ...nn.layers import LatentAttentionLayer, SelfAttentionLayer
        if hasattr(net, "vertex_names") and any(
                isinstance(getattr(v, "layer_conf", None),
                           (SelfAttentionLayer, LatentAttentionLayer))
                for v in net.vertices):
            return "paged"
        return "state"

    # ---------------------------------------------------------------- cache
    def _pool_sharding(self) -> Optional[NamedSharding]:
        """Head-axis sharding for the paged pools: every pool-shaped array
        in the decode subsystem carries its heads on axis 3 —
        k/v pools [n_layers, nb, blk, H*Dh] (heads side by side, so an
        even split of the axis is a split by head), int8 codes
        [n_layers, nb, blk, H, Dh] and scales [n_layers, nb, blk, H],
        dense draft caches [n_layers, slots+1, cap, H, Dh] — so ONE spec
        serves them all (PartitionSpec pads trailing axes with None)."""
        if self.mesh is None:
            return None
        return NamedSharding(self.mesh,
                             PartitionSpec(None, None, None, MODEL_AXIS))

    def make_cache(self):
        """Fresh cache pytree: (k_pool, v_pool) for the paged adapter —
        pools for the model's attention layers and key-value heads, or the
        one pool ``(pool,)`` of a latent cache — with
        the recurrent mixers' per-slot state behind them where the model
        has any; the zeroed recurrent-state carry (decode_slots + 1 rows,
        last row is the prefill-padding trash slot) for the state
        adapter."""
        c = self.config
        if self.adapter == "paged":
            cache = make_pools(self.spec.n_blocks, c.num_blocks,
                               c.block_len, self.spec.kv_heads,
                               self.spec.head_dim, self.dtype,
                               quantized=self.kv_quantized,
                               latent=self.latent)
            sh = self._pool_sharding()
            if sh is not None:
                cache = jax.tree.map(lambda a: jax.device_put(a, sh), cache)
            if self.stateful:
                # [recurrent layers of a kind, slots + 1, ...] a KIND of
                # state, each in its own dtype: donated and updated by the
                # same programs as the pools
                cache = cache + tuple(
                    jnp.zeros(shape, dtype) for shape, dtype in
                    self.spec.recurrent_state_specs(c.decode_slots + 1))
            if self.sparse:
                # the block-sparse layers' compressed keys, a slot
                cache = cache + (make_compressed(
                    len(self.spec.sparse_names), c.decode_slots, c.capacity,
                    self.spec.selection.stride, self.spec.kv_heads,
                    self.spec.head_dim, self.dtype),)
            if self.windowed:
                # a ring a slot for the sliding-window layers, K and V,
                # last in the pytree
                cache = cache + make_rings(
                    self.spec.n_window_layers, c.decode_slots,
                    self.spec.window, c.block_len, self.spec.kv_heads,
                    self.spec.head_dim, self.dtype)
        else:
            cache = jax.tree.map(jnp.zeros_like, self._init_states)
        try:     # memprof owner hint: the block pool dominates live HBM
            from ...telemetry import memprof
            memprof.tag(cache, (self.cost_path or "generation")
                        + ".kvcache")
        except Exception:       # pragma: no cover - defensive
            pass
        return cache

    def fresh_key(self):
        return jax.random.PRNGKey(self.config.seed)

    def kv_bytes_per_token(self) -> Optional[float]:
        """Block-pool device bytes per token SLOT (K + V, all layers/
        heads; a latent cache's one row a layer, as laid out) — the
        capacity-per-byte currency the quantized tier
        moves; published as ``generation.<m>.kv_bytes_per_token``.
        None for the state adapter (no token-addressed pool)."""
        if self.adapter != "paged":
            return None
        s = self.spec
        if self.kv_quantized:
            per_head = s.head_dim * 1 + 4          # int8 codes + f32 scale
        else:
            per_head = s.head_dim * jnp.dtype(self.dtype).itemsize
        return float(self.n_pools * s.n_blocks * s.kv_heads * per_head)

    def cache_row_bytes(self) -> Optional[int]:
        """Bytes of ONE layer's cache row of one token as laid out (every
        pool): what a decode step reads per live row and layer."""
        per_token = self.kv_bytes_per_token()
        return None if per_token is None else int(per_token
                                                  // self.spec.n_blocks)

    def window_cache_bytes_per_slot(self) -> int:
        """Device bytes one decode slot's rings hold (K and V, every
        sliding-window layer): ``window`` rows and a page a layer whatever
        the sequence's length; 0 for a model without such layers."""
        if not self.windowed:
            return 0
        s, c = self.spec, self.config
        return int(2 * s.n_window_layers * ring_pages(s.window, c.block_len)
                   * c.block_len * s.kv_heads * s.head_dim
                   * jnp.dtype(self.dtype).itemsize)

    def recurrent_state_bytes(self) -> int:
        """Device bytes of the recurrent mixers' per-slot state (every
        slot, the trash row included); 0 for a model that keeps K/V
        alone."""
        if not self.stateful:
            return 0
        return sum(int(math.prod(shape)) * jnp.dtype(dtype).itemsize
                   for shape, dtype in self.spec.recurrent_state_specs(
                       self.config.decode_slots + 1))

    def state_bytes_per_slot(self) -> int:
        """Device bytes ONE decode slot's recurrent states hold, every
        kind in its own dtype (a lightning-attention layer's float32
        matrix a head; a short convolution's rows)."""
        return self.recurrent_state_bytes() // (self.config.decode_slots + 1)

    def index_bytes_per_token(self) -> float:
        """Device bytes of compressed keys a token of context costs, every
        block-sparse layer (one row of the key-value heads every
        ``stride`` tokens); 0 for a model that selects nothing."""
        if not self.sparse:
            return 0.0
        s = self.spec
        return (len(s.sparse_names) * s.kv_heads * s.head_dim
                * jnp.dtype(self.dtype).itemsize / s.selection.stride)

    def split_stats(self, first):
        """A program's first result as read back -> (tokens, counters):
        the counters are the int32s a model with expert layers appends
        (fullest expert's pairs, experts touched), else None."""
        if not self.stats_len:
            return first, None
        return first[:-self.stats_len], first[-self.stats_len:]

    def make_draft_cache(self):
        """Fresh draft cache: dense per-slot K/V for a transformer draft,
        zeroed recurrent states (slots + 1 rows) for an LSTM draft; None
        when speculation is off."""
        if self.draft_adapter is None:
            return None
        from .speculative import make_dense_draft_cache
        if self.draft_adapter == "dense":
            dcache = make_dense_draft_cache(self.draft_spec,
                                            self.config.decode_slots,
                                            self.config.capacity)
            sh = self._pool_sharding()
            if sh is not None and self._draft_sharded:
                dcache = jax.tree.map(lambda a: jax.device_put(a, sh),
                                      dcache)
            return dcache
        return jax.tree.map(jnp.zeros_like, self._draft_init_states)

    def kv_pool_bytes_per_chip(self, cache=None) -> int:
        """Device bytes of the block pool resident on ONE chip — the
        m×-reduction number the sharded-decode tier is bought for
        (``generation.<m>.kv_pool_bytes_per_chip``). With no mesh this is
        simply the full pool size."""
        return per_replica_bytes(cache if cache is not None
                                 else self.make_cache())

    # ------------------------------------------------------------- programs
    def _prefill_fn(self):
        spec, mb = self.spec, self.config.blocks_per_seq

        def fn(params, state, cache, packed, key):
            if self._trace_hook is not None:
                self._trace_hook()
            tokens, lengths, tables, slots, temp, topk = unpack_prefill(
                packed, mb)
            if self.adapter == "paged":
                pools = cache[:self.n_pools]
                # the head runs on the one row of each prompt that is
                # sampled from, selected before it: [P, d], not [P, L, d]
                rows = lengths - 1
                states = []
                if self.kv_quantized:
                    # int8 tier: compute the prefill logits through FAKE-
                    # QUANTIZED attention (QuantSimStore) so the first
                    # sampled token matches what a decode-step replay of
                    # the same prompt would produce — the prefix-cache
                    # hit path replays the unmatched suffix through the
                    # decode program, and both must see identical K/V
                    store = QuantSimStore(spec.n_blocks)
                    live = (jnp.arange(tokens.shape[1])[None, :]
                            < lengths[:, None]) if self.stats_len else None
                    hidden, stats = spec.window_hidden_stats(
                        params, state, tokens,
                        jnp.zeros((tokens.shape[0],), jnp.int32), store, live)
                    last = spec.logits_at(params, hidden, rows)
                    ks, vs = store.ks, store.vs
                else:
                    # with ``lengths`` the recurrent mixers' states come
                    # back at each prompt's TRUE length and the expert
                    # layers' counters over the live rows; a model that
                    # has neither asks for neither
                    last, ks, vs, states, stats = spec.prefill_full(
                        params, state, tokens, rows,
                        lengths if self.stateful or self.stats_len else None)
                if self.sparse:
                    crows = spec.compressed_rows(ks)
                if self.windowed:
                    # the layers that keep the whole context go to the
                    # pages, the sliding-window layers to the rings
                    (ks, wks), (vs, wvs) = (spec.split_kinds(ks),
                                            spec.split_kinds(vs))
                # K and V, or a latent cache's one pool of rows (``ks``)
                out = tuple(prefill_scatter(pool, kv, tables)
                            for pool, kv in zip(pools, (ks, vs)))
                if self.stateful:
                    # beside the pools, in the slots' rows (padding rows
                    # carry slot S: the trash row), a kind of state in
                    # its own pool
                    out += tuple(
                        rec.at[:, slots].set(new.astype(rec.dtype))
                        for rec, new in zip(
                            cache[self.n_pools:self.n_pools + self.n_rec],
                            spec.states_by_kind(states)))
                if self.sparse:
                    # the block-sparse layers' compressed keys, from the
                    # prompt's keys as the pages keep them
                    out += (compressed_prefill_fill(
                        cache[self.n_pools + self.n_rec], crows, slots),)
                if self.windowed:
                    # each prompt's last rows at its TRUE length, in its
                    # slot's rings (padding rows: the trash ring)
                    out += tuple(ring_prefill_fill(ring, kv, lengths, slots)
                                 for ring, kv in zip(cache[-2:], (wks, wvs)))
                tok, key = sample_tokens(last, key, temp, topk)
                if stats is not None:
                    # the counters ride back behind the tokens
                    tok = jnp.concatenate([tok, stats])
                return tok, out, key
            P = tokens.shape[0]
            zero = jax.tree.map(
                lambda c: jnp.zeros((P,) + c.shape[1:], c.dtype), cache)
            logits, final = spec.prefill_scan(params, state, tokens, lengths,
                                              zero)
            cache = jax.tree.map(lambda c, n: c.at[slots].set(n), cache,
                                 final)
            tok, key = sample_tokens(logits, key, temp, topk)
            return tok, cache, key
        return fn

    def _decode_fn(self):
        spec, blk = self.spec, self.config.block_len

        def fn(params, state, cache, packed, prev, key):
            if self._trace_hook is not None:
                self._trace_hook()
            tokens, host_known, pos, tables, active, temp, topk = \
                unpack_decode(packed)
            # a row's token: the host's where the host knows it (a slot's
            # first token after its prefill, a replayed prompt token), else
            # what the step before sampled, never read by the host before
            # this launch (its counters ride behind the tokens)
            tokens = jnp.where(host_known, tokens, prev[:tokens.shape[0]])
            if self.adapter == "paged":
                store = PagedStore(
                    cache[0], None if self.latent else cache[1], tables, pos,
                    active, blk,
                    cache[self.n_pools:self.n_pools + self.n_rec]
                    if self.stateful else None,
                    cache[-2:] if self.windowed else None,
                    cache[self.n_pools + self.n_rec] if self.sparse
                    else None)
                logits, stats = spec.decode_step_stats(
                    params, state, tokens, pos, store,
                    active if self.stats_len else None)
                tok, key = sample_tokens(logits, key, temp, topk)
                if stats is not None:
                    # in the tokens' own type: the next step takes this
                    # array back as ``prev``
                    tok = jnp.concatenate([tok, stats.astype(tok.dtype)])
                return tok, store.cache, key
            S = tokens.shape[0]
            cur = jax.tree.map(lambda c: c[:S], cache)
            logits, new = spec.decode_step(params, state, tokens, cur)

            def merge(c, n):
                keep = active.reshape((S,) + (1,) * (n.ndim - 1))
                return jnp.concatenate(
                    [jnp.where(keep, n, c[:S]), c[S:]], axis=0)
            cache = jax.tree.map(merge, cache, new)
            tok, key = sample_tokens(logits, key, temp, topk)
            return tok, cache, key
        return fn

    def _sds(self, a):
        # under a mesh the cache argument's layout is part of the AOT
        # contract: lowering against the sharded spec is what compiles the
        # one cross-chip decode step (and what keeps re-dispatch from
        # recompiling — the runtime pools carry the same sharding)
        if self.mesh is not None and hasattr(a, "sharding") \
                and isinstance(a.sharding, NamedSharding):
            return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                        sharding=a.sharding)
        return jax.ShapeDtypeStruct(a.shape, a.dtype)

    def _cache_spec(self):
        return jax.tree.map(self._sds, self.make_cache())

    def _draft_cache_spec(self):
        return jax.tree.map(self._sds, self.make_draft_cache())

    def _key_spec(self):
        k = self.fresh_key()
        return jax.ShapeDtypeStruct(k.shape, k.dtype)

    def _prefill_avals(self, P: int, L: int):
        """What a (P, L) prefill program takes behind (params, state,
        cache): the packed host array and the key."""
        return (jax.ShapeDtypeStruct(
            (P, PREFILL_COLS + self.config.blocks_per_seq + L), jnp.int32),
            self._key_spec())

    def _decode_avals(self):
        """The same for the decode step: the packed host array, the step
        before's result and the key."""
        c = self.config
        return (jax.ShapeDtypeStruct(
            (c.decode_slots, DECODE_COLS + c.blocks_per_seq), jnp.int32),
            jax.ShapeDtypeStruct((c.decode_slots + self.stats_len,),
                                 jnp.int32),
            self._key_spec())

    def _cow_fn(self):
        n = self.n_pools

        def fn(cache, src, dst):
            if self._trace_hook is not None:
                self._trace_hook()
            return cow_copy(cache[:n], src, dst) + tuple(cache[n:])
        return fn

    def _spec_fns(self):
        """(draft_prefill, propose, rewind_or_None, verify) builders."""
        from . import speculative as sp
        tgt, blk, k = self.spec, self.config.block_len, self.spec_k
        hook = self._trace_hook

        def hooked(f):
            def g(*a):
                if hook is not None:
                    hook()
                return f(*a)
            return g

        verify = hooked(sp.verify_fn(tgt, blk, k))
        if self.draft_adapter == "dense":
            return (hooked(sp.draft_prefill_dense_fn(self.draft_spec)),
                    hooked(sp.propose_dense_fn(self.draft_spec, k)),
                    None, verify)
        return (hooked(sp.draft_prefill_state_fn(self.draft_spec)),
                hooked(sp.propose_state_fn(self.draft_spec, k)),
                hooked(sp.rewind_state_fn()), verify)

    # --------------------------------------------------------------- warm-up
    def _traced(self, fn, donate: Tuple[int, ...], *avals):
        """Trace one program under the mesh it will run over so layer code
        can see it (a Pallas kernel must split itself per device under a
        multi-device jit — ops/pallas_attention.py). The context wraps the
        tracing ONLY: it is part of every jit cache key, and the small
        eager programs of warm-up (pool zeros, shard placement) must be
        the ones first traffic reuses."""
        with (jax.sharding.use_abstract_mesh(self.mesh.abstract_mesh)
              if self.mesh is not None else contextlib.nullcontext()):
            return jax.jit(fn, donate_argnums=donate).trace(*avals)

    def _aot(self, fn, donate: Tuple[int, ...], *avals):
        """AOT-compile one program."""
        return self._traced(fn, donate, *avals).lower().compile()

    def warm(self) -> "GenerationProgramSet":
        """Compile every prefill rung and the decode step; touch each once
        so first traffic pays no one-time dispatch setup. NEVER called on
        the decode hot path."""
        c = self.config
        i32 = jnp.int32
        cache_spec = self._cache_spec()
        mb = c.blocks_per_seq
        prefill = self._prefill_fn()
        decode = self._decode_fn()
        for P in c.prefill_batches:
            for L in c.prompt_rungs:
                traced = self._traced(
                    prefill, _DONATE_CACHE, self.params, self.state,
                    cache_spec, *self._prefill_avals(P, L))
                self.head_rows[(P, L)] = _head_rows(traced.jaxpr.jaxpr,
                                                    self.spec.vocab)
                self._compiled[("prefill", P, L)] = traced.lower().compile()
        S = c.decode_slots
        self._compiled[("decode",)] = self._aot(
            decode, _DONATE_CACHE, self.params, self.state, cache_spec,
            *self._decode_avals())
        if self.prefix_enabled:
            # the copy-on-write block copy: src/dst are runtime scalars, so
            # ONE executable serves every copy
            self._compiled[("cow",)] = self._aot(
                self._cow_fn(), (0,), cache_spec,
                jax.ShapeDtypeStruct((), i32), jax.ShapeDtypeStruct((), i32))
        if self.spec_k:
            self._warm_spec(cache_spec, i32)
        # one touch per executable: first real traffic must not pay
        # dispatch-setup either
        cache, key = self.make_cache(), self.fresh_key()
        self.kv_pool_chip_bytes = self.kv_pool_bytes_per_chip(cache)
        for P in c.prefill_batches:
            for L in c.prompt_rungs:
                _, cache, key = self.run_prefill(
                    cache, padding_prefill(P, L, mb, S), key)
        _, cache, key = self.run_decode(
            cache, np.zeros((S,), np.int32), np.zeros((S,), np.int32),
            np.zeros((S, mb), np.int32), np.zeros((S,), np.bool_), key,
            np.zeros((S,), np.float32), np.zeros((S,), np.int32))
        if self.prefix_enabled:
            cache = self.run_cow(cache, 0, 0)
        if self.spec_k:
            cache = self._touch_spec(cache)
        self._register_costs()
        return self

    def _register_costs(self) -> None:
        """Cost-model accounting (telemetry/perf.py): register every
        warmed executable's cost analysis keyed by program. The decode
        step and verify window pair with the per-step latency histograms
        the scheduler already observes (``decode_step_ms`` /
        ``verify_step_ms``), so the perf fold yields live MFU/roofline
        gauges for the decode loop; prefill rungs register cost-only
        (roofline classification without a paired timing stream). Never
        raises into warm-up."""
        if self.cost_path is None:
            return
        try:
            from ...telemetry import get_registry
            from ...telemetry.perf import (accounting_enabled,
                                           get_cost_index)
            if not (accounting_enabled() and get_registry().enabled):
                return
            idx = get_cost_index()
            base = self.cost_path
            m = self.model_shards        # per-chip share of a tp program
            idx.register(f"{base}.decode_step",
                         program=self._compiled[("decode",)],
                         items_per_step=float(self.config.decode_slots),
                         model_axis_size=m,
                         timing_metric=f"{base}.decode_step_ms")
            if ("verify",) in self._compiled:
                idx.register(f"{base}.verify",
                             program=self._compiled[("verify",)],
                             items_per_step=float(self.config.decode_slots),
                             model_axis_size=m,
                             timing_metric=f"{base}.verify_step_ms")
            for key, compiled in self._compiled.items():
                if key[0] == "prefill":
                    _, P, L = key
                    idx.register(f"{base}.prefill.b{P}xp{L}",
                                 program=compiled, items_per_step=float(P),
                                 model_axis_size=m)
        except Exception:       # pragma: no cover - defensive
            pass

    def _warm_spec(self, cache_spec, i32):
        """Compile the draft + verify executables (speculative decoding).
        Cache-carrying programs donate their cache argument, exactly like
        the decode step — the pools update in place."""
        c = self.config
        S, mb, k = c.decode_slots, c.blocks_per_seq, self.spec_k
        dcache_spec = self._draft_cache_spec()
        d_prefill, propose, rewind, verify = self._spec_fns()
        sds = jax.ShapeDtypeStruct
        for P in c.prefill_batches:
            for L in c.prompt_rungs:
                if self.draft_adapter == "dense":
                    self._compiled[("draft_prefill", P, L)] = self._aot(
                        d_prefill, _DONATE_CACHE,
                        self.draft_params, self.draft_state, dcache_spec,
                        sds((P, L), i32), sds((P,), i32))
                else:
                    self._compiled[("draft_prefill", P, L)] = self._aot(
                        d_prefill, _DONATE_CACHE,
                        self.draft_params, self.draft_state, dcache_spec,
                        sds((P, L), i32), sds((P,), i32), sds((P,), i32))
        if self.draft_adapter == "dense":
            self._compiled[("propose",)] = self._aot(
                propose, _DONATE_CACHE,
                self.draft_params, self.draft_state, dcache_spec,
                sds((S,), i32), sds((S,), i32), sds((S,), jnp.bool_))
        else:
            # the state propose RETURNS its input states untouched inside
            # the stack; no donation (the scheduler still needs states_all
            # until rewind commits)
            self._compiled[("propose",)] = self._aot(
                propose, (), self.draft_params, self.draft_state,
                dcache_spec, sds((S,), i32))
            stack_spec = jax.tree.map(
                lambda a: sds((k + 1, S) + a.shape[1:], a.dtype),
                dcache_spec)
            self._compiled[("rewind",)] = self._aot(
                rewind, (0,), dcache_spec, stack_spec, sds((S,), i32),
                sds((S,), jnp.bool_))
        self._compiled[("verify",)] = self._aot(
            verify, _DONATE_CACHE,
            self.params, self.state, cache_spec, sds((S, k + 1), i32),
            sds((S,), i32), sds((S, mb), i32), sds((S,), jnp.bool_))

    def _touch_spec(self, cache):
        c = self.config
        S, mb, k = c.decode_slots, c.blocks_per_seq, self.spec_k
        zS = np.zeros((S,), np.int32)
        dcache = self.make_draft_cache()
        for P in c.prefill_batches:
            for L in c.prompt_rungs:
                dcache = self.run_draft_prefill(
                    dcache, np.zeros((P, L), np.int32),
                    np.ones((P,), np.int32), np.full((P,), S, np.int32))
        out = self.run_propose(dcache, zS, zS, np.zeros((S,), np.bool_))
        if self.draft_adapter == "dense":
            _, dcache = out
        else:
            _, stack = out
            dcache = self.run_rewind(dcache, stack, np.ones((S,), np.int32),
                                     np.zeros((S,), np.bool_))
        _, cache = self.run_verify(cache, np.zeros((S, k + 1), np.int32),
                                   zS, np.zeros((S, mb), np.int32),
                                   np.zeros((S,), np.bool_))
        return cache

    @property
    def warmed(self) -> bool:
        c = self.config
        want = {("prefill", P, L) for P in c.prefill_batches
                for L in c.prompt_rungs} | {("decode",)}
        if self.prefix_enabled:
            want |= {("cow",)}
        if self.spec_k:
            want |= {("draft_prefill", P, L) for P in c.prefill_batches
                     for L in c.prompt_rungs} | {("propose",), ("verify",)}
            if self.draft_adapter == "state":
                want |= {("rewind",)}
        return want <= set(self._compiled)

    # ---------------------------------------------------------------- running
    def _host_of(self, which, *args) -> Tuple[int, int]:
        """``_host_args`` of executable ``which``'s launches, counted at
        its first one."""
        host = self.host_args.get(which)
        if host is None:
            host = self.host_args[which] = _host_args(args)
        return host

    def run_prefill(self, cache, packed, key):
        """One blocking prefill of ``packed`` (``pack_prefill``: the (P,
        L) program is read off its shape). Returns (first_tokens np [P],
        cache', key'); a model with expert layers appends its counters to
        the tokens (``split_stats``)."""
        P = packed.shape[0]
        L = packed.shape[1] - PREFILL_COLS - self.config.blocks_per_seq
        exe = self._compiled.get(("prefill", P, L))
        if exe is None:
            from ..errors import ServingError
            raise ServingError(
                f"no warmed prefill program for (batch={P}, rung={L}) — "
                f"call warm() before serving (warmed: "
                f"{sorted(k for k in self._compiled if k[0] == 'prefill')})")
        args = (self.params, self.state, cache, packed, key)
        return _launch_and_read("prefill", exe, *args,
                                host=self._host_of(("prefill", P, L), *args))

    def launch_decode(self, cache, packed, prev, key,
                      step: Optional[int] = None):
        """Launch one decode step on ``packed`` (``pack_decode``) and
        return (next_tokens ON THE DEVICE [S + stats_len], cache', key')
        without waiting for it. A row's token is the packed one where the
        row is ``host_known``, else the row of ``prev``: the step before's
        first result as it left the device (None: every row is the
        host's). ``read_decode`` reads the tokens back. ``step`` is the
        loop's number for the step: it rides the launch's span and the
        read's."""
        if prev is None:
            prev = self._no_prev
        args = (self.params, self.state, cache, packed, prev, key)
        return _launch("decode", self._exe(("decode",)), *args, step=step,
                       host=self._host_of(("decode",), *args))

    @staticmethod
    def read_decode(first, step: Optional[int] = None) -> np.ndarray:
        """Block until a launched step's tokens (and counters,
        ``split_stats``) are on the host."""
        return _read("decode", first, step)

    def run_decode(self, cache, tokens, pos, tables, active, key, temp,
                   topk):
        """One blocking decode step on the host's tokens. Returns
        (next_tokens np [S], cache', key'); a model with expert layers
        appends its counters to the tokens (``split_stats``)."""
        first, cache, key = self.launch_decode(
            cache, pack_decode(tokens, np.ones(tokens.shape, np.bool_), pos,
                               tables, active, temp, topk), None, key)
        return self.read_decode(first), cache, key

    def _exe(self, key):
        exe = self._compiled.get(key)
        if exe is None:
            from ..errors import ServingError
            raise ServingError(f"no warmed {key} program — call warm() "
                               "before serving")
        return exe

    # --------------------------------------------- prefix-cache programs
    def run_cow(self, cache, src: int, dst: int):
        """Copy block ``src`` -> ``dst`` in both pools (copy-on-write)."""
        return self._exe(("cow",))(cache, np.int32(src), np.int32(dst))

    # --------------------------------------------- speculative programs
    def run_draft_prefill(self, dcache, tokens, lengths, slots):
        """Draft consumes the FULL prompt (cache-hit admissions included:
        the draft is cheap — that is the point). Returns the draft cache."""
        P, L = tokens.shape
        exe = self._exe(("draft_prefill", P, L))
        if self.draft_adapter == "dense":
            return exe(self.draft_params, self.draft_state, dcache, tokens,
                       slots)
        return exe(self.draft_params, self.draft_state, dcache, tokens,
                   lengths, slots)

    def run_propose(self, dcache, cur, pos, active):
        """Returns (proposals np [S,k], dcache') for the dense draft, or
        (proposals np [S,k], states_stack) for the state draft (the caller
        commits the stack through run_rewind after verify)."""
        exe = self._exe(("propose",))
        if self.draft_adapter == "dense":
            props, dcache = exe(self.draft_params, self.draft_state, dcache,
                                cur, pos, active)
            return np.asarray(props), dcache
        props, stack = exe(self.draft_params, self.draft_state, dcache, cur)
        return np.asarray(props), stack

    def run_rewind(self, dcache, stack, idx, mask):
        """State-draft only: commit, per slot, the stacked state matching
        what verify accepted (masked slots keep their state)."""
        return self._exe(("rewind",))(dcache, stack, idx, mask)

    def run_verify(self, cache, feeds, pos, tables, active):
        """One batched target pass over [S, k+1] fed tokens. Returns
        (greedy targets np [S,k+1], cache')."""
        args = (self.params, self.state, cache, feeds, pos, tables, active)
        return _launch_and_read("verify", self._exe(("verify",)), *args,
                                host=self._host_of(("verify",), *args))

    # --------------------------------------------------------------- hot-swap
    def with_params_from(self, net, draft_net=None) -> "GenerationProgramSet":
        """Same-architecture swap: new set sharing THIS set's executables.
        The draft model (when speculating) carries over unless a new one is
        given. Raises ValueError when the signature changed (caller warms a
        fresh set before cutover)."""
        new = GenerationProgramSet(net, config=self.config,
                                   adapter=self.adapter,
                                   draft_net=draft_net or self.draft_net,
                                   trace_hook=self._trace_hook,
                                   cost_path=self.cost_path,
                                   mesh=self.mesh)
        if new.signature != self.signature:
            raise ValueError("parameter/architecture changed; full warm-up "
                             "required")
        new._compiled = self._compiled
        new.kv_pool_chip_bytes = self.kv_pool_chip_bytes
        new.head_rows = self.head_rows
        new.host_args = self.host_args
        return new
