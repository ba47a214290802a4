"""serving/generation — autoregressive decode subsystem.

Paged-KV-cache incremental decode with continuous batching and per-token
streaming (ROADMAP open item 4): the serving engine's
precompiled-fixed-shape-program discipline (cuDNN's shape-specialized
primitives, arXiv:1410.0759, applied to whole XLA programs) extended to
generation, where the working set GROWS per token. The trick is vLLM-style
paging: the KV cache is a fixed block pool + per-sequence block tables, so
context growth is block allocation — no array ever changes shape, nothing
ever recompiles after warm-up.

Pillars:
  - kvcache.py      block pool, refcounted free-list allocator,
                    scatter, the PagedStore / PagedWindowStore bridges
                    into models/decode.py (attention reads the pages in
                    place: ops/pallas_paged_attention.py), the COW block
                    copy
  - prefix.py       copy-on-write prefix-cache sharing: rolling
                    prompt-prefix hash chain over immutable full blocks,
                    refcounts + LRU + eviction under pool pressure
  - speculative.py  draft-propose k tokens / one batched target verify:
                    dense (truncated transformer) + state (LSTM) draft
                    adapters, exact greedy acceptance rule
  - programs.py     GenerationConfig + AOT-warmed prefill (bucketed),
                    decode-step, cow, draft-prefill/propose/rewind and
                    verify executables, buffer-donated cache, jit-carried
                    PRNG
  - sampling.py     greedy / temperature / top-k, in-program: argmax
                    alone for a batch of greedy rows; the draw, and a
                    top-k threshold selected exactly without a sort,
                    only when a row has a temperature
  - scheduler.py    continuous batching: step-boundary admission (prefix
                    matched, suffix replayed), slot backfill, verify-step
                    interleave, TokenStream per request, cohort-pinned
                    hot-swap, armed RecompileDetector, block-accounting
                    quiesce invariant
  - metrics.py      TTFT (uncached AND cached), decode/verify latency,
                    tokens/sec, slot occupancy, block-pool economics
                    (shared/COW/LRU/evictions), accepted-per-verify ->
                    GET /metrics + telemetry registry
  - engine.py       GenerationEngine facade (multi-model, hot-swap, drain)

Model math lives in models/decode.py (TransformerDecodeSpec /
LSTMDecodeSpec + decode_window + the naive_generate bit-exactness
reference); the HTTP streaming surface is serving/http.py
(POST /generate).
"""
from .engine import GenerationEngine
from .kvcache import (BlockAllocator, PagedStore, PagedWindowStore,
                      cow_copy, make_pools)
from .metrics import GenerationMetrics
from .prefix import PrefixCache
from .programs import GenerationConfig, GenerationProgramSet
from .sampling import sample_tokens
from .scheduler import ModelRuntime, TokenStream
from .speculative import DenseDraftStore, accept_greedy

__all__ = [
    "GenerationEngine", "GenerationConfig", "GenerationProgramSet",
    "GenerationMetrics", "ModelRuntime", "TokenStream", "BlockAllocator",
    "PagedStore", "PagedWindowStore", "PrefixCache", "DenseDraftStore",
    "accept_greedy", "cow_copy", "make_pools", "sample_tokens",
]
