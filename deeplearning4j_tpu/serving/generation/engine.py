"""GenerationEngine: the autoregressive-serving facade.

The decode-side sibling of ``serving.InferenceEngine``: multi-model
registry, AOT warm-up, continuous-batching scheduling (ModelRuntime per
model), per-token streaming, zero-downtime hot-swap with the
finish-on-old-params cutover rule, drain-then-stop lifecycle.

    eng = GenerationEngine(net, model_name="lm",
                           block_len=16, max_seq_len=128, decode_slots=8)
    tokens, reason = eng.generate([5, 7, 11], max_tokens=32)
    for tok in eng.generate([5, 7, 11], max_tokens=32, stream=True):
        ...                     # per-token, TTFT = one prefill away

Serve it over HTTP by passing ``generation=eng`` to
``serving.ServingHTTPServer`` (POST /generate streams NDJSON chunks).
"""
from __future__ import annotations

import threading
from typing import Dict, Optional, Sequence, Tuple, Union

from ..errors import DrainingError, UnknownModelError
from ..registry import load_net
from .metrics import GenerationMetrics
from .programs import GenerationConfig, GenerationProgramSet
from .scheduler import ModelRuntime, TokenStream


class GenerationEngine:
    def __init__(self, net=None, *, model_name: str = "default",
                 config: Optional[GenerationConfig] = None,
                 adapter: str = "auto", warm: bool = True,
                 watch_recompiles: bool = True, draft=None, mesh=None,
                 **config_kwargs):
        self._models: Dict[str, ModelRuntime] = {}
        self._default: Optional[str] = None
        self._lock = threading.Lock()
        self._draining = False
        self._trace_count = 0
        self._watch = watch_recompiles
        self._mesh = mesh          # default (data, model) mesh for add_model
        if net is not None:
            self.add_model(model_name, net, config=config, adapter=adapter,
                           warm=warm, default=True, draft=draft, mesh=mesh,
                           **config_kwargs)

    # ------------------------------------------------------------------ models
    def add_model(self, name: str, net, *,
                  config: Optional[GenerationConfig] = None,
                  adapter: str = "auto", warm: bool = True,
                  default: bool = False, draft=None, mesh=None,
                  **config_kwargs) -> ModelRuntime:
        """Register a generation model. Per-model opt-ins (ISSUE 14):
        ``draft=`` attaches a speculative-decoding draft model (the
        config's ``spec_k`` proposals per verify window, default 4);
        ``prefix_cache=`` (config/kwarg) disables or forces prompt-prefix
        KV sharing (default: on for paged-transformer models);
        ``mesh=`` (ISSUE 20) a ``(data, model)`` mesh whose model axis
        shards the projections and KV pools by head across chips
        (defaults to the engine-level mesh)."""
        with self._lock:
            if name in self._models:
                raise ValueError(f"generation model '{name}' already "
                                 "registered (use hot_swap to replace)")
        cfg = config or GenerationConfig(**config_kwargs)
        self._pause_detectors()
        try:
            ps = GenerationProgramSet(net, config=cfg, adapter=adapter,
                                      draft_net=draft,
                                      trace_hook=self._on_trace,
                                      cost_path=f"generation.{name}",
                                      mesh=mesh or self._mesh)
            if warm:
                ps.warm()
        finally:
            self._resume_detectors()
        rt = ModelRuntime(name, ps, GenerationMetrics(name=name),
                          watch_recompiles=self._watch)
        with self._lock:
            if name in self._models:      # lost a registration race
                rt.stop(drain=False, timeout=1.0)
                raise ValueError(f"generation model '{name}' already "
                                 "registered")
            self._models[name] = rt
            if default or self._default is None:
                self._default = name
        return rt

    def remove_model(self, name: str) -> None:
        rt = self._get(name)
        with self._lock:
            self._models.pop(name, None)
            if self._default == name:
                self._default = next(iter(self._models), None)
        rt.stop(drain=True)

    def _get(self, name: Optional[str]) -> ModelRuntime:
        with self._lock:
            key = name or self._default
            if key is None or key not in self._models:
                raise UnknownModelError(
                    f"no generation model {key!r} (registered: "
                    f"{sorted(self._models)})")
            return self._models[key]

    def names(self):
        with self._lock:
            return sorted(self._models)

    @property
    def default_name(self) -> Optional[str]:
        return self._default

    # ------------------------------------------------------------- generation
    def generate(self, prompt, *, model: Optional[str] = None,
                 max_tokens: Optional[int] = None,
                 temperature: float = 0.0, top_k: int = 0,
                 stop: Sequence[int] = (),
                 timeout: Optional[float] = None, stream: bool = False,
                 speculative: bool = True
                 ) -> Union[TokenStream, Tuple[list, str]]:
        """Generate up to ``max_tokens`` tokens after ``prompt`` (a 1-D int
        token-id sequence). ``stream=True`` returns a TokenStream to
        iterate; otherwise blocks and returns (tokens, finish_reason).
        ``temperature<=0`` is greedy; ``top_k<=0`` disables the top-k cut;
        ``stop`` token ids terminate generation (not emitted);
        ``speculative=False`` opts this request out of draft-verify decode
        on a speculating model (sampling requests opt out automatically —
        the exact-output guarantee is greedy-only)."""
        if self._draining:
            raise DrainingError("generation engine is draining")
        rt = self._get(model)
        ts = rt.submit(prompt,
                       max_new=(max_tokens if max_tokens is not None
                                else rt.config.default_max_tokens),
                       temperature=temperature, top_k=top_k, stop=stop,
                       timeout=timeout, speculative=speculative)
        if stream:
            return ts
        return ts.result()

    # --------------------------------------------------------------- hot-swap
    def hot_swap(self, name: str, net_or_path, draft=None) -> int:
        """Replace model ``name`` with zero downtime. Cutover rule:
        generations in flight at swap time FINISH on the old params AND the
        old draft (their cohort keeps its program set, cache pool, prefix
        cache and draft cache until it drains); every admission after the
        swap runs the new params. Same-architecture swaps reuse the
        compiled executables (the draft carries over unless a new one is
        given); changed architectures warm a full new program set BEFORE
        the cutover. Returns the new version."""
        rt = self._get(name)
        net = load_net(net_or_path) if isinstance(net_or_path, str) \
            else net_or_path
        with rt.swap_lock:
            old = rt.active_ps
            try:
                new_ps = old.with_params_from(net, draft_net=draft)
            except ValueError:
                self._pause_detectors()
                try:
                    new_ps = GenerationProgramSet(
                        net, config=old.config, adapter="auto",
                        draft_net=draft or old.draft_net,
                        trace_hook=self._on_trace,
                        cost_path=old.cost_path, mesh=old.mesh).warm()
                finally:
                    self._resume_detectors()
            rt.active_ps = new_ps         # atomic: next admission cohort
            rt.version += 1
            rt.metrics.record_swap()
            return rt.version

    def reload_from_checkpoint(self, name: str, path: str) -> int:
        return self.hot_swap(name, load_net(path))

    # ---------------------------------------------------------- observability
    def metrics(self) -> Dict[str, dict]:
        with self._lock:
            rts = list(self._models.values())
        return {rt.name: rt.metrics.snapshot() for rt in rts}

    def models(self) -> Dict[str, dict]:
        with self._lock:
            rts = list(self._models.values())
        return {rt.name: {
            "version": rt.version,
            "adapter": rt.active_ps.adapter,
            "warmed": rt.active_ps.warmed,
            "decode_slots": rt.config.decode_slots,
            "block_len": rt.config.block_len,
            "capacity": rt.config.capacity,
            "num_blocks": rt.config.num_blocks,
            "prompt_rungs": list(rt.config.prompt_rungs),
            "prefill_batches": list(rt.config.prefill_batches),
            "in_flight": rt.in_flight,
            "queue_depth": rt.queue_depth,
            "prefix_cache": rt.active_ps.prefix_enabled,
            "kv_cache_dtype": rt.config.kv_cache_dtype,
            "kv_bytes_per_token": rt.active_ps.kv_bytes_per_token(),
            "cache_kind": getattr(rt.active_ps.spec, "cache_kind", None),
            "cache_bytes_per_token": rt.active_ps.kv_bytes_per_token(),
            "conv_state_bytes": rt.active_ps.recurrent_state_bytes(),
            # recurrent mixers of every kind (a short convolution's rows, a
            # lightning-attention layer's float32 matrix a head): what one
            # slot's states hold; ``conv_state_bytes`` is all slots'
            "state_bytes_per_slot": rt.active_ps.state_bytes_per_slot(),
            "linear_layers": len(rt.active_ps.in_place_names),
            # block-sparse layers (full-context pages, read by selection):
            # how many, what their compressed keys cost a token, and the
            # selection's sizes (None: the model selects nothing)
            "sparse_layers": len(getattr(rt.active_ps.spec, "sparse_names",
                                         [])),
            "index_bytes_per_token": rt.active_ps.index_bytes_per_token(),
            "selection": rt.active_ps.spec.selection._asdict()
            if rt.active_ps.sparse else None,
            # sliding-window layers: their width (None: the model has
            # none), how many there are, and what one slot's rings hold
            # (K and V, all of them); ``cache_bytes_per_token`` counts the
            # layers that keep the whole context alone
            "window": getattr(rt.active_ps.spec, "window", None),
            "window_layers": getattr(rt.active_ps.spec, "n_window_layers",
                                     0),
            "window_cache_bytes_per_slot":
                rt.active_ps.window_cache_bytes_per_slot(),
            "model_shards": rt.active_ps.model_shards,
            "kv_pool_bytes_per_chip": rt.active_ps.kv_pool_chip_bytes,
            "speculative": {
                "enabled": rt.active_ps.spec_k > 0,
                "k": rt.active_ps.spec_k,
                "draft_adapter": rt.active_ps.draft_adapter,
            },
        } for rt in rts}

    def queue_depths(self) -> Dict[str, int]:
        with self._lock:
            rts = list(self._models.values())
        return {rt.name: rt.queue_depth for rt in rts}

    def steering(self) -> dict:
        """Per-model routing signals + the worst-case aggregate a fleet
        router steers on (``/health``'s ``steering`` key): total queue
        depth, max slot occupancy, min block-pool free fraction, and the
        request-weighted prefix hit rate across models."""
        with self._lock:
            rts = list(self._models.values())
        per = {rt.name: rt.steering() for rt in rts}
        rows = list(per.values())
        hits = sum(r["prefix_hit_rate"] * r["prefix_lookups"] for r in rows)
        lookups = sum(r["prefix_lookups"] for r in rows)
        return {
            "queue_depth": sum(r["queue_depth"] for r in rows),
            "in_flight": sum(r["in_flight"] for r in rows),
            "slot_occupancy": max(
                (r["slot_occupancy"] for r in rows), default=0.0),
            "block_pool_free_frac": min(
                (r["block_pool_free_frac"] for r in rows), default=1.0),
            "prefix_hit_rate": round(hits / lookups, 4) if lookups else 0.0,
            "prefix_lookups": lookups,
            "block_len": per.get(self._default, {}).get(
                "block_len", rows[0]["block_len"] if rows else None),
            "models": per,
        }

    def publish_metrics(self, storage, session_id: str = "generation"):
        with self._lock:
            rts = list(self._models.values())
        for rt in rts:
            rt.metrics.publish(storage, session_id=session_id,
                               worker_id=rt.name)

    @property
    def trace_count(self) -> int:
        return self._trace_count

    def _on_trace(self):
        self._trace_count += 1

    @staticmethod
    def compile_count() -> int:
        from ..metrics import xla_compile_count
        return xla_compile_count()

    @property
    def draining(self) -> bool:
        return self._draining

    def _pause_detectors(self):
        """Warm-up compiles are legitimate — keep them out of the armed
        decode-loop recompile watchdogs."""
        with self._lock:
            rts = list(self._models.values())
        for rt in rts:
            if rt._det is not None:
                rt._det.__exit__(None, None, None)

    def _resume_detectors(self):
        with self._lock:
            rts = list(self._models.values())
        for rt in rts:
            if rt._det is not None:
                rt._det.__enter__()

    # ------------------------------------------------------------- lifecycle
    def stop(self, drain: bool = True, timeout: float = 10.0) -> None:
        with self._lock:
            self._draining = True
            rts = list(self._models.values())
        for rt in rts:
            rt.stop(drain=drain, timeout=timeout)
