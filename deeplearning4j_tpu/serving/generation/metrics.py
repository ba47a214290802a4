"""Generation observability: per-model token/latency/occupancy counters.

Same contract as ``serving.metrics.ServingMetrics`` — a local snapshot dict
(the ``GET /metrics`` payload) with every recording mirrored into the shared
telemetry registry under ``generation.<model>.*`` so training, forward
serving and decode land on ONE reporting surface. Adds the decode-specific
signals: time-to-first-token, per-decode-step latency, per-user streaming
rate, slot occupancy, block-pool usage, and the decode loop's own
recompile count (the RecompileDetector the scheduler keeps armed after
warm-up).
"""
from __future__ import annotations

import threading
import time
from collections import deque
from typing import Dict

from ...telemetry import get_registry
from ...telemetry.registry import _percentile


# loop phase (scheduler.py) -> its histogram and snapshot key
_PHASE_METRIC = {"admit_batch": "admit_ms", "emit": "emit_ms"}

# what a decode pass or a verify window writes to the registry: the names
# are built once per ``GenerationMetrics`` (``_step_names``), not by
# f-string on every pass
_STEP_KEYS = ("decode_steps", "decode_steps_overlapped",
              "overrun_tokens_dropped", "tokens_out", "decode_step_ms",
              "slot_occupancy", "queue_depth", "tokens_per_sec",
              "spec.verify_steps", "spec.proposed", "spec.accepted",
              "verify_step_ms", "spec.accepted_per_verify")


class GenerationMetrics:
    def __init__(self, window: int = 4096, name: str = "default",
                 registry=None):
        self._lock = threading.Lock()
        self.name = name
        self._registry = registry
        self._step_names = {k: f"generation.{name}.{k}" for k in _STEP_KEYS}
        self._ttft_ms = deque(maxlen=window)
        self._step_ms = deque(maxlen=window)
        self._tok_t = deque(maxlen=window)       # emission timestamps
        self._queue_wait_ms = deque(maxlen=window)
        self._phase_ms = {p: deque(maxlen=window) for p in _PHASE_METRIC}
        self.requests = 0
        self.admits_jumped = 0      # admissions ahead of an earlier arrival
        self.tokens_out = 0
        self.prefills = 0
        self.prefill_rows = 0
        self.decode_steps = 0
        self.decode_slot_steps = 0              # active slots summed per step
        # the decode pipeline: steps launched while the device still held
        # an unread one, and tokens a late ``stop`` threw away
        self.decode_steps_overlapped = 0
        self.overrun_tokens_dropped = 0
        self.finished: Dict[str, int] = {}
        self.rejected: Dict[str, int] = {"full": 0, "exhausted": 0,
                                         "draining": 0, "deadline": 0,
                                         "error": 0}
        self.swaps = 0
        self.decode_recompiles = 0
        self.slots = 0
        self.kv_bytes_per_token = None          # quantized-KV tier (ISSUE 17)
        # prefix-cache economics (ISSUE 14)
        self._ttft_cached_ms = deque(maxlen=window)
        self.prefix_hits = 0
        self.prefix_misses = 0
        self.prefix_tokens_saved = 0
        self.cow_copies = 0
        self.prefix_evictions = 0
        self.prefix_skipped_stateful = 0
        self.prefix_skipped_windowed = 0
        self._prefix_gauges: dict = {}
        # speculative decoding
        self._verify_ms = deque(maxlen=window)
        self.verify_steps = 0
        self.verify_slot_steps = 0
        self.spec_proposed = 0
        self.spec_accepted = 0
        self.spec_emitted = 0
        self._t0 = time.monotonic()
        self._rate_t = self._t0

    @property
    def registry(self):
        return self._registry if self._registry is not None else get_registry()

    # ------------------------------------------------------------- recording
    def record_request(self) -> None:
        with self._lock:
            self.requests += 1
        reg = self.registry
        if reg.enabled:
            reg.counter(f"generation.{self.name}.requests").inc()

    def record_admission(self, queue_ms: float, jumped: int = 0) -> None:
        """One request's wait from submission to its slot, and how many
        earlier-queued requests still waited when it got it (0 under
        arrival order)."""
        with self._lock:
            self._queue_wait_ms.append(queue_ms)
            if jumped:
                self.admits_jumped += 1
        reg = self.registry
        if reg.enabled:
            reg.histogram(
                f"generation.{self.name}.queue_wait_ms").observe(queue_ms)
            if jumped:
                reg.counter(f"generation.{self.name}.admits_jumped").inc()

    def record_phase(self, phase: str, ms: float) -> None:
        """A host phase of the loop between two program calls: the
        admission pass (``admit_batch`` -> ``admit_ms``) or the emission
        after a step (``emit`` -> ``emit_ms``). The loop's idle wait has
        its trace event only."""
        ring = self._phase_ms.get(phase)
        if ring is None:
            return
        with self._lock:
            ring.append(ms)
        reg = self.registry
        if reg.enabled:
            reg.histogram(f"generation.{self.name}."
                          f"{_PHASE_METRIC[phase]}").observe(ms)

    def record_prefill(self, rows: int, ttft_ms_per_row,
                       emitted: int = 0) -> None:
        now = time.monotonic()
        with self._lock:
            self.prefills += 1
            self.prefill_rows += rows
            self._ttft_ms.extend(ttft_ms_per_row)
            self.tokens_out += emitted          # each row's FIRST token
            self._tok_t.extend([now] * emitted)
        reg = self.registry
        if reg.enabled:
            reg.counter(f"generation.{self.name}.prefills").inc()
            if emitted:
                reg.counter(
                    f"generation.{self.name}.tokens_out").inc(emitted)
            h = reg.histogram(f"generation.{self.name}.ttft_ms")
            for v in ttft_ms_per_row:
                h.observe(v)

    def record_decode_step(self, step_ms: float, active_slots: int,
                           emitted: int, *, slots: int,
                           queue_depth: int, overlapped: int = 0,
                           overrun: int = 0) -> None:
        """One decode step, where it is READ. ``step_ms`` is the loop's
        pass that read it (the launch of the next step and this step's
        read-back), not launch-to-result: with a step always in flight
        it is the interval at which steps complete. ``overlapped``: the
        device still held an unread step when this one was launched;
        ``overrun``: tokens of slots a ``stop`` had already finished."""
        now = time.monotonic()
        with self._lock:
            self.decode_steps += 1
            self.decode_steps_overlapped += overlapped
            self.overrun_tokens_dropped += overrun
            self.decode_slot_steps += active_slots
            self.tokens_out += emitted
            self._step_ms.append(step_ms)
            self._tok_t.extend([now] * emitted)
            self.slots = slots
        reg = self.registry
        if reg.enabled:
            n = self._step_names
            reg.counter(n["decode_steps"]).inc()
            # incremented by 0 too: both read 0 from the first step on
            reg.counter(n["decode_steps_overlapped"]).inc(overlapped)
            reg.counter(n["overrun_tokens_dropped"]).inc(overrun)
            reg.counter(n["tokens_out"]).inc(emitted)
            reg.histogram(n["decode_step_ms"]).observe(step_ms)
            self._loop_gauges(reg, now, active_slots, slots, queue_depth)

    def _loop_gauges(self, reg, now: float, active_slots: int, slots: int,
                     queue_depth: int) -> None:
        """What the fleet's collector steers by, after every pass."""
        n = self._step_names
        reg.gauge(n["slot_occupancy"]).set(
            active_slots / slots if slots else 0.0)
        reg.gauge(n["queue_depth"]).set(queue_depth)
        # throttled: the rate scan over the timestamp ring is not free
        # and the decode step is the serving hot loop
        if now - self._rate_t >= 0.5:
            self._rate_t = now
            reg.gauge(n["tokens_per_sec"]).set(
                self._recent_tokens_per_sec(now))

    # -------------------------------------------------- prefix cache (hits)
    def record_prefix_hit(self, tokens_saved: int) -> None:
        with self._lock:
            self.prefix_hits += 1
            self.prefix_tokens_saved += tokens_saved
        reg = self.registry
        if reg.enabled:
            reg.counter(f"generation.{self.name}.prefix.hits").inc()
            if tokens_saved:
                reg.counter(
                    f"generation.{self.name}.prefix.tokens_saved").inc(
                    tokens_saved)
            self._hit_rate_gauge(reg)

    def record_prefix_miss(self) -> None:
        with self._lock:
            self.prefix_misses += 1
        reg = self.registry
        if reg.enabled:
            reg.counter(f"generation.{self.name}.prefix.misses").inc()
            self._hit_rate_gauge(reg)

    def _hit_rate_gauge(self, reg) -> None:
        total = self.prefix_hits + self.prefix_misses
        if total:
            reg.gauge(f"generation.{self.name}.prefix_hit_rate").set(
                round(self.prefix_hits / total, 4))

    def record_cow(self) -> None:
        with self._lock:
            self.cow_copies += 1
        reg = self.registry
        if reg.enabled:
            reg.counter(f"generation.{self.name}.prefix.cow_copies").inc()

    def record_prefix_skipped_stateful(self, n: int = 1) -> None:
        """Admissions that went past the prefix cache because the model's
        sequences carry a recurrent state the cached pages do not hold."""
        with self._lock:
            self.prefix_skipped_stateful += n
        reg = self.registry
        if reg.enabled:
            reg.counter(
                f"generation.{self.name}.prefix_skipped_stateful").inc(n)

    def record_prefix_skipped_windowed(self, n: int = 1) -> None:
        """Admissions that went past the prefix cache because the model has
        sliding-window layers, whose rings do not keep the window's rows
        at a matched boundary."""
        with self._lock:
            self.prefix_skipped_windowed += n
        reg = self.registry
        if reg.enabled:
            reg.counter(
                f"generation.{self.name}.prefix_skipped_windowed").inc(n)

    def record_prefix_evictions(self, n: int) -> None:
        with self._lock:
            self.prefix_evictions += n
        reg = self.registry
        if reg.enabled:
            reg.counter(f"generation.{self.name}.prefix.evictions").inc(n)

    def record_cached_first_token(self, ttft_ms: float) -> None:
        """TTFT for a cache-hit admission (prefill skipped; first token
        fell out of the replay's final decode step)."""
        with self._lock:
            self._ttft_cached_ms.append(ttft_ms)
        reg = self.registry
        if reg.enabled:
            reg.histogram(
                f"generation.{self.name}.ttft_cached_ms").observe(ttft_ms)

    def set_prefix_gauges(self, stats: dict) -> None:
        """Mirror the active cohort's block-pool economics (shared blocks,
        cached-LRU size) — the /metrics 'prefix' gauges."""
        with self._lock:
            self._prefix_gauges = dict(stats)
        reg = self.registry
        if reg.enabled:
            reg.gauge(f"generation.{self.name}.prefix.shared_blocks").set(
                stats.get("shared_blocks", 0))
            reg.gauge(
                f"generation.{self.name}.prefix.cached_lru_blocks").set(
                stats.get("cached_lru_blocks", 0))

    # ------------------------------------------------- speculative decoding
    def record_verify(self, step_ms: float, active_slots: int, *,
                      proposed: int, accepted: int, emitted: int,
                      slots: int, queue_depth: int) -> None:
        """One draft-propose + verify window: ``accepted`` draft tokens
        matched the target's greedy choice; ``emitted`` includes each
        slot's correction token (the per-target-dispatch yield)."""
        now = time.monotonic()
        with self._lock:
            self.verify_steps += 1
            self.verify_slot_steps += active_slots
            self.spec_proposed += proposed
            self.spec_accepted += accepted
            self.spec_emitted += emitted
            self.tokens_out += emitted
            # verify windows are k+1-token passes — kept OUT of the
            # one-token decode_step_ms population (own percentiles below)
            self._verify_ms.append(step_ms)
            self._tok_t.extend([now] * emitted)
            self.slots = slots
            per_verify = (self.spec_emitted / self.verify_slot_steps
                          if self.verify_slot_steps else 0.0)
        reg = self.registry
        if reg.enabled:
            n = self._step_names
            reg.counter(n["spec.verify_steps"]).inc()
            reg.counter(n["spec.proposed"]).inc(proposed)
            reg.counter(n["spec.accepted"]).inc(accepted)
            reg.counter(n["tokens_out"]).inc(emitted)
            reg.histogram(n["verify_step_ms"]).observe(step_ms)
            reg.gauge(n["spec.accepted_per_verify"]).set(
                round(per_verify, 3))
            self._loop_gauges(reg, now, active_slots, slots, queue_depth)

    def record_finish(self, reason: str) -> None:
        with self._lock:
            self.finished[reason] = self.finished.get(reason, 0) + 1
        reg = self.registry
        if reg.enabled:
            reg.counter(
                f"generation.{self.name}.finished.{reason}").inc()

    def record_rejection(self, kind: str) -> None:
        with self._lock:
            self.rejected[kind] = self.rejected.get(kind, 0) + 1
        reg = self.registry
        if reg.enabled:
            reg.counter(f"generation.{self.name}.rejected.{kind}").inc()

    def record_swap(self) -> None:
        with self._lock:
            self.swaps += 1
        reg = self.registry
        if reg.enabled:
            reg.counter(f"generation.{self.name}.hot_swaps").inc()

    def record_recompile(self, n: int) -> None:
        with self._lock:
            self.decode_recompiles = n

    def set_kv_bytes_per_token(self, v) -> None:
        """Block-pool bytes per token slot (the quantized-KV capacity
        currency); None (state adapter) publishes nothing."""
        if v is None:
            return
        with self._lock:
            self.kv_bytes_per_token = float(v)
        reg = self.registry
        if reg.enabled:
            reg.gauge(f"generation.{self.name}.kv_bytes_per_token").set(
                float(v))

    def _recent_tokens_per_sec(self, now: float, window_s: float = 5.0):
        if not self._tok_t:
            return 0.0
        cut = now - window_s
        # the ring is count-bounded: at high rates it evicts timestamps
        # still inside the window — measure over the span actually
        # retained, or the gauge saturates at maxlen/window_s
        oldest = self._tok_t[0]
        if oldest > cut:                       # evicted inside the window
            cut = oldest
            span = max(now - cut, 1e-3)
        else:
            span = max(min(window_s, now - self._t0), 1e-3)
        n = 0
        for t in reversed(self._tok_t):
            if t < cut:
                break
            n += 1
        return round(n / span, 2)

    # ------------------------------------------------------------- reporting
    def snapshot(self) -> dict:
        now = time.monotonic()
        with self._lock:
            ttft = sorted(self._ttft_ms)
            ttft_c = sorted(self._ttft_cached_ms)
            step = sorted(self._step_ms)
            verify = sorted(self._verify_ms)
            host = {"queue_wait_ms": sorted(self._queue_wait_ms)}
            for phase, ring in self._phase_ms.items():
                host[_PHASE_METRIC[phase]] = sorted(ring)
            # occupancy over BOTH step kinds: a speculation-saturated
            # engine advances slots through verify windows, not plain
            # decode steps — counting only the latter read near-zero
            # under full load
            steps_all = self.decode_steps + self.verify_steps
            occ = ((self.decode_slot_steps + self.verify_slot_steps)
                   / (steps_all * self.slots)
                   if steps_all and self.slots else 0.0)
            lookups = self.prefix_hits + self.prefix_misses
            out = {
                "requests": self.requests,
                "admits_jumped": self.admits_jumped,
                "tokens_out": self.tokens_out,
                "prefills": self.prefills,
                "prefill_rows": self.prefill_rows,
                "decode_steps": self.decode_steps,
                "decode_steps_overlapped": self.decode_steps_overlapped,
                "overrun_tokens_dropped": self.overrun_tokens_dropped,
                "ttft_ms": {"p50": round(_percentile(ttft, 0.50), 3),
                            "p99": round(_percentile(ttft, 0.99), 3)},
                "decode_step_ms": {"p50": round(_percentile(step, 0.50), 3),
                                   "p99": round(_percentile(step, 0.99), 3)},
                # host side of the loop: a request's wait for its slot, an
                # admission pass, the emission after a step
                **{k: {"p50": round(_percentile(v, 0.50), 3),
                       "p99": round(_percentile(v, 0.99), 3)}
                   for k, v in host.items()},
                "slot_occupancy": round(occ, 4),
                "tokens_per_sec_recent": self._recent_tokens_per_sec(now),
                "finished": dict(self.finished),
                "rejected": dict(self.rejected),
                "hot_swaps": self.swaps,
                "decode_recompiles": self.decode_recompiles,
                "kv_bytes_per_token": self.kv_bytes_per_token,
                "uptime_s": round(now - self._t0, 1),
                # block-pool economics: who is sharing, what the cache
                # holds, what COW and eviction cost
                "prefix": {
                    "hits": self.prefix_hits,
                    "misses": self.prefix_misses,
                    "hit_rate": (round(self.prefix_hits / lookups, 4)
                                 if lookups else 0.0),
                    "tokens_saved": self.prefix_tokens_saved,
                    "cow_copies": self.cow_copies,
                    "evictions": self.prefix_evictions,
                    "skipped_stateful": self.prefix_skipped_stateful,
                    "skipped_windowed": self.prefix_skipped_windowed,
                    "shared_blocks": self._prefix_gauges.get(
                        "shared_blocks", 0),
                    "cached_lru_blocks": self._prefix_gauges.get(
                        "cached_lru_blocks", 0),
                    "cached_blocks": self._prefix_gauges.get(
                        "cached_blocks", 0),
                    "ttft_cached_ms": {
                        "p50": round(_percentile(ttft_c, 0.50), 3),
                        "p99": round(_percentile(ttft_c, 0.99), 3)},
                },
                "speculative": {
                    "verify_steps": self.verify_steps,
                    "verify_step_ms": {
                        "p50": round(_percentile(verify, 0.50), 3),
                        "p99": round(_percentile(verify, 0.99), 3)},
                    "proposed": self.spec_proposed,
                    "accepted": self.spec_accepted,
                    "emitted": self.spec_emitted,
                    "accepted_tokens_per_verify": (
                        round(self.spec_emitted / self.verify_slot_steps, 3)
                        if self.verify_slot_steps else 0.0),
                    "proposals_accepted_per_verify": (
                        round(self.spec_accepted / self.verify_slot_steps, 3)
                        if self.verify_slot_steps else 0.0),
                },
            }
            return out

    def publish(self, storage, session_id: str = "generation",
                worker_id: str = "default") -> dict:
        snap = self.snapshot()
        storage.put_update(session_id, worker_id, snap)
        return snap
