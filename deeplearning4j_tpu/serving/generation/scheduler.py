"""Continuous batching: slot-based decode scheduling with step-boundary
admission, per-token streams, and cohort-pinned hot-swap.

One dispatch thread per model owns the decode loop:

    loop:  admit (bucketed prefill for queued requests, into free slots)
           -> per live cohort, LAUNCH decode step k+1 (ALL in-flight
              sequences advance one token), then READ step k
           -> emit step k's tokens to per-request TokenStreams, retire
              finished slots (stop token / max_tokens / deadline /
              cancel), which frees their cache blocks for the next
              admission

The decode pipeline is one step deep: a cohort keeps at most one launched
and unread step between passes (``_Cohort.unread``), so the host's
dispatch and emission run while the device works and the device's next
step is queued when the current one ends. Step k+1 takes its tokens from
step k's result ON THE DEVICE, except the rows whose token the host knows
(a slot's first token after its prefill, a prefix hit's replayed prompt
token: ``_host_known``). Positions advance at the launch. A slot whose
last token by count is in flight gives its slot and pages back at that
launch (device order queues whatever reuses them behind the step that
still reads them) and is delivered at the read; a ``stop`` token is seen
one step late and the token the slot ran over is dropped at the next read
and counted. Deadline and cancellation are host facts and act at the
read. A cohort that speculates keeps the synchronous order (launch k,
read k): ``_spec_step`` reads the host's tokens.

The loop's host phases between the blocking program spans are recorded as
complete events of category ``phase`` (``generation.admit_batch``,
``generation.emit``, ``generation.idle_wait``): not ``span``, because a
reader that lines the device's clock up with the host's does so on spans
that BLOCK on the device, and these cover the rest of the loop.

The loop accounts for itself (ISSUE 42). Every launched decode step has a
number; the launch's span, the read's and the pass's carry it, so a
launch is paired with its read. A pass says what it waited for: its
children's own durations (``launch_ms``, ``read_wait_ms``) and the time
inside a garbage collection of generation 1 or 2; every sixteenth decode
pass also samples the loop thread's CPU time and involuntary context
switches so far (``loop_cpu_ms``, ``nivcsw``: ONE system call, because
on a sandboxed host each costs microseconds; a prefill or a verify
window, rare and long, takes its own ``cpu_ms``). A pass far longer than
its kind's recent median leaves one ``generation.stall`` instant event
with all of that and the thread's usage since the last sample (at most
one a second: what an operator pages on). Every request that ends,
however it ends, leaves ONE ``generation.request`` complete event of
category ``request`` from its submission to its end, written by the
thread that ends it.

Admission happens at step boundaries only — a new request never stalls
in-flight decode, it just lands in the next step's batch (freed slots are
backfilled from the queue; idle slots ride along masked). Which waiting
requests a pass admits is ``GenerationConfig.admission_choice``: when all
that waits fits into the pass, all of it in arrival order; when more wait
than a pass takes, the head of the queue first, then the next waiting
requests OF THE HEAD'S PROMPT RUNG (inside ``ADMIT_LOOKAHEAD``), cut back
to the largest batch rung they fill, so that the prefill launched pads no
row to another request's rung and has no empty row. Order among waiting
requests therefore changes under load, within a bound: the head leaves at
every admitting pass, so a request is admitted within as many admitting
passes as its place in the queue when it arrived, and only requests of
the current head's rung ever pass it. A head whose blocks do not fit
admits nobody. ``generation.admit`` carries ``jumped``, the number of
earlier-queued requests still waiting when the request got its slot (0
in arrival order), counted as ``generation.<m>.admits_jumped``.

All device work goes through the cohort's AOT-warmed
``GenerationProgramSet``; the host side is numpy-only, so steady state
never traces (a ``RecompileDetector`` stays armed on the loop to prove
it).

Hot-swap cutover rule: a request is pinned to the program set (params) it
was admitted under. After ``hot_swap``, new admissions form a NEW cohort on
the new params (its own cache pool); old cohorts keep decoding on the old
params until they drain, then their pool is dropped. During the transition
each step runs one decode program per live cohort.
"""
from __future__ import annotations

import gc
import itertools
import queue as _queue
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Sequence, Set

import numpy as np

from ...telemetry import (RecompileDetector, get_registry,
                          record_external_span, span)
from ...telemetry.flightrec import get_flight_recorder
from ...telemetry.spans import wall_us
from ...telemetry.tracecontext import current_trace_id, event
from ..errors import (BlockPoolExhaustedError, DeadlineExceededError,
                      DrainingError, GenerationClosedError, QueueFullError,
                      ShapeMismatchError)
from .kvcache import BlockAllocator
from .metrics import GenerationMetrics
from .prefix import PrefixCache
from .programs import (ADMIT_LOOKAHEAD, GenerationProgramSet, pack_decode,
                       padding_prefill, unpack_prefill)


try:
    from resource import RUSAGE_THREAD, getrusage

    def _thread_usage():
        """(CPU milliseconds, involuntary context switches) of the calling
        thread so far, in one system call."""
        ru = getrusage(RUSAGE_THREAD)
        return (ru.ru_utime + ru.ru_stime) * 1e3, ru.ru_nivcsw
except ImportError:                # a platform without per-thread usage
    def _thread_usage():
        return None

# a decode pass in this many samples the loop thread's usage: on the chip
# tool's host a system call costs 6-7 us alone and about 20 us in the
# serving process, and a pass of 1.9 ms has 10 us to spare (PERF.md §5)
_USAGE_EVERY = 16


def selected_keys(t, sel):
    """Keys position ``t`` (numpy int64 array) of a block-sparse layer
    attends to: its chosen blocks' rows up to itself. Every block while
    the context is short (``ops.sparse_select``), else ``topk`` blocks of
    which its own holds ``t mod block + 1`` rows."""
    t = np.asarray(t, np.int64)
    own = t % sel.block + 1
    dense = (t < sel.dense_len) | (t // sel.block + 1 <= sel.topk)
    return np.where(dense, t + 1, (sel.topk - 1) * sel.block + own)


def selection_rows(plens, sel):
    """(keys the block-sparse selection names, compressed keys it is
    scored against) summed over the positions of prompts of lengths
    ``plens``, one layer's: what a prefill's sparse attention and its
    scoring have to do."""
    chosen = index = 0
    for n in plens:
        t = np.arange(int(n), dtype=np.int64)
        chosen += int(selected_keys(t, sel).sum())
        index += int(np.maximum((t + 1 - sel.kernel) // sel.stride + 1,
                                0).sum())
    return chosen, index


class _GcWatch:
    """Nanoseconds spent so far inside garbage collections of generation 1
    or 2, whichever thread ran them (the interpreter lock holds the loop
    meanwhile): one ``gc.callbacks`` hook, installed with a model's
    runtime and removed with it. A pass reads ``total_ns`` before and
    after."""

    def __init__(self):
        self.total_ns = 0
        self._t0 = 0
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase: str, info: dict) -> None:
        if not info["generation"]:
            return
        if phase == "start":
            self._t0 = time.perf_counter_ns()
        elif self._t0:
            self.total_ns += time.perf_counter_ns() - self._t0
            self._t0 = 0

    def close(self) -> None:
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)


# a pass stalls where it takes longer than the larger of _STALL_MS and
# _STALL_X times the median of its kind's last _STALL_HISTORY passes (once
# the kind has _STALL_LEAST of them); at most one event in _STALL_EVERY_S
_STALL_MS = 50.0
_STALL_X = 20.0
_STALL_HISTORY = 256
_STALL_LEAST = 8
_STALL_EVERY_S = 1.0

_request_ids = itertools.count(1)      # unique in the process


class TokenStream:
    """Per-request token stream: the scheduler produces, ONE consumer
    iterates (or calls ``result()`` — not both). Always terminates: every
    admitted request is finished with a reason (or failed) exactly once,
    so iterating callers can never hang. ``request_id`` is the integer the
    request's ``generation.admit`` and ``generation.request`` events
    carry."""

    def __init__(self, request_id: Optional[int] = None):
        self.request_id = request_id
        self._q: "_queue.Queue" = _queue.Queue()
        self._done = threading.Event()
        self.finish_reason: Optional[str] = None
        self.error: Optional[BaseException] = None
        self.emitted = 0
        self._cancel_cb = None

    # ---------------------------------------------------- producer (loop)
    def _put(self, tok: int) -> None:
        self.emitted += 1
        self._q.put(("tok", tok))

    def _finish(self, reason: str, error: Optional[BaseException] = None):
        if self._done.is_set():
            return
        self.finish_reason = reason
        self.error = error
        self._done.set()
        self._q.put(("end", reason))

    # ------------------------------------------------------------ consumer
    def __iter__(self):
        while True:
            kind, val = self._q.get()
            if kind == "tok":
                yield val
            else:
                return

    def result(self, raise_on_error: bool = True):
        """Drain the stream; returns (tokens, finish_reason). With
        ``raise_on_error`` a stream that failed (engine error/shutdown)
        raises instead of returning partial output."""
        tokens = list(self)
        if raise_on_error and self.error is not None \
                and self.finish_reason not in ("deadline",):
            raise self.error
        return tokens, self.finish_reason

    @property
    def done(self) -> bool:
        return self._done.is_set()

    def cancel(self) -> None:
        """Consumer gave up (e.g. HTTP client disconnected): the scheduler
        retires the slot at the next step boundary."""
        if self._cancel_cb is not None:
            self._cancel_cb()


class _GenRequest:
    __slots__ = ("prompt", "max_new", "temperature", "top_k", "stop",
                 "deadline", "stream", "slot", "blocks", "shared_blocks",
                 "replay", "replaying", "matched_tokens", "spec", "emitted",
                 "unread", "cancelled", "cancel_reason", "enqueue_t",
                 "cohort", "trace_id", "id", "queue_ms", "first_t", "last_t",
                 "steps", "rung", "batch", "prompt_rung", "jumped")

    def __init__(self, prompt: np.ndarray, max_new: int, temperature: float,
                 top_k: int, stop: frozenset, deadline: float,
                 speculative: bool = True, prompt_rung: int = 0):
        self.prompt = prompt
        # the rung its prompt pads to, stamped here so that an admission
        # pass chooses its batch by integer compares
        self.prompt_rung = prompt_rung
        # earlier-queued requests still waiting when it was admitted
        self.jumped = 0
        self.max_new = max_new
        self.temperature = temperature
        self.top_k = top_k
        self.stop = stop
        self.deadline = deadline
        self.id = next(_request_ids)
        self.stream = TokenStream(self.id)
        self.stream._cancel_cb = self._cancel
        self.slot: Optional[int] = None
        self.blocks: List[int] = []          # owned (freed at finish)
        self.shared_blocks: List[int] = []   # cache custody (released)
        self.replay: "deque[int]" = deque()  # prompt suffix still to feed
        self.replaying = False
        self.matched_tokens = 0
        # speculative decoding is exact only for greedy requests; sampling
        # ones ride the plain decode path
        self.spec = bool(speculative) and temperature <= 0.0
        self.cohort = None                  # set at admission
        self.emitted = 0
        self.unread = 0        # tokens of its launched steps still to read
        self.cancelled = False
        self.cancel_reason = "cancelled"
        self.enqueue_t = time.monotonic()
        # what the request's one record says of its life
        # (``_record_request``): the wait for its slot, the loop's clock at
        # its first and last emission, the passes it rode, its prefill
        self.queue_ms: Optional[float] = None
        self.first_t: Optional[float] = None
        self.last_t: Optional[float] = None
        self.steps = 0
        self.rung = 0
        self.batch = 0
        # the submitter's trace id rides the request across the queue
        # handoff into the decode loop thread (None = untraced: the
        # per-token trace events are skipped entirely)
        self.trace_id = current_trace_id()

    def _cancel(self):
        self.cancelled = True


# what the read of a launched step does with a slot's sampled token
_EMIT = 0           # a generated token
_REPLAY = 1         # a prompt token was fed mid-replay: the sample is dropped
_REPLAY_LAST = 2    # the final prompt token was fed: a hit's FIRST token


class _Step:
    """A launched decode step whose tokens the host has not read: its
    number (counted up per model at the launch), the device's result, the
    (slot, request, what to do with its token) triples live at the
    launch, and the step's span attributes."""
    __slots__ = ("number", "tokens", "pairs", "attrs")

    def __init__(self, number, tokens, pairs, attrs):
        self.number = number
        self.tokens = tokens
        self.pairs = pairs
        self.attrs = attrs


class _Cohort:
    """In-flight sequences pinned to one program set (one model version):
    their cache pool, block allocator, block tables, prefix cache and
    draft cache live and die with the cohort — shared prefix K/V and draft
    proposals can never cross a hot-swap boundary. ``unread`` is the
    cohort's launched and unread decode step between two passes of the
    loop (None: nothing of its is in flight)."""
    __slots__ = ("ps", "cache", "allocator", "tables", "slots", "version",
                 "prefix", "draft_cache", "unread")

    def __init__(self, ps: GenerationProgramSet, version: int):
        self.ps = ps
        self.version = version
        self.cache = ps.make_cache()
        self.allocator = BlockAllocator(ps.config.num_blocks)
        S, mb = ps.config.decode_slots, ps.config.blocks_per_seq
        self.tables = np.zeros((S, mb), np.int32)
        self.slots: Set[int] = set()
        self.prefix: Optional[PrefixCache] = (
            PrefixCache(self.allocator, ps.config.block_len)
            if ps.prefix_enabled else None)
        self.draft_cache = ps.make_draft_cache()
        self.unread: Optional[_Step] = None


class ModelRuntime:
    """Scheduler + device state for one generation model."""

    def __init__(self, name: str, ps: GenerationProgramSet,
                 metrics: Optional[GenerationMetrics] = None, *,
                 watch_recompiles: bool = True):
        self.name = name
        self.active_ps = ps
        self.version = 1
        self.swap_lock = threading.Lock()
        self.config = ps.config
        self.metrics = metrics or GenerationMetrics(name=name)
        self.metrics.set_kv_bytes_per_token(ps.kv_bytes_per_token())
        S = self.config.decode_slots
        self._queue: "deque[_GenRequest]" = deque()
        self._cond = threading.Condition()
        self._slots_free: Set[int] = set(range(S))
        self._slot_req: Dict[int, _GenRequest] = {}
        # released at the launch of their last step, delivered at its read
        self._early: Set[_GenRequest] = set()
        self._tokens = np.zeros(S, np.int32)
        # rows whose next token is the host's (``_tokens``), not the device's
        self._host_known = np.ones(S, np.bool_)
        self._pos = np.zeros(S, np.int32)
        self._temp = np.zeros(S, np.float32)
        self._topk = np.zeros(S, np.int32)
        self._active = np.zeros(S, np.bool_)
        self._cohorts: List[_Cohort] = []
        self._key = ps.fresh_key()
        self._draining = False
        self._stopped = False
        self._step_no = 0                 # the last launched step's number
        self._gc = _GcWatch()
        self._recent = {kind: deque(maxlen=_STALL_HISTORY)
                        for kind in ("decode_step", "prefill", "verify")}
        self._stall_t = 0.0
        # the loop thread's last two usage samples: (monotonic clock, CPU
        # ms, involuntary context switches), taken on the loop's own thread
        self._usage = self._usage_before = None
        self._det = RecompileDetector(allowed=0, warn=False) \
            if watch_recompiles else None
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name=f"generation-{name}")
        self._thread.start()

    # -------------------------------------------------------------- admission
    @property
    def queue_depth(self) -> int:
        return len(self._queue)

    @property
    def in_flight(self) -> int:
        """Requests admitted and not finished: those that hold a slot and
        those whose last token is launched and still to be delivered."""
        return len(self._slot_req) + len(self._early)

    def _admitted(self) -> List[_GenRequest]:
        """Every request admitted and not finished (under ``_cond``)."""
        return list(self._slot_req.values()) + list(self._early)

    def _work_left(self) -> bool:
        return bool(self._queue or self._slot_req
                    or any(c.unread is not None for c in self._cohorts))

    @property
    def draining(self) -> bool:
        return self._draining

    def steering(self) -> dict:
        """Cheap routing signals for the fleet router (the ``/health``
        steering payload — the router must not scrape full ``/metrics``
        per admission): prefix hit rate, instantaneous decode-slot
        occupancy, block-pool free fraction and queue depth, plus the
        ``block_len`` the affinity hash needs. Lock-free reads of ints
        under the GIL — a slightly torn snapshot only mis-routes one
        request, it cannot corrupt anything."""
        cfg = self.config
        coh = self._cohorts[-1] if self._cohorts else None
        free = coh.allocator.free_blocks if coh is not None \
            else cfg.num_blocks
        m = self.metrics
        lookups = m.prefix_hits + m.prefix_misses
        return {
            "queue_depth": len(self._queue),
            "in_flight": self.in_flight,
            "decode_slots": cfg.decode_slots,
            "slot_occupancy": round(len(self._slot_req) / cfg.decode_slots,
                                    4),
            "block_len": cfg.block_len,
            "blocks_total": cfg.num_blocks,
            "block_pool_free_frac": (round(free / cfg.num_blocks, 4)
                                     if cfg.num_blocks else 1.0),
            "prefix_hit_rate": (round(m.prefix_hits / lookups, 4)
                                if lookups else 0.0),
            "prefix_lookups": lookups,
        }

    def submit(self, prompt, *, max_new: int, temperature: float = 0.0,
               top_k: int = 0, stop: Sequence[int] = (),
               timeout: Optional[float] = None,
               speculative: bool = True) -> TokenStream:
        cfg = self.config
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        plen = int(prompt.shape[0])
        if plen < 1:
            raise ShapeMismatchError("empty prompt")
        if max_new < 1:
            raise ShapeMismatchError(f"max_tokens must be >= 1, "
                                     f"got {max_new}")
        if plen > cfg.max_prompt_len:
            raise ShapeMismatchError(
                f"prompt length {plen} exceeds the largest warmed prompt "
                f"rung {cfg.max_prompt_len}")
        if plen + max_new > cfg.capacity:
            raise ShapeMismatchError(
                f"prompt ({plen}) + max_tokens ({max_new}) exceeds cache "
                f"capacity {cfg.capacity} tokens")
        if self.active_ps.adapter == "paged":
            need = cfg.blocks_needed(plen, max_new)
            if need > cfg.num_blocks - 1:
                raise BlockPoolExhaustedError(
                    f"request needs {need} cache blocks but the pool only "
                    f"has {cfg.num_blocks - 1} — lower max_tokens or grow "
                    f"num_blocks; retry will not help at this size",
                    retryable=False)
        timeout = cfg.default_timeout_s if timeout is None else timeout
        req = _GenRequest(prompt, int(max_new), float(temperature),
                          int(top_k), frozenset(int(s) for s in stop),
                          time.monotonic() + timeout,
                          speculative=speculative,
                          prompt_rung=cfg.prompt_rung(plen))
        with self._cond:
            if self._draining or self._stopped:
                self.metrics.record_rejection("draining")
                raise DrainingError(
                    f"generation model '{self.name}' is draining/stopped")
            if len(self._queue) >= self.config.queue_limit:
                cohorts = self._cohorts       # loop thread rebinds the list
                coh = cohorts[-1] if cohorts else None
                if self.active_ps.adapter == "paged" and coh is not None \
                        and coh.allocator.free_blocks == 0 \
                        and (coh.prefix is None
                             or coh.prefix.lru_blocks == 0):
                    self.metrics.record_rejection("exhausted")
                    raise BlockPoolExhaustedError(
                        f"model '{self.name}': KV block pool exhausted and "
                        f"admission queue full ({self.config.queue_limit}) "
                        f"— retry after in-flight generations complete")
                self.metrics.record_rejection("full")
                raise QueueFullError(
                    f"model '{self.name}' generation queue full "
                    f"({self.config.queue_limit} requests)")
            self.metrics.record_request()
            self._queue.append(req)
            self._cond.notify_all()
        if req.trace_id is not None:
            event("generation.submit", model=self.name, prompt_len=plen,
                  max_tokens=int(max_new))
        return req.stream

    # ------------------------------------------------------------ loop body
    def _loop(self):
        if self._det is not None:
            self._det.__enter__()
        self._sample_usage()
        idle_t0 = None
        try:
            while True:
                with self._cond:
                    if self._stopped:
                        break
                    if not self._work_left():
                        # one event per idle PERIOD, not per 20 ms wake-up:
                        # an idle engine must not fill the trace ring
                        if idle_t0 is None:
                            idle_t0 = time.perf_counter()
                        self._cond.wait(0.02)
                        continue
                if idle_t0 is not None:
                    self._phase("idle_wait", idle_t0)
                    idle_t0 = None
                try:
                    if self._queue:
                        self._admit()
                    self._step()
                except Exception as e:       # defensive: nobody may hang
                    self._fail_all(e)
        finally:
            if self._det is not None:
                self._det.__exit__(None, None, None)
            self._shutdown_flush()

    def _phase(self, name: str, t0: float) -> float:
        """Record the host phase of the loop that began at ``t0``
        (``time.perf_counter``) and ends now; returns now, so that phases
        chain."""
        now = time.perf_counter()
        ms = (now - t0) * 1e3
        record_external_span("generation." + name, ms, cat="phase",
                             model=self.name)
        self.metrics.record_phase(name, ms)
        return now

    # ------------------------------------------- the loop's own accounting
    def _pass_begin(self, cpu: bool = True):
        """Inside a pass's span, first: the collector's total and, for the
        rare and long kinds of pass, the loop thread's CPU clock."""
        return (time.thread_time_ns() if cpu else None), self._gc.total_ns

    def _pass_end(self, sp, begun) -> dict:
        """Inside a pass's span, last: what the pass waited for.
        ``launch_ms`` and ``read_wait_ms`` are the child spans' own
        durations (the span is the stopwatch); ``gc_ms`` is there on a
        pass inside which a collection of generation 1 or 2 ran. Set on
        the span and returned for ``_note_pass``."""
        cpu0, gc0 = begun
        kids = sp.child_ms or {}
        cost = {"launch_ms": round(kids.get("generation.dispatch", 0.0), 3),
                "read_wait_ms": round(kids.get("generation.readback", 0.0),
                                      3)}
        if self._gc.total_ns != gc0:
            cost["gc_ms"] = round((self._gc.total_ns - gc0) / 1e6, 3)
        if cpu0 is not None:
            cost["cpu_ms"] = round((time.thread_time_ns() - cpu0) / 1e6, 3)
        for k, v in cost.items():
            sp.set_attr(k, v)
        return cost

    def _sample_usage(self, sp=None):
        """The loop thread's CPU time and involuntary context switches so
        far (cumulative, one system call; the loop's thread only), kept
        for a stall's account and set on ``sp``."""
        usage = _thread_usage()
        if usage is not None:
            self._usage_before = self._usage
            self._usage = (time.monotonic(),) + usage
            if sp is not None:
                sp.set_attr("loop_cpu_ms", round(usage[0], 3))
                sp.set_attr("nivcsw", usage[1])
        return usage

    def _note_pass(self, kind: str, sp, cost: dict, step: int = -1) -> None:
        """After a pass's span has closed: one ``generation.stall`` event
        where it took far longer than its kind's recent passes, with what
        it waited for and what the loop's thread did since its last usage
        sample (at most ``_USAGE_EVERY`` decode passes back)."""
        recent = self._recent[kind]
        wall_ms = sp.dur_ms
        if wall_ms > _STALL_MS and len(recent) >= _STALL_LEAST \
                and wall_ms > _STALL_X * sorted(recent)[len(recent) // 2]:
            now = time.monotonic()
            if now - self._stall_t >= _STALL_EVERY_S:
                self._stall_t = now
                inside = {}
                # against the last sample taken before this pass began
                # (the pass may have taken the newest one itself)
                before = self._usage
                if before is not None and \
                        (now - before[0]) * 1e3 < wall_ms:
                    before = self._usage_before
                usage = self._sample_usage()
                if before is not None and usage is not None:
                    inside = {
                        "since_sample_ms": round((now - before[0]) * 1e3, 3),
                        "cpu_since_sample_ms": round(usage[0] - before[1],
                                                     3),
                        "nivcsw_since_sample": usage[1] - before[2]}
                if self._det is not None:
                    # against what the loop's last iteration recorded
                    inside["compiles"] = self._det.count \
                        - self.metrics.decode_recompiles
                event("generation.stall", model=self.name,
                      span="generation." + kind, step=step,
                      wall_ms=round(wall_ms, 3), **cost, **inside)
        recent.append(wall_ms)

    def _end(self, r: _GenRequest, reason: str,
             error: Optional[BaseException] = None) -> None:
        """Finish a request's stream, once, and leave its ONE record: a
        complete event from its submission to now. Category ``request``,
        not ``span``: it blocks on nothing, and a reader that fits the
        device's clock takes every ``span``. ``first_token_us`` and
        ``last_token_us`` are on the events' clock (``ts``)."""
        if r.stream.done:
            return
        r.stream._finish(reason, error)
        if not get_registry().enabled:
            return
        now, now_us = time.monotonic(), wall_us()
        attrs = {"model": self.name, "request": r.id,
                 "prompt_len": len(r.prompt), "tokens": r.emitted,
                 "matched_tokens": int(r.matched_tokens), "steps": r.steps,
                 "slot": -1 if r.slot is None else r.slot, "rung": r.rung,
                 "batch": r.batch, "reason": reason}
        if r.queue_ms is not None:
            attrs["queue_ms"] = r.queue_ms
        if r.first_t is not None:
            attrs["ttft_ms"] = round((r.first_t - r.enqueue_t) * 1e3, 3)
            attrs["first_token_us"] = now_us - round((now - r.first_t) * 1e6)
            attrs["last_token_us"] = now_us - round((now - r.last_t) * 1e6)
        if r.trace_id is not None:
            attrs["trace_id"] = r.trace_id
        record_external_span("generation.request",
                             (now - r.enqueue_t) * 1e3, cat="request",
                             **attrs)

    def _cohort_for_admission(self) -> _Cohort:
        ps = self.active_ps
        if self._cohorts and self._cohorts[-1].ps is ps:
            return self._cohorts[-1]
        coh = _Cohort(ps, self.version)
        self._cohorts.append(coh)
        return coh

    def _worth_replaying(self, matched_blocks: int, plen: int) -> bool:
        """A cache hit replays its unmatched suffix ONE token per decode
        dispatch — dramatically slower than a batched prefill for a long
        suffix. Only take the hit when the suffix fits the configured
        replay budget (``prefix_max_replay``, default 2 blocks); a shorter
        match admits as a plain miss (and still registers its blocks)."""
        if not matched_blocks:
            return False
        suffix = plen - matched_blocks * self.config.block_len
        if suffix == 0:
            suffix = 1                    # block-aligned: COW + one feed
        return suffix <= self.config.prefix_max_replay

    def _setup_blocks(self, coh: _Cohort, r: _GenRequest) -> None:
        """Blocks for one admission (paged adapter, under the cond lock):
        take references on the longest cached prefix, evict refcount-0
        LRU blocks if the fresh remainder needs room, allocate the rest.
        ``r.matched_tokens == len(prompt)`` flags the block-aligned full
        match whose COW copy the caller performs after the lock."""
        cfg = self.config
        total = cfg.blocks_needed(len(r.prompt), r.max_new)
        plen = len(r.prompt)
        if coh.prefix is None or not self._worth_replaying(
                coh.prefix.probe(r.prompt), plen):
            # miss (or a match too short to beat prefill): plain path —
            # still evict refcount-0 LRU blocks under pool pressure;
            # registration after prefill extends the cached chain
            if coh.prefix is not None:
                evicted = coh.prefix.ensure_free(total)
                if evicted:
                    self.metrics.record_prefix_evictions(evicted)
            r.blocks = coh.allocator.alloc(total) if total else []
            return
        shared, matched = coh.prefix.match(r.prompt)
        # the final prompt token must still be FED through decode for its
        # next-token logits; when the match covers the whole prompt that
        # feed writes inside the last shared block -> COW copy needed
        fresh = total - len(shared) + (1 if matched == plen and shared
                                       else 0)
        evicted = coh.prefix.ensure_free(fresh)
        if evicted:
            self.metrics.record_prefix_evictions(evicted)
        r.blocks = coh.allocator.alloc(fresh) if fresh else []
        r.shared_blocks = shared
        r.matched_tokens = matched

    def _blocks_fit(self, coh: _Cohort, r: _GenRequest) -> bool:
        """Whether the pool can hold ``r`` now (under the cond lock): the
        blocks it needs beyond its cached prefix, against the free blocks
        and what the prefix cache could evict for it."""
        if coh.ps.adapter == "state":
            return True
        cfg, plen = self.config, len(r.prompt)
        fresh = cfg.blocks_needed(plen, r.max_new)
        budget = coh.allocator.free_blocks
        if coh.prefix is not None:
            m = coh.prefix.probe(r.prompt)
            if not self._worth_replaying(m, plen):
                m = 0                # short match -> plain miss
            # a match of the whole prompt copies its last block on write
            fresh += (1 if m and m * cfg.block_len == plen else 0) - m
            budget += coh.prefix.evictable_for(r.prompt)
        return fresh <= budget

    def _put_back(self, coh: _Cohort, r: _GenRequest, place: int) -> None:
        """Undo an admission of this pass before anything was launched for
        it (under the cond lock): blocks and slot go back, and the request
        to ``place`` in the queue, where it waited."""
        if r.blocks:
            coh.allocator.free(r.blocks)
            r.blocks = []
        if r.shared_blocks:
            coh.prefix.release(r.shared_blocks)
            r.shared_blocks = []
        r.matched_tokens = 0
        del self._slot_req[r.slot]
        self._slots_free.add(r.slot)
        r.slot = r.cohort = None
        self._queue.insert(place, r)

    def _admit(self):
        """One admission pass; what of it is host work between program
        calls is recorded as ``generation.admit_batch`` phases."""
        self._phase("admit_batch", self._admit_pass(time.perf_counter()))

    def _admit_pass(self, t_phase: float) -> float:
        """Expire what was cancelled or ran out of time in the queue, then
        admit what ``GenerationConfig.admission_choice`` takes of the
        waiting requests, each under the block budget, and prefill it.
        ``t_phase`` is where the pass began; returns the clock from
        which its host work is not recorded yet (a prefill on the way
        records the part before its launch and its own emission)."""
        cfg = self.config
        cands: List[_GenRequest] = []
        now = time.monotonic()
        with self._cond:
            # expire/cancel while queued
            q = self._queue
            keep: "deque[_GenRequest]" = deque()
            while q:
                r = q.popleft()
                if r.cancelled:
                    self._end(r, r.cancel_reason)
                    self.metrics.record_finish(r.cancel_reason)
                elif now > r.deadline:
                    self.metrics.record_rejection("deadline")
                    self._end(r, "deadline", DeadlineExceededError(
                        "deadline expired while queued for admission"))
                else:
                    keep.append(r)
            self._queue = keep
            if not self._queue or not self._slots_free:
                return t_phase
            coh = self._cohort_for_admission()
            waiting = len(self._queue)
            chosen = cfg.admission_choice(
                [r.prompt_rung for r in itertools.islice(
                    self._queue, ADMIT_LOOKAHEAD)],
                waiting, len(self._slots_free))
            for i in chosen:
                # ``len(cands)`` requests before it have left the queue
                r = self._queue[i - len(cands)]
                if not self._blocks_fit(coh, r):
                    # wait for blocks to free: a head that does not fit
                    # admits nobody (head-of-line: a large request is not
                    # starved by smaller ones), a partner ends the search
                    break
                del self._queue[i - len(cands)]
                # register the request for failure delivery BEFORE block
                # setup: if _setup_blocks raises (an accounting bug —
                # the budget above should prevent it), the
                # loop's _fail_all resolves this caller instead of
                # leaving a popped-but-unregistered stream hanging
                r.slot = self._slots_free.pop()
                r.cohort = coh
                r.jumped = i - len(cands)
                self._slot_req[r.slot] = r
                if coh.ps.adapter != "state":
                    self._setup_blocks(coh, r)
                cands.append(r)
            if len(chosen) < waiting:
                # more wait than a pass takes, so a later pass launches
                # anyway: no empty row either where a partner's blocks
                # did not fit. The last admitted go back to their places
                keep = cfg.filled_batch(len(cands))
                while len(cands) > keep:
                    r = cands.pop()
                    self._put_back(coh, r, chosen[len(cands)] - len(cands))
        if not cands:
            return t_phase
        now = time.monotonic()
        for r in cands:
            # admission: queue -> slot handoff, stamped per request (the
            # loop thread has no context of its own); the wait is taken on
            # one clock by the one thread that knows both ends
            queue_ms = (now - r.enqueue_t) * 1e3
            r.queue_ms = round(queue_ms, 3)
            event("generation.admit", trace_id=r.trace_id, model=self.name,
                  request=r.id, slot=r.slot, prompt_len=len(r.prompt),
                  queue_ms=r.queue_ms, jumped=r.jumped)
            self.metrics.record_admission(queue_ms, r.jumped)
        hits = [r for r in cands if r.matched_tokens]
        misses = [r for r in cands if not r.matched_tokens]
        if misses:
            t_phase = self._prefill_misses(coh, misses, t_phase)
        if hits:
            self._admit_hits(coh, hits)
        if coh.ps.spec_k:
            # speculating requests only: sampling/opted-out rows would
            # waste draft compute and could force a larger (P, L) rung
            spec_cands = [r for r in cands if r.spec]
            if spec_cands:
                self._draft_prefill(coh, spec_cands)
        if coh.prefix is not None:
            self.metrics.set_prefix_gauges(coh.prefix.stats())
        return t_phase

    def _prefill_misses(self, coh: _Cohort, cands: List["_GenRequest"],
                        t_phase: float) -> float:
        """One batched prefill for the admitted misses. ``t_phase`` is
        where the admission pass began: the pass up to the launch is one
        ``generation.admit_batch`` phase, the emission after the read-back
        one ``generation.emit``; returns the clock at which that ended."""
        cfg = self.config
        S, mb = cfg.decode_slots, cfg.blocks_per_seq
        P = cfg.prefill_rung(len(cands))
        L = cfg.prompt_rung(max(len(r.prompt) for r in cands))
        # the launch's one host array, filled a row a candidate through
        # views of its columns (padding rows -> the trash slot)
        packed = padding_prefill(P, L, mb, S)
        tokens, lengths, tables_p, slots, temp, topk = unpack_prefill(
            packed, mb)
        for i, r in enumerate(cands):
            plen = len(r.prompt)
            tokens[i, :plen] = r.prompt
            lengths[i] = plen
            tables_p[i, :len(r.blocks)] = r.blocks
            slots[i] = r.slot
            temp[i] = r.temperature
            topk[i] = r.top_k
        self._phase("admit_batch", t_phase)
        plens = lengths[:len(cands)].astype(np.int64)
        live_tokens = int(plens.sum())
        extra = {}
        if coh.ps.windowed:
            # keys a sliding-window layer needs: row t sees min(t + 1, W)
            inside = np.minimum(plens, coh.ps.spec.window)
            extra["attn_window_key_rows"] = int(
                (inside * (inside + 1) // 2
                 + (plens - inside) * coh.ps.spec.window).sum())
        if coh.ps.sparse:
            # what the block-sparse layers' lists name, and what they are
            # scored against (a layer's; host integers from the lengths)
            sel_rows, index_rows = selection_rows(plens,
                                                  coh.ps.spec.selection)
            extra["attn_selected_key_rows"] = sel_rows
            extra["attn_index_rows"] = index_rows
        if coh.ps.n_rec:
            extra["linear_rows"] = live_tokens
        with span("generation.prefill", model=self.name, batch=len(cands),
                  rung=L, rows=P, tokens=live_tokens,
                  padded_tokens=P * L,
                  # keys causal attention needs a layer: row t of a prompt
                  # sees t + 1 of them
                  attn_key_rows=int((plens * (plens + 1) // 2).sum()),
                  head_rows=coh.ps.head_rows.get((P, L)),
                  sampled=int(np.count_nonzero(temp > 0.0)), **extra) as sp:
            begun = self._pass_begin()
            first, coh.cache, self._key = coh.ps.run_prefill(
                coh.cache, packed, self._key)
            first, stats = coh.ps.split_stats(first)
            if stats is not None:
                # the expert layers' routing, read back with the tokens:
                # pairs from live and from padding positions (host
                # integers), the fullest expert's pairs and the experts
                # touched, over the model's expert layers
                pairs = coh.ps.spec.n_moe * coh.ps.spec.moe_top_k
                sp.set_attr("moe_pairs", live_tokens * pairs)
                sp.set_attr("moe_pairs_padded", (P * L - live_tokens) * pairs)
                sp.set_attr("moe_load_max", int(stats[0]))
                sp.set_attr("experts_touched", int(stats[1]))
            cost = self._pass_end(sp, begun)
        self._note_pass("prefill", sp, cost)
        if coh.ps.prefix_skipped_stateful:
            self.metrics.record_prefix_skipped_stateful(len(cands))
        if coh.ps.prefix_skipped_windowed:
            self.metrics.record_prefix_skipped_windowed(len(cands))
        t_phase = time.perf_counter()
        now = time.monotonic()
        emitted = 0
        for i, r in enumerate(cands):
            s = r.slot
            r.rung, r.batch = int(L), len(cands)
            coh.slots.add(s)
            coh.tables[s] = tables_p[i]
            self._pos[s] = len(r.prompt)
            self._temp[s] = r.temperature
            self._topk[s] = r.top_k
            self._host_known[s] = True      # the first token, read just now
            if coh.prefix is not None:
                self.metrics.record_prefix_miss()
                # the prompt's full blocks are immutable from here on:
                # index them so the next identical prefix skips this
                # prefill; custody of the registered blocks moves to the
                # cache (released at finish, not freed)
                managed = coh.prefix.register(r.prompt, tables_p[i],
                                              r.blocks)
                if managed:
                    drop = set(managed)
                    r.blocks = [b for b in r.blocks if b not in drop]
                    r.shared_blocks.extend(managed)
            if r.trace_id is not None:
                event("generation.prefill", trace_id=r.trace_id,
                      model=self.name, slot=s, rung=int(L),
                      batch=len(cands),
                      ttft_ms=round((now - r.enqueue_t) * 1e3, 3))
            did_emit, _ = self._slot_emit(coh, r, int(first[i]), now)
            emitted += did_emit
        self.metrics.record_prefill(
            len(cands), [(now - r.enqueue_t) * 1e3 for r in cands],
            emitted)
        return self._phase("emit", t_phase)

    def _admit_hits(self, coh: _Cohort, hits: List["_GenRequest"]):
        """Cache-hit admission: NO target prefill. The sequence's table
        points at the shared read-only blocks; the unmatched prompt suffix
        replays through the warmed decode program (one token per step,
        teacher-forced), and the first emitted token falls out of the step
        that feeds the final prompt token. Block-aligned full matches COW
        the last shared block first — its final position gets rewritten by
        that feed, and shared blocks are never written."""
        blk = self.config.block_len
        for r in hits:
            s = r.slot
            plen = len(r.prompt)
            cow = 0
            if r.matched_tokens == plen:
                # copy-on-write: table entry m-1 becomes a private copy
                src = r.shared_blocks[-1]
                dst = r.blocks[0]
                coh.cache = coh.ps.run_cow(coh.cache, src, dst)
                coh.prefix.release([src])
                r.shared_blocks = r.shared_blocks[:-1]
                coh.prefix.cow_copies += 1
                self.metrics.record_cow()
                table = r.shared_blocks + [dst] + r.blocks[1:]
                start = plen - 1
                cow = 1
            else:
                table = r.shared_blocks + r.blocks
                start = r.matched_tokens
            row = np.zeros(self.config.blocks_per_seq, np.int32)
            row[:len(table)] = table
            coh.slots.add(s)
            coh.tables[s] = row
            self._pos[s] = start
            self._temp[s] = r.temperature
            self._topk[s] = r.top_k
            self._tokens[s] = int(r.prompt[start])
            self._host_known[s] = True
            self._active[s] = True
            r.replay = deque(int(t) for t in r.prompt[start + 1:])
            r.replaying = True
            self.metrics.record_prefix_hit(start)
            if r.trace_id is not None:
                event("generation.prefix_hit", trace_id=r.trace_id,
                      model=self.name, slot=s,
                      matched_tokens=int(r.matched_tokens),
                      shared_blocks=len(r.shared_blocks) + cow,
                      cow=cow, replay_tokens=plen - start)

    def _draft_prefill(self, coh: _Cohort, cands: List["_GenRequest"]):
        """The draft consumes every admitted FULL prompt (hits included —
        the target skipped its matched span, the draft is cheap and has no
        paged cache to share)."""
        cfg = self.config
        S = cfg.decode_slots
        P = cfg.prefill_rung(len(cands))
        L = cfg.prompt_rung(max(len(r.prompt) for r in cands))
        tokens = np.zeros((P, L), np.int32)
        lengths = np.ones(P, np.int32)
        slots = np.full(P, S, np.int32)
        for i, r in enumerate(cands):
            plen = len(r.prompt)
            tokens[i, :plen] = r.prompt
            lengths[i] = plen
            slots[i] = r.slot
        coh.draft_cache = coh.ps.run_draft_prefill(coh.draft_cache, tokens,
                                                   lengths, slots)

    def _step(self):
        for coh in list(self._cohorts):
            live = [s for s in sorted(coh.slots) if self._active[s]]
            if not live and coh.unread is None:
                continue
            # speculative slots (greedy, past replay) advance through
            # draft-propose + one batched verify; everything else —
            # spec disabled, sampling requests, prompt-suffix replay —
            # rides the plain one-token decode program
            spec_on = coh.ps.spec_k > 0
            plain = [s for s in live
                     if not spec_on or not self._slot_req[s].spec
                     or self._slot_req[s].replaying]
            specs = [s for s in live if s not in set(plain)]
            if plain or coh.unread is not None:
                self._plain_step(coh, plain)
            if specs:
                self._spec_step(coh, specs)
        if self._det is not None:
            self.metrics.record_recompile(self._det.count)
        # drop drained cohorts (old params/pools released); one whose last
        # step is still to be read has not drained
        self._cohorts = [c for c in self._cohorts
                         if c.slots or c.unread is not None
                         or c.ps is self.active_ps]
        if not self._slot_req:
            self._check_quiesce()

    def _plain_step(self, coh: _Cohort, live: List[int]):
        """One pass of a cohort's decode pipeline: launch the next step
        for ``live``, THEN read the step launched a pass ago and emit it.
        The ``generation.decode_step`` span covers the pass and carries
        the attributes of the step it READS. With nothing unread (the
        first step after an idle period or a drain; every step of a
        cohort that speculates) the step to read is launched here first,
        and a speculating cohort launches nothing behind it. The span
        also says what the pass waited for: ``step`` and ``launched`` (the
        step read, and the one launched behind it or -1), its children's
        durations and, every ``_USAGE_EVERY``-th step, the loop thread's
        CPU time and involuntary context switches so far."""
        step, coh.unread = coh.unread, None
        # the step this pass reads: the unread one, or the next to launch
        number = self._step_no + 1 if step is None else step.number
        with span("generation.decode_step", annotate=("step",),
                  model=self.name, step=number) as sp:
            begun = self._pass_begin(cpu=False)
            if step is None:
                step = self._launch_step(coh, live, None)
                live = [s for s in live if s in coh.slots]
            if live and not coh.ps.spec_k:
                coh.unread = self._launch_step(coh, live, step)
            for k, v in step.attrs.items():
                sp.set_attr(k, v)
            sp.set_attr("launched",
                        -1 if coh.unread is None else coh.unread.number)
            nxt, stats = coh.ps.split_stats(
                coh.ps.read_decode(step.tokens, step.number))
            if stats is not None:
                # the experts' routing arrives with the read, so all of a
                # step's attributes sit on the one span
                sp.set_attr("moe_pairs", len(step.pairs) * coh.ps.spec.n_moe
                            * coh.ps.spec.moe_top_k)
                sp.set_attr("experts_touched", int(stats[1]))
            if step.number % _USAGE_EVERY == 0:
                self._sample_usage(sp)
            cost = self._pass_end(sp, begun)
        self._note_pass("decode_step", sp, cost, step.number)
        t_phase = time.perf_counter()
        dt_ms = sp.dur_ms
        now = time.monotonic()
        emitted = overrun = 0
        for s, r, what in step.pairs:
            r.steps += 1
            if what != _REPLAY:
                r.unread -= 1
            if r.stream.done:
                # finished at the read before this one, by a fact the
                # host learned after this step's launch: the token the
                # slot ran over is dropped (its write lies inside the
                # slot's own reserved pages, at a position no one reads)
                if what != _REPLAY and r.stream.finish_reason == "stop":
                    overrun += 1
                continue
            if r.trace_id is not None:
                # one event per decode step the request participated
                # in — the per-request timeline's heartbeat
                event("generation.decode_step", trace_id=r.trace_id,
                      model=self.name, slot=s, token_index=r.emitted,
                      step_ms=round(dt_ms, 3))
            if what == _REPLAY:
                # a mid-prompt prediction: discarded (the next prompt
                # token was teacher-forced at the launch)
                self._closed_by_host(coh, r, now, "while replaying the "
                                     "prompt suffix")
                continue
            if what == _REPLAY_LAST:
                self.metrics.record_cached_first_token(
                    (now - r.enqueue_t) * 1e3)
            did_emit, cont = self._slot_emit(coh, r, int(nxt[s]), now)
            emitted += did_emit
            if cont and coh.unread is None:
                # nothing launched behind this step: the next one feeds
                # this token from the host
                self._host_known[s] = True
        self.metrics.record_decode_step(
            dt_ms, len(step.pairs), emitted,
            slots=self.config.decode_slots,
            queue_depth=len(self._queue),
            overlapped=step.attrs["overlapped"], overrun=overrun)
        self._phase("emit", t_phase)

    def _launch_step(self, coh: _Cohort, live: List[int],
                     prev: Optional[_Step]) -> _Step:
        """Launch one decode step for ``live`` behind ``prev`` (the
        cohort's unread step, whose tokens the rows the host does not know
        take on the device): pack the host's arrays as they stand into
        the step's one host array, launch, and advance the host's state
        to where the NEXT launch starts: positions, replayed prompt
        tokens, and the slots whose last token by count this step
        samples."""
        cfg = self.config
        S = cfg.decode_slots
        mask = np.zeros(S, np.bool_)
        mask[live] = True
        # ``overlapped``: the device still held an unread step at this launch
        attrs = {"slots": len(live), "overlapped": int(prev is not None)}
        if coh.ps.adapter == "paged":
            # what the step attends to against what it reads per layer:
            # each live slot's valid positions (this step's included), and
            # the whole pages that hold them, which the attention kernel
            # fetches through the table; the int8 tier still gathers every
            # slot's whole table
            seen = self._pos[live] + 1
            blk = cfg.block_len
            attrs.update(
                live_tokens=int(seen.sum()),
                gathered_tokens=S * cfg.capacity if coh.ps.kv_quantized
                else int((-(-seen // blk) * blk).sum()),
                # one layer's row of one token, as the pools lay it out
                cache_row_bytes=coh.ps.cache_row_bytes())
            if coh.ps.windowed:
                # the rows a sliding-window layer attends to this step
                attrs["window_tokens"] = int(
                    np.minimum(seen, coh.ps.spec.window).sum())
            if coh.ps.sparse:
                # the rows a block-sparse layer attends to this step (its
                # chosen pages, the slot's own up to its position) and the
                # compressed keys it scores them from
                sel = coh.ps.spec.selection
                attrs["selected_tokens"] = int(
                    selected_keys(seen - 1, sel).sum())
                attrs["index_rows"] = int(
                    np.maximum((seen - sel.kernel) // sel.stride + 1,
                               0).sum())
            if coh.ps.n_rec:
                # recurrent states read and written this step, every kind
                attrs["state_bytes"] = len(live) \
                    * coh.ps.state_bytes_per_slot()
        # a slot keeps its last request's temperature after it finishes,
        # and another cohort's slots are not this step's: only the live
        # rows may decide whether the program's sampler draws
        temp = np.where(mask, self._temp, np.float32(0.0))
        attrs["sampled"] = int(np.count_nonzero(temp > 0.0))
        self._step_no += 1
        number = self._step_no
        # the launch may still read its host array after it returns, and
        # this loop writes its own arrays before the step has run: the
        # packed array is fresh, and nobody writes it
        tokens, coh.cache, self._key = coh.ps.launch_decode(
            coh.cache,
            pack_decode(self._tokens, self._host_known, self._pos,
                        coh.tables, mask, temp, self._topk),
            None if prev is None else prev.tokens, self._key, number)
        pairs = []
        for s in live:
            r = self._slot_req[s]
            self._pos[s] += 1        # whatever the token's value
            if r.replay:
                self._tokens[s] = r.replay.popleft()    # stays the host's
                pairs.append((s, r, _REPLAY))
                continue
            r.unread += 1
            self._host_known[s] = False
            what = _EMIT
            if r.replaying:
                what = _REPLAY_LAST
                self._replay_done(coh, r)
            pairs.append((s, r, what))
            if r.emitted + r.unread >= r.max_new:
                # the token in flight is the slot's last by count: the
                # next launch leaves it out and its slot and pages go
                # back NOW (what reuses them is queued behind this step);
                # the stream finishes where the token is delivered
                self._release(coh, r, early=True)
        return _Step(number, tokens, pairs, attrs)

    def _replay_done(self, coh: _Cohort, r: "_GenRequest") -> None:
        """The step that feeds a cache hit's FINAL prompt token is
        launched: full prompt blocks beyond the matched span are valid for
        whatever is queued behind it. Index them so the NEXT request
        extends the cached chain."""
        r.replaying = False
        if coh.prefix is None:
            return
        managed = coh.prefix.register(r.prompt, coh.tables[r.slot], r.blocks)
        if managed:
            drop = set(managed)
            r.blocks = [b for b in r.blocks if b not in drop]
            r.shared_blocks.extend(managed)
        self.metrics.set_prefix_gauges(coh.prefix.stats())

    def _spec_step(self, coh: _Cohort, specs: List[int]):
        """Draft proposes k tokens per slot; ONE batched target pass
        verifies; the longest agreeing prefix + the target's correction
        token are emitted — plain-greedy-identical output, up to k+1
        tokens per target dispatch."""
        from .speculative import accept_greedy
        cfg = self.config
        S, k = cfg.decode_slots, coh.ps.spec_k
        mask = np.zeros(S, np.bool_)
        mask[specs] = True
        with span("generation.verify", model=self.name, slots=len(specs),
                  k=k) as sp:
            begun = self._pass_begin()
            props, aux = coh.ps.run_propose(
                coh.draft_cache, self._tokens, self._pos, mask)
            if coh.ps.draft_adapter == "dense":
                coh.draft_cache = aux
            feeds = np.concatenate(
                [self._tokens[:, None], props], axis=1).astype(np.int32)
            targets, coh.cache = coh.ps.run_verify(
                coh.cache, feeds, self._pos, coh.tables, mask)
            cost = self._pass_end(sp, begun)
        self._note_pass("verify", sp, cost)
        t_phase = time.perf_counter()
        dt_ms = sp.dur_ms
        counts, emitted_toks = accept_greedy(props, targets)
        now = time.monotonic()
        emitted = 0
        accepted = 0
        cont_mask = np.zeros(S, np.bool_)
        rewind_idx = np.ones(S, np.int32)
        for s in specs:
            r = self._slot_req[s]
            r.steps += 1
            if r.trace_id is not None:
                event("generation.verify", trace_id=r.trace_id,
                      model=self.name, slot=s, token_index=r.emitted,
                      proposed=k, accepted=int(counts[s]),
                      step_ms=round(dt_ms, 3))
            accepted += int(counts[s])
            n_emit, cont = 0, False
            for tok in emitted_toks[s]:
                did, cont = self._slot_emit(coh, r, int(tok), now)
                n_emit += did
                if not cont:
                    break
            emitted += n_emit
            if cont:
                self._pos[s] += n_emit
                cont_mask[s] = True
                rewind_idx[s] = n_emit
        if coh.ps.draft_adapter == "state":
            # commit, per continuing slot, the draft state matching what
            # verify accepted (s_{j+1} = after the j-th accepted proposal)
            coh.draft_cache = coh.ps.run_rewind(
                coh.draft_cache, aux, rewind_idx, cont_mask)
        self.metrics.record_verify(
            dt_ms, len(specs), proposed=k * len(specs), accepted=accepted,
            emitted=emitted, slots=S,
            queue_depth=len(self._queue))
        self._phase("emit", t_phase)

    def _check_quiesce(self):
        """Block-accounting invariant at quiesce (no in-flight requests;
        an unread step counts as in flight): every allocated block is
        exactly a cached block (refcounted owner
        refs are gone, so cached == prefix index incl. its LRU). A
        violation is a leak or a double-custody bug — fail loudly (the
        loop's defensive except turns this into _fail_all + a flight
        dump) rather than serving corrupt shared state."""
        for coh in self._cohorts:
            if coh.ps.adapter != "paged" or coh.slots \
                    or coh.unread is not None:
                continue
            alloc = set(coh.allocator.allocated)
            cached = (coh.prefix.cached_block_ids()
                      if coh.prefix is not None else set())
            if alloc != cached:
                raise RuntimeError(
                    f"block accounting violated at quiesce for model "
                    f"'{self.name}': leaked={sorted(alloc - cached)} "
                    f"phantom={sorted(cached - alloc)}")

    def _slot_emit(self, coh: _Cohort, r: _GenRequest, tok: int,
                   now: float):
        """Handle one sampled token for a slot: emit/terminate. Returns
        (emitted, continuing)."""
        if self._closed_by_host(coh, r, now, "mid-generation after "
                                f"{r.emitted} tokens"):
            return (0, False)
        if tok in r.stop:
            return self._finish_slot(coh, r, "stop")
        r.stream._put(tok)
        r.emitted += 1
        if r.first_t is None:
            r.first_t = now
        r.last_t = now
        if r.emitted >= r.max_new:
            out = self._finish_slot(coh, r, "length")
            return (1, out[1])
        self._tokens[r.slot] = tok
        self._active[r.slot] = True
        return (1, True)

    def _closed_by_host(self, coh: _Cohort, r: _GenRequest, now: float,
                        where: str) -> bool:
        """Cancellation and the deadline are facts of the host: they act
        where a step is read. True when one of them finished ``r``."""
        if r.cancelled:
            # a shutdown-cancel must surface as an ERROR to blocking
            # callers (engine stopped under them); a consumer cancel is a
            # normal close
            err = GenerationClosedError("engine stopped mid-generation") \
                if r.cancel_reason == "shutdown" else None
            self._finish_slot(coh, r, r.cancel_reason, err)
            return True
        if now > r.deadline:
            self._finish_slot(coh, r, "deadline", DeadlineExceededError(
                f"deadline expired {where}"))
            return True
        return False

    def _finish_slot(self, coh: _Cohort, r: _GenRequest, reason: str,
                     error: Optional[BaseException] = None):
        """Deliver the end of a request's stream and, unless the launch of
        its last step already did, give its slot and pages back."""
        self._end(r, reason, error)
        if r.trace_id is not None:
            event("generation.finish", trace_id=r.trace_id,
                  model=self.name, slot=r.slot, reason=reason,
                  tokens=r.emitted)
        self.metrics.record_finish(reason)
        if r in self._early:
            with self._cond:
                self._early.discard(r)
                self._cond.notify_all()
        else:
            self._release(coh, r)
        return (0, False)

    def _release(self, coh: _Cohort, r: _GenRequest, early: bool = False):
        """Give a request's slot and pages back. ``early``: at the launch
        of its last step, with the token still to be delivered."""
        s = r.slot
        if r.blocks:
            coh.allocator.free(r.blocks)
            r.blocks = []
        if r.shared_blocks:
            # cache-custody blocks: drop this sequence's reference;
            # refcount-0 blocks park in the LRU for the next identical
            # prefix (eviction under pool pressure frees them)
            coh.prefix.release(r.shared_blocks)
            r.shared_blocks = []
        if coh.prefix is not None:
            self.metrics.set_prefix_gauges(coh.prefix.stats())
        coh.slots.discard(s)
        self._active[s] = False
        with self._cond:
            del self._slot_req[s]
            self._slots_free.add(s)
            if early:
                self._early.add(r)
            self._cond.notify_all()

    def _fail_all(self, exc: BaseException):
        """A dispatch-side failure must resolve every caller (the batcher
        contract): fail queued + in-flight (the callers of an unread step
        whose slot went back at its launch among them: a device error
        surfaces at the read of step k with k+1 launched, and fails
        both), release blocks/slots.
        Iterates ``_slot_req`` (not cohort slot sets) so requests whose
        PREFILL raised — admitted but never added to a cohort's slots —
        are failed too instead of hanging their callers. Every cohort is
        dropped: after a program failure its cache may reference donated
        (invalidated) buffers, so the next admission must build a fresh
        pool."""
        self.metrics.record_rejection("error")
        with self._cond:
            queued = list(self._queue)
            self._queue.clear()
            reqs = self._admitted()
        in_flight = len(reqs)
        for r in queued:
            self._end(r, "error", exc)
        for r in reqs:
            self._finish_slot(r.cohort, r, "error", exc)
        self._cohorts = []
        # black box AFTER resolving every caller (a slow dump write must
        # never delay their failure); the ring still holds the
        # spans/events — and trace ids — leading up to the failure
        get_flight_recorder().dump(
            "generation_error", model=self.name, error=str(exc),
            error_type=type(exc).__name__, in_flight=in_flight,
            queued=len(queued))

    def _shutdown_flush(self):
        err = DrainingError(f"generation model '{self.name}' stopped")
        with self._cond:
            queued = list(self._queue)
            self._queue.clear()
            reqs = self._admitted()
        for r in queued:
            self._end(r, "shutdown", err)
            self.metrics.record_finish("shutdown")
        for r in reqs:
            self._finish_slot(r.cohort, r, "shutdown",
                              GenerationClosedError(
                                  "engine stopped mid-generation"))
        self._cohorts = []

    # ------------------------------------------------------------- lifecycle
    def stop(self, drain: bool = True, timeout: float = 10.0):
        """drain=True: refuse new work (503) but let queued + in-flight
        generations COMPLETE (bounded by ``timeout``); drain=False: refuse
        new work and terminate everything now. Either way every stream is
        finished — no caller is left hanging."""
        with self._cond:
            self._draining = True
            if not drain:
                for r in list(self._queue):
                    self._end(r, "shutdown", DrainingError(
                        f"model '{self.name}' shut down before admission"))
                self._queue.clear()
                for r in self._admitted():
                    r.cancelled = True
                    r.cancel_reason = "shutdown"
            self._cond.notify_all()
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline and \
                (self._queue or self.in_flight):
            time.sleep(0.005)
        with self._cond:
            self._stopped = True
            self._cond.notify_all()
        self._thread.join(timeout=5.0)
        self._shutdown_flush()    # belt-and-braces if the thread wedged
        self._gc.close()
