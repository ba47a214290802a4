"""Solver: the outer training loop.

Reference: optimize/Solver.java:43 dispatching to
optimize/solvers/StochasticGradientDescent.java:58-100 (gradientAndScore ->
updater -> step -> listeners), and MultiLayerNetwork.fit's epoch/minibatch
loop (MultiLayerNetwork.java:1076-1182) with async prefetch (:1080-1083).

TPU-first: gradient+updater+apply is ONE jitted, buffer-donated XLA program
per minibatch (the reference's per-layer host orchestration disappears).
The iteration counter is a traced scalar so LR schedules don't trigger
recompiles.
"""
from __future__ import annotations

import logging
import os
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..datasets.dataset import DataSet, ListDataSetIterator
from ..datasets.prefetch import (BatchWindow, DevicePrefetchIterator,
                                 iter_windows, skip_batches)
from ..telemetry import device_memory_gauges, get_registry, span
from .listeners import PerformanceListener, TrainingListener

log = logging.getLogger("deeplearning4j_tpu")


def train_step_math(net, params, state, opt_state, it, rng, x, y,
                    lmask=None, fmask=None, grad_sync=None, update_fn=None,
                    with_health=False):
    """THE single-step update: loss+grads -> updater -> new carry. Every
    SGD-path program — Solver per-step and scan-window, ParallelWrapper
    sync per-step and sync window — traces exactly this function, so the
    'fused window is bit-identical to K per-step dispatches' contract is
    structural, not convention.

    ``grad_sync``: optional cross-worker combine applied to the raw grad
    pytree between backward and updater (ParallelWrapper's bucketed
    overlap path passes ``overlap.bucketed_pmean`` with its schedule
    here, under shard_map; the ZeRO path passes its reduce-scatter).
    ``update_fn``: optional replacement for ``net.updater.update`` with
    the same ``(grads, opt_state, params, it) -> (params, opt_state)``
    signature — the ZeRO engine's sharded update plugs in here, and
    receives whatever ``grad_sync`` produced (the full tree, or its
    local gradient shards). Both seams live in THIS function so the
    fused scan window carries the same sync + update structure as the
    per-step path — structurally, not by convention.

    ``with_health=True`` (the armed TrainingWatch, telemetry/slo.py)
    additionally returns a [3] f32 health vector — loss, grad-norm²,
    non-finite count — computed INSIDE this same program on the PRE-sync
    local grads, so watching costs zero extra dispatches and zero host
    syncs (the watch materializes it on its own worker thread at window
    boundaries). The params/opt math is untouched either way."""
    # ``loss`` and ``updater`` are the top-level scopes of the step's
    # operations in a device trace; the graph's vertices nest under ``loss``
    def lf(p):
        with jax.named_scope("loss"):
            return net.loss_fn(p, state, x, y, train=True, rng=rng,
                               labels_mask=lmask, features_mask=fmask)
    (loss, new_state), grads = jax.value_and_grad(lf, has_aux=True)(params)
    health = None
    if with_health:
        from ..telemetry.slo import training_health_vec
        health = training_health_vec(loss, grads)
    if grad_sync is not None:
        grads = grad_sync(grads)
    update = net.updater.update if update_fn is None else update_fn
    with jax.named_scope("updater"):
        new_params, new_opt = update(grads, opt_state, params, it)
    if with_health:
        return new_params, new_state, new_opt, loss, health
    return new_params, new_state, new_opt, loss


def _feed_sig(*feeds) -> tuple:
    """Cheap hashable shape/dtype signature of the per-batch feed arrays
    (params/state shapes are fixed per net, so the feed alone keys a
    distinct XLA program) — the dedupe key for one-time cost capture."""
    sig = []
    for t in feeds:
        if t is None:
            continue
        for leaf in (t if isinstance(t, (list, tuple)) else (t,)):
            sig.append((tuple(leaf.shape), str(leaf.dtype)))
    return tuple(sig)


class Solver:
    def __init__(self, net):
        self.net = net
        self._steps = {}
        self._cost_counts = {}      # (path, feed-sig) -> steps dispatched
        # one capture attempt per path per solver: after it, the
        # per-iteration accounting cost drops to one attribute check
        # (a second feed shape's program is deliberately not captured —
        # the hot program is the one whose MFU matters)
        self._win_cost_done = False
        self._step_cost_done = False

    # -------------------------------------------------------------- step fns
    def _get_step(self, has_lmask: bool, has_fmask: bool,
                  health: bool = False):
        key = (has_lmask, has_fmask, health)
        if key in self._steps:
            return self._steps[key]
        net = self.net

        def step(params, state, opt_state, it, rng, x, y, lmask=None, fmask=None):
            return train_step_math(net, params, state, opt_state, it, rng,
                                   x, y, lmask, fmask, with_health=health)

        self._steps[key] = jax.jit(step, donate_argnums=(0, 2))
        return self._steps[key]

    def _get_window_step(self, has_lmask: bool, has_fmask: bool,
                         health: bool = False):
        """ONE jitted, buffer-donated lax.scan program for a K-step window:
        params/state/opt_state as carry, stacked [K, ...] batches as xs,
        per-step losses as ys. The scan body is the same math as
        ``_get_step`` (fold_in(base_rng, it) -> value_and_grad ->
        updater.update at iteration ``it``), so K fused steps are
        bit-identical to K sequential dispatches (gradients always;
        in pure-f32 runs a stateful updater's elementwise chain may fuse
        differently in the scan body — <= 1 ulp per step, same math);
        the window amortizes the per-step Python dispatch to one host
        round-trip per window.
        K itself is not part of the cache key — scan length comes from
        the stacked shapes (XLA recompiles per distinct K, as it would
        per distinct batch shape). ``health=True`` stacks the per-step
        [3] health vectors as a second scan output ([K, 3] — the armed
        TrainingWatch's window flush reads them off-thread)."""
        key = ("window", has_lmask, has_fmask, health)
        if key in self._steps:
            return self._steps[key]
        net = self.net

        def window_step(params, state, opt_state, it0, base_rng, xs, ys,
                        lmasks=None, fmasks=None):
            seq = (xs, ys) \
                + ((lmasks,) if has_lmask else ()) \
                + ((fmasks,) if has_fmask else ())

            def body(carry, inp):
                params, state, opt_state, it = carry
                x, y = inp[0], inp[1]
                lm = inp[2] if has_lmask else None
                fm = inp[2 + int(has_lmask)] if has_fmask else None
                rng = jax.random.fold_in(base_rng, it)
                out = train_step_math(
                    net, params, state, opt_state, it, rng, x, y, lm, fm,
                    with_health=health)
                new_params, new_state, new_opt = out[0], out[1], out[2]
                ys_out = (out[3], out[4]) if health else out[3]
                return (new_params, new_state, new_opt, it + 1), ys_out

            (params, state, opt_state, _), scanned = jax.lax.scan(
                body, (params, state, opt_state, it0), seq)
            if health:
                losses, healths = scanned
                return params, state, opt_state, losses, healths
            return params, state, opt_state, scanned

        self._steps[key] = jax.jit(window_step, donate_argnums=(0, 2))
        return self._steps[key]

    def _get_tbptt_step(self, has_lmask: bool, has_fmask: bool, chunk_len: int):
        """Jitted tBPTT chunk step: optimize on one chunk, carry recurrent
        state (stop-gradient across the chunk boundary — reference
        doTruncatedBPTT, MultiLayerNetwork.java:1312)."""
        key = ("tbptt", has_lmask, has_fmask, chunk_len)
        if key in self._steps:
            return self._steps[key]
        net = self.net

        def step(params, state, opt_state, rnn_states, it, rng, x, y,
                 lmask=None, fmask=None):
            def lf(p):
                loss, (new_state, rnn_out) = net.loss_fn(
                    p, state, x, y, train=True, rng=rng, labels_mask=lmask,
                    features_mask=fmask, rnn_states=rnn_states,
                    collect_rnn_states=True)
                return loss, (new_state, rnn_out)
            (loss, (new_state, rnn_out)), grads = \
                jax.value_and_grad(lf, has_aux=True)(params)
            new_params, new_opt = net.updater.update(grads, opt_state, params, it)
            rnn_out = jax.lax.stop_gradient(rnn_out)
            return new_params, new_state, new_opt, rnn_out, loss

        self._steps[key] = jax.jit(step, donate_argnums=(0, 2))
        return self._steps[key]

    def _fit_tbptt_batch(self, x, y, lmask, fmask, base_rng):
        """Chunked tBPTT over the time axis. Works for single-array MLN data
        and for ComputationGraph multi-input/multi-output lists (reference
        MultiLayerNetwork.doTruncatedBPTT :1312; ComputationGraph tBPTT branch
        :908): time-series arrays ([B,T,F], and [B,T] masks) are chunked;
        static 2-D inputs/labels are fed whole to every chunk."""
        net = self.net
        time_lens = [v.shape[1] for v in (x if isinstance(x, list) else [x])
                     if v.ndim == 3]
        # a seq2seq graph can have only static 2-D inputs with time-series
        # LABELS (DuplicateToTimeSeriesVertex expands them); chunk by those
        time_lens += [v.shape[1] for v in (y if isinstance(y, list) else [y])
                      if v is not None and v.ndim == 3]
        if not time_lens:
            raise ValueError("tBPTT requires at least one [B,T,F] time-series "
                             "input or label")
        if len(set(time_lens)) > 1:
            raise ValueError(
                f"tBPTT requires all time-series inputs/labels to share one "
                f"sequence length, got {sorted(set(time_lens))} (chunking "
                f"mixed-length sequences would misalign the carry)")
        T = time_lens[0]
        k = net.conf.tbptt_fwd_length

        def ch3(v, t0, t1):      # features/labels: chunk 3-D time series only
            if isinstance(v, list):
                return [ch3(u, t0, t1) for u in v]
            return v[:, t0:t1] if (v is not None and v.ndim == 3) else v

        def chm(m, t0, t1):      # [B,T] per-timestep masks
            if isinstance(m, list):
                return [chm(u, t0, t1) for u in m]
            return m[:, t0:t1] if (m is not None and m.ndim == 2) else m

        rnn_states = None
        loss = None
        for t0 in range(0, T, k):
            t1 = min(t0 + k, T)
            xc = ch3(x, t0, t1)
            yc = ch3(y, t0, t1)
            lc = chm(lmask, t0, t1)
            fc = chm(fmask, t0, t1)
            step_fn = self._get_tbptt_step(lc is not None, fc is not None, t1 - t0)
            rng = jax.random.fold_in(base_rng, net.iteration_count)
            kwargs = {}
            if lc is not None:
                kwargs["lmask"] = lc
            if fc is not None:
                kwargs["fmask"] = fc
            net.params, net.state, net.opt_state, rnn_states, loss = step_fn(
                net.params, net.state, net.opt_state, rnn_states,
                jnp.asarray(net.iteration_count, jnp.int32), rng, xc, yc, **kwargs)
            net.iteration_count += 1
        return loss

    # ------------------------------------------------------------------- fit
    def fit(self, data=None, labels=None, *, epochs=1, batch_size=None,
            iterator=None, dataset=None, async_prefetch: bool = True,
            prefetch_depth: int = 2, steps_per_dispatch: int = 1,
            skip_first_batches: int = 0):
        net = self.net
        if net.params is None:
            net.init()
        if steps_per_dispatch < 1:
            raise ValueError("steps_per_dispatch must be >= 1")
        if skip_first_batches < 0:
            raise ValueError("skip_first_batches must be >= 0")
        tbptt = net.conf.backprop_type == "tbptt"
        algo = getattr(net.conf, "optimization_algorithm", "sgd")
        if algo in ("sgd", "stochastic_gradient_descent"):
            algo = "sgd"      # reference enum name STOCHASTIC_GRADIENT_DESCENT
        second_order = None
        if algo and algo != "sgd":
            if tbptt:
                raise ValueError("tBPTT is an SGD-path feature; second-order "
                                 "solvers run full-sequence batches")
            if not hasattr(self, "_second_order") or self._second_order is None:
                from .second_order import make_optimizer
                self._second_order = make_optimizer(
                    algo, net,
                    getattr(net.conf, "max_num_line_search_iterations", 5))
            second_order = self._second_order
        if iterator is None:
            if dataset is not None:
                iterator = ListDataSetIterator([dataset])
            elif _is_multi(data) or _is_multi(labels):
                # multi-input/multi-output (MultiDataSet-style); no batching split
                iterator = ListDataSetIterator([DataSet(data, labels)])
            else:
                features = np.asarray(data)
                labels = np.asarray(labels)
                bs = batch_size or features.shape[0]
                iterator = ListDataSetIterator(features=features, labels=labels,
                                               batch_size=bs)
        # Device-side prefetch (datasets/prefetch.py): a background thread
        # pulls + host-prepares batch N+1 AND ships it to the device while
        # step N computes, so the host->device transfer overlaps device
        # compute (the reference's AsyncDataSetIterator overlapped only the
        # host half). A caller-supplied DevicePrefetchIterator (e.g. with a
        # mesh sharding) is used as-is.
        if isinstance(iterator, DevicePrefetchIterator):
            it_wrapped = iterator
        elif async_prefetch and prefetch_depth >= 1:
            it_wrapped = DevicePrefetchIterator(iterator, prefetch_depth,
                                                dtype=net.conf.dtype)
        else:     # prefetch_depth < 1 opts out, same as async_prefetch=False
            it_wrapped = iterator
        prefetcher = (it_wrapped if isinstance(it_wrapped, DevicePrefetchIterator)
                      else None)
        dtype = jnp.dtype(net.conf.dtype)
        base_rng = jax.random.PRNGKey(net.conf.seed + 7919)
        perf = [l for l in net.listeners if isinstance(l, PerformanceListener)]
        # Fused multi-step dispatch (steps_per_dispatch=K): K prefetched
        # device-resident batches run through ONE jitted lax.scan program,
        # so an epoch costs O(num_windows) host round-trips instead of
        # O(num_steps). tBPTT and second-order solvers keep the per-step
        # path (their step structure is not a fixed-shape scan body);
        # ragged remainder windows and unstackable batches fall back
        # per-step inside iter_windows.
        fused_k = steps_per_dispatch
        if fused_k > 1 and (tbptt or second_order is not None):
            log.debug("steps_per_dispatch=%d ignored: %s path is per-step",
                      fused_k, "tbptt" if tbptt else "second-order")
            fused_k = 1

        # Telemetry (telemetry/): structured fit -> epoch -> window|step ->
        # dispatch spans plus iteration/window counters. Every span is pure
        # host bookkeeping (two clock reads, one dict) — nothing here can
        # add a device sync, and a disabled registry short-circuits to
        # shared no-ops (pinned by the sync-freedom + overhead tier-1
        # tests).
        reg = get_registry()
        # Training-health watch (telemetry/slo.py): when one is armed the
        # SGD step programs carry the in-program health output; tbptt and
        # second-order keep their own step structure and are not watched.
        watch = None
        if not tbptt and second_order is None:
            from ..telemetry.slo import get_training_watch
            watch = get_training_watch()
        # Request tracing: every span/event under this fit carries ONE
        # trace id — the caller's active context (e.g. ElasticTrainer's
        # supervised run) or a fresh one per fit call.
        from ..telemetry.tracecontext import (current_trace_context,
                                              new_trace_context,
                                              use_trace_context)
        if reg.enabled:
            # memory-profiler owner hints (telemetry/memprof.py): label
            # the param tree once per fit so the live-array top-K table
            # attributes these shapes — metadata only, no device reads
            from ..telemetry import memprof
            # opt_state first: SGD-style zero states share (shape, dtype)
            # with their params — later hints win, params is the better
            # label for the collision
            if getattr(net, "opt_state", None) is not None:
                memprof.tag(net.opt_state, "opt_state")
            memprof.tag(net.params, "params")
        ctx = current_trace_context()
        with use_trace_context(ctx if ctx is not None
                               else new_trace_context()):
            with span("fit", epochs=epochs, steps_per_dispatch=fused_k,
                      net=type(net).__name__):
                for epoch in range(epochs):
                    with span("epoch", index=epoch):
                        self._fit_epoch(net, it_wrapped, prefetcher,
                                        iterator, dtype, base_rng, perf,
                                        fused_k, tbptt, second_order, reg,
                                        skip=(skip_first_batches
                                              if epoch == 0 else 0),
                                        watch=watch)
        if watch is not None:
            watch.flush()          # end-of-fit is a window boundary too
        return net

    def _fit_epoch(self, net, it_wrapped, prefetcher, iterator, dtype,
                   base_rng, perf, fused_k, tbptt, second_order, reg,
                   skip: int = 0, watch=None):
        for l in net.listeners:
            if isinstance(l, TrainingListener):
                l.on_epoch_start(net)
        # Performance accounting (telemetry/perf.py): one-time cost-model
        # capture per distinct step program (an abstract lower() — no
        # backend compile, no device read) + per-step time decomposition
        # buffered on this thread and flushed/folded into perf.* gauges at
        # window/epoch boundaries. SGD paths only — tbptt/second-order
        # keep their own step structure (same scoping as TrainingWatch).
        acct = cost_index = None
        if reg.enabled and not tbptt and second_order is None:
            from ..telemetry.perf import (StepAccounting,
                                          accounting_enabled,
                                          get_cost_index)
            if accounting_enabled():
                acct = StepAccounting(reg)
                cost_index = get_cost_index()
        # Solver-owned window-dispatch timing: the cost index pairs the
        # captured window program with THIS histogram rather than the
        # span.dispatch_ms one, which ParallelWrapper's dispatch spans
        # also feed — a PW fit in the same process must not pollute the
        # fit program's MFU denominator
        _h_disp = (reg.histogram("perf.fit.dispatch_ms")
                   if acct is not None else None)
        # Capture only once a program has dispatched this many STEPS: the
        # capturing lower() is a full (abstract) retrace — ~0.1s for a
        # tiny net, seconds for a big one — so a short exploratory fit
        # never pays it, while any run long enough for its MFU to matter
        # amortizes it to noise. Lower it (e.g. 1) to capture immediately.
        capture_after = max(1, int(os.environ.get(
            "DL4J_TPU_PERF_CAPTURE_AFTER", "256")))
        # ETL timing (reference lastEtlTime, set in the fit loop
        # MultiLayerNetwork.java:1130 and reported by
        # PerformanceListener.java:111,178): with device prefetch the
        # honest number is the time the consumer BLOCKED waiting for a
        # device-resident batch (zero when the pipeline keeps up);
        # without it, the gap between iterations spent fetching +
        # host-preparing the batch.
        _etl_t0 = time.perf_counter()
        _etl_prev_total = 0.0
        # metric objects hoisted out of the loop: name->object resolution
        # once per epoch, one lock-protected int add per iteration
        _c_iters = reg.counter("train.iterations")
        _c_windows = reg.counter("train.windows")
        # Mid-epoch resume (fit_with_checkpointing / ElasticTrainer): the
        # first `skip` batches of this epoch were already trained by the
        # run that wrote the checkpoint — consume them without dispatching
        # (iteration_count already covers them) so the epoch isn't
        # replayed. Skipping BEFORE windowing keeps the window grid a
        # plain positional grouping of the remaining stream; per-batch
        # math is grouping-invariant (the scan-window contract).
        src = skip_batches(it_wrapped, skip) if skip else iter(it_wrapped)
        if skip:
            _etl_t0 = time.perf_counter()
            if prefetcher is not None:
                _etl_prev_total = prefetcher.total_wait_ms
        stream = iter_windows(src, fused_k) if fused_k > 1 else src
        for item in stream:
            if prefetcher is not None:
                # delta of the cumulative wait covers both a single
                # batch and a K-batch window's worth of feed blocking.
                # When a windowed group falls back to bare batches,
                # the group's whole wait lands on its first batch
                # (iter_windows pulled all K before yielding) — lumpy
                # per-iteration attribution, correct epoch total.
                etl_ms = prefetcher.total_wait_ms - _etl_prev_total
                _etl_prev_total = prefetcher.total_wait_ms
            else:
                etl_ms = (time.perf_counter() - _etl_t0) * 1e3
            if isinstance(item, BatchWindow):
                k = len(item)
                with span("window", k=k, iteration=net.iteration_count):
                    xs, ys, lms, fms = item.stacked(
                        cast=lambda a: cast_feed(a, dtype))
                    step_fn = self._get_window_step(lms is not None,
                                                    fms is not None,
                                                    health=watch is not None)
                    kwargs = {}
                    if lms is not None:
                        kwargs["lmasks"] = lms
                    if fms is not None:
                        kwargs["fmasks"] = fms
                    it0 = net.iteration_count
                    if cost_index is not None and not self._win_cost_done:
                        sig = ("fit-window", id(self), k,
                               _feed_sig(xs, ys, lms, fms))
                        c = self._cost_counts.get(sig, 0) + k
                        self._cost_counts[sig] = c
                        if c - k < capture_after <= c:
                            self._win_cost_done = True
                            # crossed the warm-up threshold: capture now,
                            # BEFORE the dispatch (donation invalidates
                            # params/opt_state buffers after the call)
                            cost_index.maybe_capture(
                                "fit/epoch/window", sig, step_fn,
                                (net.params, net.state, net.opt_state,
                                 jnp.asarray(it0, jnp.int32), base_rng,
                                 xs, ys), kwargs, steps_per_call=k,
                                timing_metric="perf.fit.dispatch_ms")
                    with span("dispatch", k=k) as sp:
                        out = step_fn(net.params, net.state, net.opt_state,
                                      jnp.asarray(it0, jnp.int32),
                                      base_rng, xs, ys, **kwargs)
                    # the span's own interval (0.0 from a disabled
                    # registry, under which nothing below reads it)
                    dispatch_ms = sp.dur_ms
                    if _h_disp is not None:
                        _h_disp.observe(dispatch_ms)
                    net.params, net.state, net.opt_state, losses = out[:4]
                    if watch is not None:
                        # [K, 3] device stack: appended, never read here
                        watch.on_health(it0, out[4], k)
                    device_ms = max(
                        (time.perf_counter() - _etl_t0) * 1e3 - etl_ms, 0.0)
                    _c_windows.inc()
                    _c_iters.inc(k)
                    # per-step listener fan-out: losses[i] is a device
                    # slice — under the deferred-score protocol stock
                    # listeners read back only on their report/flush
                    # cycle, never per dispatched step
                    for p in perf:
                        p.note_window(k)
                    for i, ds in enumerate(item.datasets):
                        for p in perf:
                            p.note_batch(ds.num_examples(),
                                         etl_wait_ms=etl_ms / k,
                                         device_ms=device_ms / k)
                        for l in net.listeners:
                            l.iteration_done(net, net.iteration_count,
                                             losses[i])
                        net.iteration_count += 1
                if acct is not None:
                    wall_ms = (time.perf_counter() - _etl_t0) * 1e3
                    acct.on_step(input_wait_ms=etl_ms,
                                 compute_ms=dispatch_ms,
                                 host_ms=wall_ms - etl_ms - dispatch_ms,
                                 steps=k)
                _etl_t0 = time.perf_counter()
                continue
            ds = item
            dispatch_ms = None
            # ONE span per single-step iteration (the step IS the dispatch
            # here; a nested dispatch span would double the per-iteration
            # telemetry cost on the dispatch-bound path for no extra
            # attribution — the fused window branch keeps the window/
            # dispatch pair because K steps amortize it)
            with span("step", iteration=net.iteration_count):
                x = _cast_any(ds.features, dtype)
                y = _cast_any(ds.labels, dtype)
                lmask = None if ds.labels_mask is None else _cast_any(ds.labels_mask, dtype)
                fmask = None if ds.features_mask is None else _cast_any(ds.features_mask, dtype)
                if second_order is not None:
                    # one outer line-search iteration per minibatch (reference
                    # Solver dispatch, optimize/Solver.java:69-78)
                    loss = second_order.step(x, y, lmask, fmask)
                elif tbptt:
                    loss = self._fit_tbptt_batch(x, y, lmask, fmask,
                                                 base_rng)
                else:
                    step_fn = self._get_step(lmask is not None,
                                             fmask is not None,
                                             health=watch is not None)
                    rng = jax.random.fold_in(base_rng, net.iteration_count)
                    kwargs = {}
                    if lmask is not None:
                        kwargs["lmask"] = lmask
                    if fmask is not None:
                        kwargs["fmask"] = fmask
                    if cost_index is not None and \
                            not self._step_cost_done:
                        sig = ("fit-step", id(self),
                               _feed_sig(x, y, lmask, fmask))
                        c = self._cost_counts.get(sig, 0) + 1
                        self._cost_counts[sig] = c
                        if c == capture_after:
                            self._step_cost_done = True
                            cost_index.maybe_capture(
                                "fit/epoch/step", sig, step_fn,
                                (net.params, net.state, net.opt_state,
                                 jnp.asarray(net.iteration_count,
                                             jnp.int32), rng, x, y),
                                kwargs, steps_per_call=1,
                                timing_metric="perf.step.compute_ms")
                    t_d0 = time.perf_counter()
                    out = step_fn(
                        net.params, net.state, net.opt_state,
                        jnp.asarray(net.iteration_count, jnp.int32),
                        rng, x, y, **kwargs)
                    dispatch_ms = (time.perf_counter() - t_d0) * 1e3
                    net.params, net.state, net.opt_state, loss = out[:4]
                    if watch is not None:
                        watch.on_health(net.iteration_count, out[4], 1)
                # listeners get the index of the last executed iteration
                it_idx = net.iteration_count - 1 if tbptt else net.iteration_count
                # device_ms: the iteration's wall time net of ETL wait —
                # dispatch + device compute (async dispatch lets the host
                # run ahead, so per-iteration values smooth toward the true
                # device time as the in-flight queue saturates)
                device_ms = max(
                    (time.perf_counter() - _etl_t0) * 1e3 - etl_ms, 0.0)
                _c_iters.inc()
                for p in perf:
                    p.note_batch(ds.num_examples(), etl_wait_ms=etl_ms,
                                 device_ms=device_ms)
                for l in net.listeners:
                    l.iteration_done(net, it_idx, loss)
                if not tbptt:
                    net.iteration_count += 1
            if acct is not None and dispatch_ms is not None:
                wall_ms = (time.perf_counter() - _etl_t0) * 1e3
                acct.on_step(input_wait_ms=etl_ms, compute_ms=dispatch_ms,
                             host_ms=wall_ms - etl_ms - dispatch_ms)
            _etl_t0 = time.perf_counter()
        for l in net.listeners:
            if isinstance(l, TrainingListener):
                l.on_epoch_end(net)
        if reg.enabled:
            # device HBM watermark gauges, refreshed once per epoch (host
            # API read; CPU backends fall back to live-array accounting)
            device_memory_gauges(reg)
        if acct is not None:
            # epoch boundary: flush the decomposition buffers, resolve
            # every captured program against its timing histogram and
            # publish the perf.<path>.mfu/.achieved_tflops/... gauges —
            # pure host arithmetic, off the dispatch loop
            acct.flush()
            cost_index.fold(reg)
        if hasattr(iterator, "reset"):
            iterator.reset()

    def _pretrain_graph(self, iterator, epochs: int = 1):
        """ComputationGraph layerwise pretraining (reference
        ComputationGraph.pretrain): for each pretrainable layer vertex, its
        INPUT vertex's activations are the data; XLA dead-code-eliminates the
        unused downstream vertices from the traced feed computation."""
        net = self.net
        dtype = jnp.dtype(net.conf.dtype)
        base_rng = jax.random.PRNGKey(net.conf.seed + 104729)

        for vi, (name, layer) in enumerate(zip(net.vertex_names, net.layers)):
            if not hasattr(layer, "pretrain_loss"):
                continue
            in_name = net.conf.vertex_inputs[name][0]
            vertex = net.vertices[vi]

            @jax.jit
            def pretrain_step(layer_params, full_params, state, opt_state, it,
                              rng, inputs, _vi=vi, _layer=layer, _in=in_name,
                              _vertex=vertex):
                if _in in net.conf.network_inputs:
                    feed = inputs[net.conf.network_inputs.index(_in)]
                else:
                    acts, _ = net.apply_fn(full_params, state, inputs, train=False)
                    feed = acts[_in]
                if getattr(_vertex, "preprocessor", None) is not None:
                    feed = _vertex.preprocessor.apply(feed)

                def lf(p):
                    return _layer.pretrain_loss(p, feed, rng)
                loss, grads = jax.value_and_grad(lf)(layer_params)
                rule = net.updater.rule_for(_layer)
                new_p, new_s = {}, {}
                for k in layer_params:
                    upd, new_s[k] = rule.update_one(grads[k], opt_state[k],
                                                    rule.lr(it), it)
                    new_p[k] = layer_params[k] - upd.astype(layer_params[k].dtype)
                return new_p, new_s, loss

            rule = net.updater.rule_for(layer)
            opt_state = {k: rule.init_one(v) for k, v in net.params[vi].items()}
            it_count = 0
            for _ in range(epochs):
                for ds in iterator:
                    feats = ds.features if isinstance(ds.features, (list, tuple)) \
                        else [ds.features]
                    xs = [cast_feed(f, dtype) for f in feats]
                    rng = jax.random.fold_in(base_rng, it_count * 1000 + vi)
                    lp, opt_state, loss = pretrain_step(
                        net.params[vi], net.params, net.state, opt_state,
                        jnp.asarray(it_count, jnp.int32), rng, xs)
                    params = list(net.params)
                    params[vi] = lp
                    net.params = tuple(params)
                    it_count += 1
                if hasattr(iterator, "reset"):
                    iterator.reset()
        return net

    # -------------------------------------------------------------- pretrain
    def pretrain(self, iterator, epochs: int = 1):
        """Layerwise unsupervised pretraining (reference
        MultiLayerNetwork.pretrain :219-299; ComputationGraph.pretrain): for
        each pretrainable layer, feed data forward through frozen earlier
        layers and optimize that layer's reconstruction loss."""
        net = self.net
        if net.params is None:
            net.init()
        if hasattr(net, "vertex_names"):
            return self._pretrain_graph(iterator, epochs)
        dtype = jnp.dtype(net.conf.dtype)
        base_rng = jax.random.PRNGKey(net.conf.seed + 104729)

        for li, layer in enumerate(net.layers):
            if not hasattr(layer, "pretrain_loss"):
                continue

            @jax.jit
            def pretrain_step(layer_params, full_params, state, opt_state, it, rng, x,
                              _li=li, _layer=layer):
                if _li > 0:
                    acts, _ = net.apply_fn(full_params, state, x, train=False,
                                           to_layer=_li - 1)
                    feed = acts[-1]
                else:
                    feed = x
                pre = net.conf.preprocessor(_li)
                if pre is not None:
                    feed = pre.apply(feed)

                def lf(p):
                    return _layer.pretrain_loss(p, feed, rng)
                loss, grads = jax.value_and_grad(lf)(layer_params)
                rule = net.updater.rule_for(_layer)
                new_p, new_s = {}, {}
                for k in layer_params:
                    upd, new_s[k] = rule.update_one(grads[k], opt_state[k],
                                                    rule.lr(it), it)
                    new_p[k] = layer_params[k] - upd.astype(layer_params[k].dtype)
                return new_p, new_s, loss

            rule = net.updater.rule_for(layer)
            opt_state = {k: rule.init_one(v) for k, v in net.params[li].items()}
            it_count = 0
            for _ in range(epochs):
                for ds in iterator:
                    x = cast_feed(ds.features, dtype)
                    rng = jax.random.fold_in(base_rng, it_count * 1000 + li)
                    lp, opt_state, loss = pretrain_step(
                        net.params[li], net.params, net.state, opt_state,
                        jnp.asarray(it_count, jnp.int32), rng, x)
                    params = list(net.params)
                    params[li] = lp
                    net.params = tuple(params)
                    it_count += 1
                if hasattr(iterator, "reset"):
                    iterator.reset()
        return net


def _is_multi(x):
    """True for MultiDataSet-style lists of per-input ARRAYS (a plain nested
    python list of numbers is single-input data, not multi-input)."""
    return (isinstance(x, (list, tuple)) and len(x) > 0
            and isinstance(x[0], (np.ndarray, jnp.ndarray)))


def cast_feed(x, dtype):
    """THE feed-boundary cast (Solver and ParallelWrapper), device-resident
    aware: an array the DevicePrefetchIterator already shipped is never
    round-tripped through the host (cast on device only if needed); host
    arrays go through jnp.asarray. Integer dtypes (token ids, uint8 wire
    images) are preserved: a token id cast to bf16 and back names another
    token above 256."""
    if isinstance(x, jax.Array):
        if x.dtype.kind in "iu":
            return x
        return x if x.dtype == dtype else x.astype(dtype)
    x = np.asarray(x)
    if x.dtype.kind in "iu":
        return jnp.asarray(x)
    return jnp.asarray(x, dtype)


def _cast_any(x, dtype):
    """Cast a single array or a list of arrays (MultiDataSet features/labels)."""
    if isinstance(x, (list, tuple)):
        return [cast_feed(v, dtype) for v in x]
    return cast_feed(x, dtype)


