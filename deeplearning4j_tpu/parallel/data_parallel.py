"""Data-parallel training: the TPU-native ParallelWrapper.

Reference: parallelism/ParallelWrapper.java:54 — thread-per-worker data
parallelism with parameter averaging every ``averaging_frequency`` iterations
(:244-250, averageModelsParams :332-361) or SHARED_GRADIENTS mode pushing
per-iteration updates through a GradientsAccumulator; Spark variants
(SURVEY.md §2.2) implement the same two semantics across hosts.

TPU mapping (SURVEY.md §5.8):
- SHARED_GRADIENTS / averaging_frequency=1  ->  per-step synchronous
  all-reduce: ONE jitted train step over a `Mesh`, batch sharded on the
  'data' axis, params replicated; XLA/GSPMD inserts the psum over ICI.
  (This is the reference's gradient-sharing path minus the threshold
  compression, which ICI bandwidth makes unnecessary; see ops/compression
  for the DCN variant.)
- AVERAGING with frequency K>1  ->  faithfully emulated with `shard_map`:
  each device holds ITS OWN params copy, runs K local steps on its shard
  stream, then `pmean`s params (and optionally updater state — reference
  ``averageUpdaters`` flag) across the axis.

Multi-host: the same code runs under `jax.distributed.initialize()`; the mesh
then spans hosts and the collectives ride ICI/DCN — no Aeron, no parameter
server (reference SharedTrainingMaster.java:46-53 is replaced wholesale).
"""
from __future__ import annotations

import functools
import time
from typing import Any, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..datasets.dataset import AsyncDataSetIterator
from ..datasets.prefetch import (BatchWindow, DevicePrefetchIterator,
                                 iter_windows, skip_batches)
from ..optimize.listeners import PerformanceListener, TrainingListener
from ..optimize.solver import cast_feed, train_step_math
from ..telemetry import get_registry, span
from .mesh import (data_sharding, make_mesh, replicated, shard_map,
                   window_sharding)
from .overlap import (DEFAULT_BUCKET_BYTES, build_bucket_schedule,
                      bucketed_pmean, fused_pmean)
from .tensor_parallel import (MODEL_AXIS, build_opt_shardings,
                              build_param_specs, build_param_shardings,
                              model_axis_size, per_replica_bytes)
from .zero import ZeroUpdateEngine, is_zero_state


class ParallelWrapper:
    """API analogue of the reference ParallelWrapper.Builder:

        pw = ParallelWrapper(net, averaging_frequency=3,
                             training_mode="averaging", average_updaters=True)
        pw.fit(iterator, epochs=2)

    ``workers`` is accepted for API familiarity but the device count comes
    from the mesh (every chip is a worker).

    ``prefetch_buffer`` (reference Builder.prefetchBuffer) is the in-flight
    depth of the input pipeline: on the per-step sync path it is the
    DevicePrefetchIterator depth — batches ship host->device PRE-SHARDED on
    the mesh's data axis while the previous step computes; on the K-step
    averaging path it is the host-side prefetch queue (the K-batch stack is
    assembled on host).

    ``steps_per_dispatch=K`` (sync path only): windows of K pre-sharded
    device-resident batches run through ONE jitted lax.scan program —
    bit-identical to K per-step dispatches, one host round-trip per
    window. Ragged remainder windows fall back per-step; the averaging
    path (averaging_frequency>1) is already a fused K-step program and
    ignores this knob.

    ``overlap_sync=True`` (sync path, no accumulator): bucketed
    backward-overlap gradient synchronization (parallel/overlap.py) —
    the grad tree is all-reduced per ~``bucket_bytes`` bucket (small
    leaves densified into one flat psum each, packed in reverse leaf
    order) instead of the monolithic per-leaf post-backward sweep, so
    collectives launch as their gradients are produced and the sync
    dispatches O(buckets) collectives instead of O(leaves). Composes
    with ``steps_per_dispatch`` (the scan body carries the same
    schedule). Bit-identical to the unbucketed path at every bucket
    size (tests/test_overlap_sync.py).

    ``zero_stage=1|2`` (sync path): ZeRO-style cross-replica sharding of
    the weight update (parallel/zero.py, arXiv 2004.13336). Each replica
    applies the updater to only its 1/N flat shard of the grad+param
    tree — updater state is allocated SHARD-SIZED (``net.opt_state``
    becomes the engine's sharded format for the duration; convert back
    with ``gather_opt_state()``) — then all-gathers the updated params.
    Stage 1 all-reduces grads per bucket (the same collectives as
    ``overlap_sync``) and slices; stage 2 reduce-scatters per bucket
    (half the collective bytes). Both are bit-identical to the
    replicated update and compose with ``steps_per_dispatch`` windows
    and the remainder fallback (tests/test_zero.py).

    On every sync path (plain, overlap and zero), a batch whose size
    does not tile the mesh — the end-of-epoch remainder the prefetcher
    ships unsharded — dispatches through a replicated-feed program for
    that step instead of raising the divisibility error; the update is
    identical. The explicit-accumulator path keeps the loud error (its
    per-worker carry has no replicated equivalent).
    """

    def __init__(self, net, *, mesh: Optional[Mesh] = None,
                 mesh_shape: Optional[tuple] = None,
                 workers: Optional[int] = None,
                 averaging_frequency: int = 1, training_mode: str = "shared_gradients",
                 average_updaters: bool = True, prefetch_buffer: int = 2,
                 report_score_after_averaging: bool = True,
                 gradient_accumulator=None, steps_per_dispatch: int = 1,
                 overlap_sync: bool = False,
                 bucket_bytes: int = DEFAULT_BUCKET_BYTES,
                 zero_stage: int = 0,
                 step_callback=None):
        self.net = net
        devices = jax.devices()
        if mesh is not None and mesh_shape is not None:
            raise ValueError("pass mesh OR mesh_shape, not both")
        if workers is not None and mesh is None:
            devices = devices[:workers]
            if mesh_shape is None:
                mesh = make_mesh((len(devices),), ("data",), devices)
        if mesh_shape is not None:
            # (d,) is the 1-D data mesh; (d, m) adds the Megatron-style
            # model axis (parallel/tensor_parallel.py) — m=1 keeps the
            # axis in the mesh but every program stays bit-identical to
            # the 1-D path (the tp spec table is empty at m=1).
            if len(mesh_shape) == 1:
                mesh = make_mesh(tuple(mesh_shape), ("data",), devices)
            elif len(mesh_shape) == 2:
                mesh = make_mesh(tuple(mesh_shape), ("data", MODEL_AXIS),
                                 devices)
            else:
                raise ValueError(f"mesh_shape must be (d,) or (d, m), "
                                 f"got {mesh_shape}")
        self.mesh = mesh if mesh is not None else make_mesh()
        # batch-divisibility and worker accounting follow the DATA axis
        # only — the model axis replicates the batch
        _sizes = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
        self.n = int(_sizes.get("data", self.mesh.devices.size))
        self.m = model_axis_size(self.mesh)
        self.averaging_frequency = max(1, averaging_frequency)
        self.training_mode = training_mode.lower()
        self.average_updaters = average_updaters
        self.prefetch_buffer = prefetch_buffer
        # GradientsAccumulator seam (reference GradientsAccumulator.java SPI;
        # see parallel/accumulation.py). None -> GSPMD-inserted psum.
        self.gradient_accumulator = gradient_accumulator
        if gradient_accumulator is not None and \
                self.training_mode == "averaging" and self.averaging_frequency > 1:
            raise ValueError(
                "gradient_accumulator applies to the per-step gradient-sharing "
                "path (training_mode='shared_gradients'), not K-step parameter "
                "averaging — the reference makes the same split "
                "(ParallelWrapper.TrainingMode AVERAGING vs SHARED_GRADIENTS)")
        if self.m > 1:
            if self.training_mode == "averaging" \
                    and self.averaging_frequency > 1:
                raise ValueError(
                    "model-axis sharding applies to the per-step sync "
                    "path; K-step parameter averaging gives each worker "
                    "its own full param copy, which a model-sharded "
                    "layout cannot represent — use "
                    "training_mode='shared_gradients' on a (data, model) "
                    "mesh")
            if gradient_accumulator is not None:
                raise ValueError(
                    "a GradientsAccumulator ravels the full per-worker "
                    "grad tree, which a model-sharded layout cannot feed "
                    "— drop the accumulator on a (data, model) mesh")
        # Fused K-step dispatch on the sync all-reduce path (the same
        # scan-window program as Solver.fit(steps_per_dispatch=K), with
        # xs/ys landing [K, batch, ...] sharded on the data axis). The
        # explicit-accumulator path keeps per-step dispatch: its combine
        # carry is per-worker state threaded outside the scan.
        if steps_per_dispatch < 1:
            raise ValueError("steps_per_dispatch must be >= 1")
        if steps_per_dispatch > 1 and gradient_accumulator is not None:
            raise ValueError(
                "steps_per_dispatch applies to the plain sync all-reduce "
                "path; the GradientsAccumulator path dispatches per step")
        # Bucketed backward-overlap gradient sync (parallel/overlap.py):
        # shard_map step with per-bucket flat psums instead of the GSPMD
        # monolithic post-backward sweep. Orthogonal to the accumulator
        # seam (which owns its own combine) — refuse the combination.
        if overlap_sync and gradient_accumulator is not None:
            raise ValueError(
                "overlap_sync schedules the plain psum exchange in buckets; "
                "a GradientsAccumulator owns its own combine — pick one")
        if overlap_sync and self.training_mode == "averaging" \
                and self.averaging_frequency > 1:
            raise ValueError(
                "overlap_sync applies to the per-step sync all-reduce path; "
                "the K-step averaging path already runs ONE fused variadic "
                "pmean launch per window — it would silently ignore the "
                "bucket schedule")
        self.overlap_sync = overlap_sync
        self.bucket_bytes = bucket_bytes
        self._bucket_schedule = None     # built lazily from net.params
        # ZeRO sharded update (parallel/zero.py): stage 1 = shard the
        # updater state (grads still all-reduced, bucketed), stage 2 =
        # reduce-scatter the grads too. Sync-path only: the K-step
        # averaging path pmeans whole param/state trees (sharded state
        # has no per-worker trajectory to average) and the accumulator
        # owns its own combine.
        if zero_stage not in (0, 1, 2):
            raise ValueError(f"zero_stage must be 0, 1 or 2, "
                             f"got {zero_stage}")
        if zero_stage and gradient_accumulator is not None:
            raise ValueError(
                "zero_stage shards the plain sync update; a "
                "GradientsAccumulator owns its own combine — pick one")
        if zero_stage and self.training_mode == "averaging" \
                and self.averaging_frequency > 1:
            raise ValueError(
                "zero_stage applies to the per-step sync all-reduce "
                "path; the K-step averaging path averages full "
                "per-worker param/state trajectories, which a sharded "
                "updater state cannot represent")
        if zero_stage and overlap_sync:
            raise ValueError(
                "zero_stage already dispatches per-bucket overlapped "
                "collectives (stage 1 is the overlap_sync launch "
                "pattern; stage 2 reduce-scatters the same buckets) — "
                "drop overlap_sync rather than have it silently ignored")
        self.zero_stage = zero_stage
        self._zero_engine = None         # built lazily from net.params
        self.steps_per_dispatch = steps_per_dispatch
        self._acc_state = None
        self._sync_step = None
        self._sync_window_step = None
        # tensor-parallel layout (parallel/tensor_parallel.py), built
        # lazily from net.params: PartitionSpec tree + NamedSharding
        # trees for params and updater state. None until m > 1 asks.
        self._tp_specs = None
        self._tp_param_sh = None
        self._tp_opt_sh = None
        # Replicated-feed programs for sync batches that don't tile the
        # mesh (shard_map AND jit+in_shardings both enforce batch-dim
        # divisibility): the end-of-epoch remainder the prefetcher ships
        # unsharded dispatches through these instead of killing the
        # epoch. Built lazily; the update is identical (the psum over a
        # sharded batch == the replicated full-batch computation).
        self._remainder_step = None
        self._remainder_window_step = None
        self._avg_steps = {}   # keyed by chunk count (remainder batches differ)
        # Supervision seam (parallel/elastic.py): called as
        # step_callback(net, k) AFTER a dispatched item's k iterations are
        # fully accounted (params, iteration_count, listeners all
        # consistent) — the one safe place to raise control-flow out of
        # the epoch (worker-loss, preemption, mode switches, step budget).
        # Raising from a TrainingListener.iteration_done instead would
        # strand iteration_count behind params mid-item.
        self.step_callback = step_callback

    # --------------------------------------------------- tensor-parallel
    def _tp_shardings(self):
        """Param NamedSharding tree for the model axis (Megatron head/
        width split; tensor_parallel.build_param_specs). Layout hints
        only — GSPMD owns the collectives."""
        if self._tp_param_sh is None:
            self._tp_specs = build_param_specs(self.net, self.m)
            self._tp_param_sh = build_param_shardings(self.mesh,
                                                      self._tp_specs)
        return self._tp_param_sh

    def _tp_opt_shardings(self):
        """Updater-state NamedSharding tree mirroring the param specs
        (momentum/velocity slots shard with their param; scalars stay
        replicated). Materializes ``net.opt_state`` if the net has not
        trained yet — the tree's structure is the sharding's shape."""
        if self._tp_opt_sh is None:
            self._tp_shardings()
            if self.net.opt_state is None:
                self.net.opt_state = self.net.updater.init(self.net.params)
            self._tp_opt_sh = build_opt_shardings(
                self.mesh, self._tp_specs, self.net.params,
                self.net.opt_state)
        return self._tp_opt_sh

    def _auto_axes(self):
        """shard_map manual-collective builders go over 'data' only; on a
        2-D mesh the model axis stays GSPMD-managed (auto), so the tp
        layout hints on the jit boundary shard the math inside the
        manual region too."""
        return {"auto": frozenset({MODEL_AXIS})} if self.m > 1 else {}

    def _jit_manual(self, fn, feed_sh, opt_sh=None):
        """jit a shard_map-built step. 1-D path: exactly the historical
        ``jax.jit(fn, donate_argnums=(0, 2))``. 2-D path: the tp layout
        hints ride the jit boundary (params/opt model-sharded at rest,
        feeds on the data axis) so the auto model axis inside the manual
        region inherits them."""
        if self.m == 1:
            return jax.jit(fn, donate_argnums=(0, 2))
        rep = replicated(self.mesh)
        psh = self._tp_shardings()
        osh = opt_sh if opt_sh is not None else self._tp_opt_shardings()
        return jax.jit(fn, donate_argnums=(0, 2),
                       in_shardings=(psh, rep, osh, rep, rep,
                                     feed_sh, feed_sh),
                       out_shardings=(psh, rep, osh, rep))

    # ------------------------------------------------------------- sync path
    def _build_sync_step(self, feed_sharding=None):
        """Per-step all-reduce DP: jit over the mesh, batch sharded.
        ``feed_sharding`` overrides the x/y sharding (the remainder
        program passes replicated)."""
        net = self.net
        mesh = self.mesh

        def step(params, state, opt_state, it, rng, x, y):
            return train_step_math(net, params, state, opt_state, it, rng,
                                   x, y)

        rep = replicated(mesh)
        dsh = feed_sharding if feed_sharding is not None \
            else data_sharding(mesh)
        psh = self._tp_shardings() if self.m > 1 else rep
        osh = self._tp_opt_shardings() if self.m > 1 else rep
        return jax.jit(
            step, donate_argnums=(0, 2),
            in_shardings=(psh, rep, osh, rep, rep, dsh, dsh),
            out_shardings=(psh, rep, osh, rep))

    def _build_sync_window_step(self, feed_sharding=None):
        """K fused sync-DP steps in ONE jitted lax.scan program: xs/ys are
        [K, batch, ...] with the batch dim sharded on the data axis (each
        scan iteration consumes one data-sharded batch; GSPMD inserts the
        same psum as the per-step program), params/opt_state the donated
        carry, per-step losses the ys — bit-identical to K sequential
        ``_build_sync_step`` dispatches."""
        net = self.net
        mesh = self.mesh

        def window_step(params, state, opt_state, it0, base_rng, xs, ys):
            def body(carry, inp):
                params, state, opt_state, it = carry
                x, y = inp
                rng = jax.random.fold_in(base_rng, it)
                new_params, new_state, new_opt, loss = train_step_math(
                    net, params, state, opt_state, it, rng, x, y)
                return (new_params, new_state, new_opt, it + 1), loss

            (params, state, opt_state, _), losses = jax.lax.scan(
                body, (params, state, opt_state, it0), (xs, ys))
            return params, state, opt_state, losses

        rep = replicated(mesh)
        wsh = feed_sharding if feed_sharding is not None \
            else window_sharding(mesh)   # [K, batch, ...]
        psh = self._tp_shardings() if self.m > 1 else rep
        osh = self._tp_opt_shardings() if self.m > 1 else rep
        return jax.jit(
            window_step, donate_argnums=(0, 2),
            in_shardings=(psh, rep, osh, rep, rep, wsh, wsh),
            out_shardings=(psh, rep, osh, rep))

    # -------------------------------------------------- overlapped sync path
    def _grad_schedule(self):
        """Bucket schedule over the param/grad tree (built once; the grad
        tree from value_and_grad shares the params' treedef)."""
        if self._bucket_schedule is None:
            self._bucket_schedule = build_bucket_schedule(
                self.net.params, self.bucket_bytes)
            reg = get_registry()
            if reg.enabled:
                reg.gauge("parallel.bucket_count").set(
                    len(self._bucket_schedule))
        return self._bucket_schedule

    def _build_overlap_step(self):
        """Bucketed backward-overlap sync DP (parallel/overlap.py): each
        worker differentiates its local shard under shard_map, then the
        grad tree is all-reduced per ~bucket_bytes bucket — small leaves
        densified into one flat buffer per bucket (one psum launch each,
        arXiv:1905.04035), buckets packed in reverse leaf order so the
        collectives' data dependences let XLA's latency-hiding scheduler
        start ICI traffic while the backward is still producing earlier
        layers' gradients (arXiv:2004.13336) — vs the GSPMD path's
        monolithic O(leaves) post-backward sweep. State and loss ride ONE
        fused variadic pmean after the updater."""
        net = self.net
        mesh = self.mesh
        schedule = self._grad_schedule()

        def worker_step(params, state, opt_state, it, rng, x, y):
            new_params, new_state, new_opt, loss = train_step_math(
                net, params, state, opt_state, it, rng, x, y,
                grad_sync=lambda g: bucketed_pmean(g, schedule, "data"))
            new_state, loss = fused_pmean((new_state, loss), "data")
            return new_params, new_state, new_opt, loss

        rep, dsh = P(), P("data")
        fn = shard_map(worker_step, mesh=mesh,
                       in_specs=(rep, rep, rep, rep, rep, dsh, dsh),
                       out_specs=(rep, rep, rep, rep), check_vma=False,
                       **self._auto_axes())
        return self._jit_manual(fn, data_sharding(mesh))

    def _build_overlap_window_step(self):
        """K fused steps of the bucketed-overlap sync path in ONE lax.scan
        program: the scan body is ``train_step_math`` with the SAME bucket
        schedule as ``_build_overlap_step`` (the grad_sync seam carries it
        into the fused window structurally), so K fused steps stay
        bit-identical to K per-step overlap dispatches."""
        net = self.net
        mesh = self.mesh
        schedule = self._grad_schedule()

        def window_step(params, state, opt_state, it0, base_rng, xs, ys):
            def body(carry, inp):
                params, state, opt_state, it = carry
                x, y = inp
                rng = jax.random.fold_in(base_rng, it)
                new_params, new_state, new_opt, loss = train_step_math(
                    net, params, state, opt_state, it, rng, x, y,
                    grad_sync=lambda g: bucketed_pmean(g, schedule, "data"))
                new_state, loss = fused_pmean((new_state, loss), "data")
                return (new_params, new_state, new_opt, it + 1), loss

            (params, state, opt_state, _), losses = jax.lax.scan(
                body, (params, state, opt_state, it0), (xs, ys))
            return params, state, opt_state, losses

        rep, wsh = P(), P(None, "data")
        fn = shard_map(window_step, mesh=mesh,
                       in_specs=(rep, rep, rep, rep, rep, wsh, wsh),
                       out_specs=(rep, rep, rep, rep), check_vma=False,
                       **self._auto_axes())
        return self._jit_manual(fn, window_sharding(mesh))

    # --------------------------------------------------- zero sharded path
    def _zero(self) -> ZeroUpdateEngine:
        """The ZeRO engine for this net+mesh (layout built once on host;
        rebuilding only matters when the param structure changes)."""
        if self._zero_engine is None:
            self._zero_engine = ZeroUpdateEngine.from_net(
                self.net, self.mesh, stage=self.zero_stage,
                bucket_bytes=self.bucket_bytes)
        return self._zero_engine

    def gather_opt_state(self):
        """Convert ``net.opt_state`` back to the replicated per-leaf
        format (all-gather on host) — for serialization or for handing
        the net to a non-zero training path. No-op if already
        replicated."""
        if is_zero_state(self.net.opt_state):
            self.net.opt_state = self._zero().unshard_opt_state(
                self.net.opt_state)
        return self.net.opt_state

    def _build_zero_step(self, replicated_feed: bool = False):
        """Sharded-update sync DP (parallel/zero.py): grads combined via
        the engine's grad_sync (stage 1: bucketed all-reduce — the same
        launches as the overlap path; stage 2: per-bucket reduce-scatter
        at half the bytes), the updater applied to THIS worker's 1/N
        flat shard only (opt state enters [N, L] sharded on the data
        axis and stays sharded), updated params all-gathered back to
        replicated. State and loss ride ONE fused variadic pmean."""
        net = self.net
        mesh = self.mesh
        eng = self._zero()

        def worker_step(params, state, opt_state, it, rng, x, y):
            new_params, new_state, new_opt, loss = train_step_math(
                net, params, state, opt_state, it, rng, x, y,
                grad_sync=eng.grad_sync, update_fn=eng.update)
            new_state, loss = fused_pmean((new_state, loss), "data")
            return new_params, new_state, new_opt, loss

        rep = P()
        osh = P("data")                      # [N, L] state shards
        dsh = rep if replicated_feed else P("data")
        # NOTE: no auto model axis here — on jax 0.4.37 the engine's
        # axis_index / psum_scatter collectives only lowered under a
        # fully-manual region; whether jax 0.9.0's shard_map (axis_names=)
        # lifts that is UNTESTED (ROADMAP Queue 3, the ParallelWrapper
        # item, retries it).
        # On a (data, model) mesh the flat update stays sharded
        # d ways over 'data' (replicated across model); params are
        # model-sharded AT REST via the jit boundary and gathered for
        # the step — the at-rest m× memory win composes, the compute
        # inside the zero step does not.
        fn = shard_map(worker_step, mesh=mesh,
                       in_specs=(rep, rep, osh, rep, rep, dsh, dsh),
                       out_specs=(rep, rep, osh, rep), check_vma=False)
        return self._jit_manual(
            fn,
            replicated(mesh) if replicated_feed else data_sharding(mesh),
            opt_sh=NamedSharding(mesh, osh))

    def _build_zero_window_step(self, replicated_feed: bool = False):
        """K fused zero-sharded steps in ONE lax.scan program: the scan
        body is ``train_step_math`` with the SAME engine seams as
        ``_build_zero_step`` (grad_sync + update_fn ride the body
        structurally), opt-state shards in the donated carry — K fused
        steps stay bit-identical to K per-step zero dispatches."""
        net = self.net
        mesh = self.mesh
        eng = self._zero()

        def window_step(params, state, opt_state, it0, base_rng, xs, ys):
            def body(carry, inp):
                params, state, opt_state, it = carry
                x, y = inp
                rng = jax.random.fold_in(base_rng, it)
                new_params, new_state, new_opt, loss = train_step_math(
                    net, params, state, opt_state, it, rng, x, y,
                    grad_sync=eng.grad_sync, update_fn=eng.update)
                new_state, loss = fused_pmean((new_state, loss), "data")
                return (new_params, new_state, new_opt, it + 1), loss

            (params, state, opt_state, _), losses = jax.lax.scan(
                body, (params, state, opt_state, it0), (xs, ys))
            return params, state, opt_state, losses

        rep, osh = P(), P("data")
        wsh = rep if replicated_feed else P(None, "data")
        # fully-manual for the same reason as _build_zero_step
        fn = shard_map(window_step, mesh=mesh,
                       in_specs=(rep, rep, osh, rep, rep, wsh, wsh),
                       out_specs=(rep, rep, osh, rep), check_vma=False)
        return self._jit_manual(
            fn,
            replicated(mesh) if replicated_feed else window_sharding(mesh),
            opt_sh=NamedSharding(mesh, osh))

    def _remainder_step_fn(self):
        """The sync step with x/y REPLICATED: serves batches whose size
        does not tile the mesh — shard_map (overlap path) and
        jit+in_shardings (GSPMD path) both enforce batch-dim
        divisibility, so a 36-sample remainder on an 8-device mesh would
        otherwise kill the epoch. Every device redundantly computes the
        full remainder batch; the update is identical to what a sharded
        dispatch would produce (GSPMD's psum over per-shard partials IS
        the full-batch reduction), matching the contract of the
        prefetcher shipping remainders unsharded and iter_windows
        dropping ragged groups to per-step. The zero path keeps its
        sharded update under the replicated feed (every device computes
        the full-batch grads, the reduce is then a no-op-by-value, the
        shard update and all-gather run as usual)."""
        if self._remainder_step is None:
            self._remainder_step = (
                self._build_zero_step(replicated_feed=True)
                if self.zero_stage else
                self._build_sync_step(feed_sharding=replicated(self.mesh)))
        return self._remainder_step

    def _remainder_window_step_fn(self):
        """Window variant of ``_remainder_step_fn`` (uniformly
        non-divisible batch sizes stack into regular windows too)."""
        if self._remainder_window_step is None:
            self._remainder_window_step = (
                self._build_zero_window_step(replicated_feed=True)
                if self.zero_stage else
                self._build_sync_window_step(
                    feed_sharding=replicated(self.mesh)))
        return self._remainder_window_step

    # ------------------------------------------------------ accumulator path
    def _build_accum_step(self):
        """Sync DP with an explicit GradientsAccumulator combining per-worker
        flat gradients inside shard_map (reference StochasticGradientDescent
        accumulator hook :67-74 + EncodingHandler exchange). The accumulator
        carry (e.g. the threshold-compression residual) is per-worker: global
        shape [n_workers, n_params] sharded on the data axis."""
        net = self.net
        mesh = self.mesh
        acc = self.gradient_accumulator
        from jax.flatten_util import ravel_pytree

        def worker_step(params, state, opt_state, acc_state, it, rng, x, y):
            def lf(p):
                return net.loss_fn(p, state, x, y, train=True, rng=rng)
            (loss, new_state), grads = jax.value_and_grad(lf, has_aux=True)(params)
            flat, unravel = ravel_pytree(grads)
            combined, new_acc = acc.combine(flat, acc_state[0], axis="data")
            # combined grads are identical on every worker, so the updater
            # math (and its replicated state) stays in lockstep
            new_params, new_opt = net.updater.update(unravel(combined),
                                                     opt_state, params, it)
            # state + loss in one variadic pmean bind (vs a per-leaf tree
            # sweep plus a separate scalar launch)
            new_state, loss = fused_pmean((new_state, loss), "data")
            return new_params, new_state, new_opt, new_acc[None], loss

        rep, dsh = P(), P("data")
        fn = shard_map(worker_step, mesh=mesh,
                       in_specs=(rep, rep, rep, dsh, rep, rep, dsh, dsh),
                       out_specs=(rep, rep, rep, dsh, rep),
                       check_vma=False)
        return jax.jit(fn, donate_argnums=(0, 2, 3))

    def _init_acc_state(self, dtype):
        size = int(self.net.num_params())
        per_worker = self.gradient_accumulator.init(size, dtype)
        if isinstance(per_worker, tuple) and per_worker == ():
            # stateless accumulator (PsumAccumulator)
            per_worker = jnp.zeros((0,), dtype)
        return jnp.broadcast_to(per_worker, (self.n,) + per_worker.shape).copy()

    # -------------------------------------------------------- averaging path
    def _build_avg_step(self, replicated_feed: bool = False):
        """K local steps per device, then pmean of params (+updater state):
        the reference's averagingFrequency semantics, one XLA program.

        ``replicated_feed``: serves batches whose size does not tile the
        mesh (e.g. after an elastic recovery shrank the mesh): every
        worker runs the SAME K full-batch steps and the pmean of
        identical trajectories is a no-op — degenerate but well-defined
        averaging, instead of the shard_map divisibility error killing
        the epoch."""
        net = self.net
        mesh = self.mesh
        K = self.averaging_frequency
        avg_upd = self.average_updaters

        def worker_steps(params, state, opt_state, it, rng, xs, ys):
            # params/state/opt live per-device (shard_map gives the local copy;
            # xs/ys: [K, local_batch, ...] — K chunks for K local steps
            def body(carry, inp):
                params, state, opt_state, i = carry
                x, y = inp

                def lf(p):
                    return net.loss_fn(p, state, x, y, train=True,
                                       rng=jax.random.fold_in(rng, i))
                (loss, new_state), grads = jax.value_and_grad(lf, has_aux=True)(params)
                new_params, new_opt = net.updater.update(grads, opt_state, params, it + i)
                return (new_params, new_state, new_opt, i + 1), loss

            (params, state, opt_state, _), losses = jax.lax.scan(
                body, (params, state, opt_state, 0), (xs, ys))
            # parameter averaging across workers (reference :332-361):
            # params, state, (opt_state) and the scalar loss all ride ONE
            # variadic pmean bind instead of three per-leaf tree sweeps
            # plus a scalar launch — same elementwise math, O(1) dispatch
            mean_loss = jnp.mean(losses)
            if avg_upd:
                params, state, opt_state, mean_loss = fused_pmean(
                    (params, state, opt_state, mean_loss), "data")
            else:
                params, state, mean_loss = fused_pmean(
                    (params, state, mean_loss), "data")
            return params, state, opt_state, mean_loss

        rep_spec = P()
        # [K, batch, ...] -> shard batch dim; replicated when it can't tile
        dsh_spec = rep_spec if replicated_feed else P(None, "data")
        fn = shard_map(worker_steps, mesh=mesh,
                       in_specs=(rep_spec, rep_spec, rep_spec, rep_spec, rep_spec,
                                 dsh_spec, dsh_spec),
                       out_specs=(rep_spec, rep_spec, rep_spec, rep_spec),
                       check_vma=False)
        return jax.jit(fn, donate_argnums=(0, 2))

    # ------------------------------------------------------------------- fit
    def fit(self, iterator, epochs: int = 1, *, skip_first_batches: int = 0):
        net = self.net
        if skip_first_batches < 0:
            raise ValueError("skip_first_batches must be >= 0")
        if net.params is None:
            net.init()
        sync = self.training_mode == "shared_gradients" or self.averaging_frequency == 1
        if sync and self.zero_stage:
            # the engine owns the opt-state format: shard a replicated
            # tree on first entry (pure redistribution), validate an
            # already-sharded one against THIS mesh's layout
            self.net.opt_state = self._zero().shard_opt_state(
                self.net.opt_state)
        if sync and self._sync_step is None:
            if self.gradient_accumulator is not None:
                self._sync_step = self._build_accum_step()
            elif self.zero_stage:
                self._sync_step = self._build_zero_step()
            elif self.overlap_sync:
                self._sync_step = self._build_overlap_step()
            else:
                self._sync_step = self._build_sync_step()
        dtype = jnp.dtype(net.conf.dtype)
        base_rng = jax.random.PRNGKey(net.conf.seed + 31337)
        perf = [l for l in net.listeners if isinstance(l, PerformanceListener)]
        if sync:
            # Device prefetch with the mesh's data sharding: batch N+1 is
            # shipped PRE-SHARDED (per-device sub-buffers land directly)
            # while step N computes, so neither the host->device hop nor
            # the GSPMD resharding sits serially inside the step. The
            # K-step averaging path below stacks K host batches into one
            # [K, B, ...] program feed instead, so it keeps the host-side
            # prefetcher. prefetch_buffer < 1 opts out of prefetching
            # (the old host wrapper treated 0 as 'unbounded', which for a
            # device-resident queue would mean unbounded HBM — refuse the
            # footprint, not the caller).
            if isinstance(iterator, DevicePrefetchIterator):
                it_wrapped = iterator
            elif self.prefetch_buffer >= 1:
                it_wrapped = DevicePrefetchIterator(
                    iterator, self.prefetch_buffer, dtype=dtype,
                    sharding=data_sharding(self.mesh))
            else:
                it_wrapped = iterator
            prefetcher = (it_wrapped
                          if isinstance(it_wrapped, DevicePrefetchIterator)
                          else None)
        else:
            # host-side prefetch only: _run_avg stacks K host batches into
            # one [K, B, ...] feed, so a device-resident batch would just
            # round-trip device->host->device. Unwrap a caller-supplied
            # DevicePrefetchIterator to its base for the same reason.
            # prefetch_buffer < 1 opts out of the async wrapper entirely
            # (ElasticTrainer's degraded mode relies on this: a background
            # producer racing a recovery-time iterator reset() would make
            # the resumed data stream nondeterministic, and Queue(0) is
            # UNBOUNDED — it would buffer the whole epoch on host).
            base = (iterator.base
                    if isinstance(iterator, DevicePrefetchIterator)
                    else iterator)
            it_wrapped = (AsyncDataSetIterator(base, self.prefetch_buffer)
                          if self.prefetch_buffer >= 1 else base)
            prefetcher = None

        # the Solver's feed rule: floats to the net's dtype, integers
        # (token ids, uint8 wire images) stay integers — a token id cast
        # to bf16 and back names another token above 256
        def feed(v):
            return cast_feed(v, dtype)

        reg = get_registry()
        # the step programs are traced at their first dispatch below: let
        # layer code see the mesh they will run over (a Pallas kernel
        # must split itself per device — ops/pallas_attention.py); this
        # context changes tracing only, never where eager arrays land
        with jax.sharding.use_abstract_mesh(self.mesh.abstract_mesh), \
                span("fit", epochs=epochs, mode=self.training_mode,
                     devices=self.n, net="ParallelWrapper"):
            for epoch in range(epochs):
                with span("epoch", index=epoch):
                    self._fit_epoch(net, it_wrapped, prefetcher, iterator,
                                    feed, dtype, base_rng, perf, sync, reg,
                                    skip=(skip_first_batches
                                          if epoch == 0 else 0))
            if self.m > 1 and reg.enabled:
                # per-replica footprint after the layout hints settled:
                # model-sharded leaves contribute 1/m of their bytes —
                # the ≈m× reduction the tp memory claim gauges
                reg.gauge("parallel.model_axis").set(self.m)
                reg.gauge("parallel.param_bytes_per_replica").set(
                    per_replica_bytes(net.params))
                reg.gauge("parallel.opt_bytes_per_replica").set(
                    per_replica_bytes(net.opt_state))
        return net

    def _fit_epoch(self, net, it_wrapped, prefetcher, iterator, feed, dtype,
                   base_rng, perf, sync, reg, skip: int = 0):
        for l in net.listeners:
            if isinstance(l, TrainingListener):
                l.on_epoch_start(net)
        # mid-epoch resume: batches the checkpointed run already trained
        # are consumed, not dispatched (see Solver._fit_epoch)
        src = skip_batches(it_wrapped, skip) if skip else iter(it_wrapped)
        if sync:
            _t0 = time.perf_counter()
            _etl_prev_total = (prefetcher.total_wait_ms
                               if (skip and prefetcher is not None) else 0.0)
            # hoisted like Solver._fit_epoch: metric name resolution once
            # per epoch, one locked int add per iteration
            _c_iters = reg.counter("train.iterations")
            _c_windows = reg.counter("train.windows")
            # host-side collective accounting on the overlap/zero paths:
            # grad reduce launches (+ param all-gathers on zero) + the
            # fused state/loss launch, per executed step
            _c_coll = reg.counter("parallel.collective_launches")
            if self.zero_stage:
                _n_buckets = self._zero().num_reduce_launches
                _n_coll = self._zero().collectives_per_step + 1
            elif self.overlap_sync:
                _n_buckets = len(self._grad_schedule())
                _n_coll = _n_buckets + 1
            else:
                _n_buckets = _n_coll = 0
            windowed = (self.steps_per_dispatch > 1
                        and self.gradient_accumulator is None)
            stream = (iter_windows(src, self.steps_per_dispatch)
                      if windowed else src)
            for item in stream:
                if prefetcher is not None:
                    etl_ms = prefetcher.total_wait_ms - _etl_prev_total
                    _etl_prev_total = prefetcher.total_wait_ms
                else:
                    etl_ms = (time.perf_counter() - _t0) * 1e3
                if isinstance(item, BatchWindow):
                    if self._sync_window_step is None:
                        self._sync_window_step = (
                            self._build_zero_window_step()
                            if self.zero_stage else
                            self._build_overlap_window_step()
                            if self.overlap_sync
                            else self._build_sync_window_step())
                    k = len(item)
                    with span("window", k=k, iteration=net.iteration_count):
                        xs, ys, _, _ = item.stacked(cast=feed)
                        wstep = self._sync_window_step
                        n_coll = _n_coll
                        if xs.shape[1] % self.n != 0:
                            # batch size doesn't tile the mesh: dispatch
                            # the replicated window program (identical
                            # update) instead of the divisibility error
                            # (the zero remainder keeps its collectives)
                            wstep = self._remainder_window_step_fn()
                            n_coll = _n_coll if self.zero_stage else 0
                        with span("dispatch", k=k, buckets=_n_buckets):
                            (net.params, net.state, net.opt_state,
                             losses) = wstep(
                                net.params, net.state, net.opt_state,
                                jnp.asarray(net.iteration_count, jnp.int32),
                                base_rng, xs, ys)
                        device_ms = max(
                            (time.perf_counter() - _t0) * 1e3 - etl_ms, 0.0)
                        _c_windows.inc()
                        _c_iters.inc(k)
                        if n_coll:
                            _c_coll.inc(k * n_coll)
                        for p in perf:
                            p.note_window(k)
                        for i, d in enumerate(item.datasets):
                            self._notify(perf, d, losses[i],
                                         etl_wait_ms=etl_ms / k,
                                         device_ms=device_ms / k)
                            net.iteration_count += 1
                    if self.step_callback is not None:
                        self.step_callback(net, k)
                    _t0 = time.perf_counter()
                    continue
                ds = item
                # one span per single-step iteration (see Solver._fit_epoch:
                # the step IS the dispatch on this path)
                with span("step", iteration=net.iteration_count):
                    x = feed(ds.features)
                    y = feed(ds.labels)
                    rng = jax.random.fold_in(base_rng, net.iteration_count)
                    it = jnp.asarray(net.iteration_count, jnp.int32)
                    n_coll = _n_coll
                    if self.gradient_accumulator is not None:
                        if self._acc_state is None:
                            self._acc_state = self._init_acc_state(dtype)
                        (net.params, net.state, net.opt_state,
                         self._acc_state, loss) = self._sync_step(
                            net.params, net.state, net.opt_state,
                            self._acc_state, it, rng, x, y)
                    else:
                        step = self._sync_step
                        if x.shape[0] % self.n != 0:
                            # remainder batch: replicated fallback (the
                            # zero remainder keeps its collectives)
                            step = self._remainder_step_fn()
                            n_coll = _n_coll if self.zero_stage else 0
                        net.params, net.state, net.opt_state, loss = \
                            step(net.params, net.state,
                                 net.opt_state, it, rng, x, y)
                    device_ms = max(
                        (time.perf_counter() - _t0) * 1e3 - etl_ms, 0.0)
                    _c_iters.inc()
                    if n_coll:
                        _c_coll.inc(n_coll)
                    self._notify(perf, ds, loss, etl_wait_ms=etl_ms,
                                 device_ms=device_ms)
                    net.iteration_count += 1
                if self.step_callback is not None:
                    self.step_callback(net, 1)
                _t0 = time.perf_counter()
        else:
            # accumulate K batches then run the fused K-step+average program
            buf: List[Any] = []
            for ds in src:
                buf.append(ds)
                if len(buf) == self.averaging_frequency:
                    self._run_avg(buf, base_rng, dtype, perf)
                    buf = []
            if buf:
                self._run_avg(buf, base_rng, dtype, perf)
        for l in net.listeners:
            if isinstance(l, TrainingListener):
                l.on_epoch_end(net)
        if hasattr(iterator, "reset"):
            iterator.reset()

    def _run_avg(self, buf, base_rng, dtype, perf):
        net = self.net
        with span("window", k=len(buf), kind="averaging",
                  iteration=net.iteration_count):
            xs = jnp.stack([cast_feed(d.features, dtype) for d in buf])
            ys = jnp.stack([cast_feed(d.labels, dtype) for d in buf])
            rng = jax.random.fold_in(base_rng, net.iteration_count)
            # remainder batches (size not tiling the mesh) dispatch the
            # replicated-feed averaging program — same contract as the
            # sync path's remainder fallback
            rem = xs.shape[1] % self.n != 0
            key = (len(buf), rem)
            step = self._avg_steps.get(key)
            if step is None:
                step = self._avg_steps[key] = \
                    self._build_avg_step(replicated_feed=rem)
            with span("dispatch", k=len(buf)):
                net.params, net.state, net.opt_state, loss = step(
                    net.params, net.state, net.opt_state,
                    jnp.asarray(net.iteration_count, jnp.int32), rng, xs, ys)
            reg = get_registry()
            reg.counter("train.windows").inc()
            reg.counter("train.iterations").inc(len(buf))
            for p in perf:
                p.note_window(len(buf))
            for d in buf:
                self._notify(perf, d, loss)
                net.iteration_count += 1
        if self.step_callback is not None:
            self.step_callback(net, len(buf))

    def _notify(self, perf, ds, loss, etl_wait_ms: float = 0.0,
                device_ms: float = 0.0):
        net = self.net
        for p in perf:
            p.note_batch(ds.num_examples(), etl_wait_ms=etl_wait_ms,
                         device_ms=device_ms)
        for l in net.listeners:
            l.iteration_done(net, net.iteration_count, loss)
