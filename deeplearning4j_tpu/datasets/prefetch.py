"""Device-side prefetch: overlap host->device transfer with device compute.

Reference: datasets/iterator/AsyncDataSetIterator.java:30 prefetches on the
HOST; the reference's ETL discipline (PerformanceListener.java:111,178
reporting lastEtlTime per iteration) treats the feed path as a first-class
perf concern. On TPU the missing half is the host->device hop: a batch
shipped synchronously inside the step pays the full transfer latency
serially (a 407 ms/step transfer floor flattened the piped ResNet-50 row
to 0.008x the device-resident rate on the pre-PR-1 rig; not measured on
today's machine). JAX's async dispatch
makes the fix cheap — ``jax.device_put`` returns immediately while the
copy proceeds — so a background thread that ships batch N+1 while step N
computes hides the transfer entirely whenever step time exceeds the
transfer floor (the overlap discipline of SparkNet, arXiv:1511.06051, and
the weight-update sharding work, arXiv:2004.13336).

``DevicePrefetchIterator`` wraps any ``DataSetIterator`` and keeps
``depth`` batches in flight ON DEVICE. With a ``sharding``
(``NamedSharding``), ``device_put`` lands each batch pre-sharded, so
data-parallel training consumes its per-device shards with no gather or
reshard inside the jitted step. The consumer side measures the time it
actually BLOCKED waiting for a device batch — ``last_wait_ms`` /
``total_wait_ms`` — which is the honest per-iteration ETL tax (zero when
the pipeline keeps up), surfaced through
``optimize.listeners.PerformanceListener``.
"""
from __future__ import annotations

import queue
import threading
import time
from typing import List, Optional

import numpy as np

from ..telemetry import get_registry
from .dataset import DataSet, DataSetIterator
from .iterators import MultiDataSet


_NEVER_REUSE = object()    # slot sentinel: its buffer is aliased by a
                           # device array and must never be overwritten


def _definitely_copied(shipped, buf: np.ndarray) -> bool:
    """Did ``device_put`` genuinely COPY ``buf``? The CPU backend is
    zero-copy for suitably-aligned numpy buffers (the returned array
    ALIASES the source — the same property that makes the
    HostSyncDetector's transfer guard inert there), and the aliasing is
    per-buffer (alignment-dependent), so this must be checked on the
    actual shipped array, not probed per process. Host->accelerator
    transfers always copy; on a single CPU device the buffer pointers
    tell; anything unprovable counts as aliased (no reuse —
    correctness first)."""
    try:
        if all(d.platform != "cpu" for d in shipped.devices()):
            return True
        return shipped.unsafe_buffer_pointer() != buf.ctypes.data
    except Exception:
        return False


class _StagingPool:
    """Reusable host staging buffers for the float-cast path.

    Without it the producer allocates a fresh cast buffer for EVERY batch
    (``astype``) — at ResNet-50 batch sizes that is ~25MB of fresh pages
    per batch on the ship path (the ``resnet50_piped`` row measured
    0.047 GB/s through it). Slot-reuse safety is two-layered:
    ``device_put``'s source must stay intact until the transfer lands, so
    a slot blocks on the device array it last fed before overwriting — a
    no-op in steady state (that transfer is ``slots`` batches old by the
    time the slot rotates back), real back-pressure when the device falls
    behind. And a slot whose shipped array cannot be PROVEN a copy
    (zero-copy CPU aliasing, multi-shard arrays) is retired instead of
    reused — its buffer is leaked to the device array and a fresh one is
    allocated, which degrades exactly to the old per-batch-allocation
    behavior, never to corruption.
    """

    __slots__ = ("slots", "_pools", "_rr", "allocations", "pending_bytes")

    def __init__(self, slots: int):
        self.slots = max(2, int(slots))
        self._pools = {}    # (shape, dtype.str) -> [[buf, last_shipped]]
        self._rr = {}
        self.allocations = 0    # distinct buffers ever allocated (tests)
        self.pending_bytes = 0  # host bytes of the batch being shipped

    def stage(self, a: np.ndarray, dtype) -> list:
        """Cast-copy ``a`` into a pool slot; returns the slot (slot[0] is
        the buffer). Call ``mark(slot, shipped)`` after device_put."""
        key = (a.shape, np.dtype(dtype).str)
        pool = self._pools.setdefault(key, [])
        if len(pool) < self.slots:
            slot = [np.empty(a.shape, dtype), None]
            self.allocations += 1
            pool.append(slot)
        else:
            i = self._rr.get(key, 0)
            self._rr[key] = (i + 1) % self.slots
            slot = pool[i]
            if slot[1] is _NEVER_REUSE:
                # previous occupant aliased this buffer: retire it
                slot[0] = np.empty(a.shape, dtype)
                self.allocations += 1
                slot[1] = None
            elif slot[1] is not None:
                slot[1].block_until_ready()   # transfer landed: safe now
                slot[1] = None
        np.copyto(slot[0], a, casting="unsafe")
        return slot

    def mark(self, slot: list, shipped) -> None:
        slot[1] = (shipped if _definitely_copied(shipped, slot[0])
                   else _NEVER_REUSE)


class DevicePrefetchIterator(DataSetIterator):
    """Background-thread device prefetch wrapper.

    The producer thread pulls host batches from ``base`` (so host-side
    decode/augmentation overlaps too — subsumes AsyncDataSetIterator),
    ships every array with ``jax.device_put`` and enqueues the resulting
    device-resident DataSet into a queue of ``depth`` slots. The bounded
    queue is the back-pressure contract: at most ``depth`` batches sit
    ready plus one in the producer's hands, so a live stream feeding the
    base iterator blocks its publishers exactly as it would unwrapped.

    ``dtype``: optional float dtype every floating array is cast to on the
    HOST before shipping (integer arrays — token ids, uint8 image wire
    format — pass through, same rule as the solver's feed cast). Shipping
    uint8 and normalizing on device cuts wire traffic 4x vs f32. The cast
    goes through a reusable staging-buffer pool (``depth+2`` rotating
    slots per shape/dtype) instead of a fresh ``astype`` allocation per
    batch; a slot is only overwritten after its previous transfer landed.

    Bandwidth observability: the producer takes a BLOCKING transfer
    sample on the first batch of each epoch and every 64th after, and
    publishes the measured GB/s as the ``prefetch.host_to_device_gbps``
    telemetry gauge (also on ``self.host_to_device_gbps``) — a
    transport-limited feed path is attributed, not guessed.

    ``sharding``: optional ``jax.sharding.Sharding`` (or per-leaf target
    accepted by ``device_put``). When the leading dim of a batch does not
    tile the sharding (a remainder batch), the batch ships unsharded
    rather than failing mid-epoch.

    Early exit is clean: breaking out of (or erroring inside) the consuming
    loop closes the generator, which signals the producer to stop; the
    producer rechecks the stop flag on every queue-full tick and every
    base batch, so no thread is left shipping batches nobody will take.
    Exceptions raised by ``base`` surface in the consumer.

    Caveats shared with any prefetch (incl. the host AsyncDataSetIterator):
    batches already pulled from ``base`` but not yet consumed at an early
    abort are dropped — for a live stream that is up to ``depth`` + 1
    samples; and a base whose ``__iter__`` can block INDEFINITELY (a
    StreamingDataSetIterator with idle publishers) keeps its daemon
    producer parked inside the base until the next batch or end-of-stream,
    since the stop flag is only observable between base yields.
    """

    _TICK = 0.05   # stop-signal poll interval for a blocked producer

    def __init__(self, base: DataSetIterator, depth: int = 2, *,
                 sharding=None, dtype=None):
        if depth < 1:
            raise ValueError("depth must be >= 1")
        self.base = base
        self.depth = depth
        self.sharding = sharding
        self.dtype = None if dtype is None else np.dtype(dtype)
        self.last_wait_ms = 0.0     # consumer block time for the last batch
        self.total_wait_ms = 0.0    # cumulative over the current epoch
        self.batches = 0            # batches yielded in the current epoch
        # measured host->device bandwidth (GB/s) from the periodic blocking
        # samples in the producer; 0.0 until the first sample lands
        self.host_to_device_gbps = 0.0
        # cast staging buffers rotate across depth+2 slots (depth in the
        # queue + one in the producer's hands + one being consumed).
        # Each __iter__ builds its OWN pool (held by the producer closure;
        # this attribute tracks the newest for introspection): a stale
        # producer from a broken-out-of epoch can outlive stop.set() by
        # one batch, and two producers sharing slots could overwrite a
        # buffer whose transfer is still in flight.
        self._staging = _StagingPool(depth + 2)

    # ------------------------------------------------------------- shipping
    def _put_array(self, a, pool):
        """Host cast (floats -> self.dtype, through the reusable staging
        pool) + async device_put."""
        import jax
        if a is None:
            return None
        slot = None
        if not isinstance(a, jax.Array):
            a = np.asarray(a)
            if (self.dtype is not None and a.dtype.kind == "f"
                    and a.dtype != self.dtype):
                slot = pool.stage(a, self.dtype)
                a = slot[0]
            pool.pending_bytes += a.nbytes
            if slot is not None:
                shipped = self._put_host(a)
                pool.mark(slot, shipped)
                return shipped
        return self._put_host(a)

    def _put_host(self, a):
        import jax
        if self.sharding is not None:
            # explicit tiling probe (host-only shape math): a remainder
            # batch that doesn't tile the mesh ships unsharded — the
            # consuming jit reshards (or rejects) it exactly as it would
            # have without prefetch. A sharding that DOES tile but is
            # otherwise misconfigured (wrong mesh/devices) is NOT caught
            # here: device_put raises loudly rather than silently
            # degrading every batch to the unsharded path.
            tiles = True
            try:
                self.sharding.shard_shape(np.shape(a))
            except (ValueError, IndexError):
                tiles = False
            if tiles:
                return jax.device_put(a, self.sharding)
        return jax.device_put(a)

    def _put_any(self, v, pool):
        if isinstance(v, (list, tuple)):    # MultiDataSet-style per-input lists
            return [self._put_array(u, pool) for u in v]
        return self._put_array(v, pool)

    def _ship(self, ds, pool):
        """One host batch -> the same batch with device-resident arrays."""
        if isinstance(ds, MultiDataSet):
            return MultiDataSet(
                self._put_any(ds.features, pool),
                self._put_any(ds.labels, pool),
                None if ds.features_mask is None
                else self._put_any(ds.features_mask, pool),
                None if ds.labels_mask is None
                else self._put_any(ds.labels_mask, pool))
        return DataSet(self._put_any(ds.features, pool),
                       self._put_any(ds.labels, pool),
                       self._put_any(ds.features_mask, pool),
                       self._put_any(ds.labels_mask, pool),
                       metadata=getattr(ds, "metadata", None))

    # ------------------------------------------------------------ iteration
    def __iter__(self):
        q: "queue.Queue" = queue.Queue(maxsize=self.depth)
        stop = threading.Event()
        err: List[BaseException] = []
        _SENTINEL = object()
        self.last_wait_ms = 0.0
        self.total_wait_ms = 0.0
        self.batches = 0

        def offer(item) -> bool:
            """put() that gives up when the consumer went away."""
            while not stop.is_set():
                try:
                    q.put(item, timeout=self._TICK)
                    return True
                except queue.Full:
                    continue
            return False

        # Telemetry (telemetry/): ship latency + consumer stall histograms
        # and a queue-depth gauge replace the ad-hoc etl_wait_ms plumbing
        # as the shared reporting surface (the attributes below stay for
        # the PerformanceListener contract). All host-side clock reads —
        # nothing touches the in-flight device buffers.
        reg = get_registry()

        # this iteration's private staging pool: the producer closure owns
        # it, so a stale producer still draining from a previous __iter__
        # keeps ITS pool and can never corrupt this epoch's slots
        pool = _StagingPool(self.depth + 2)
        self._staging = pool

        def producer():
            import jax
            n_shipped = 0
            try:
                for ds in self.base:
                    if stop.is_set():
                        return
                    t_ship = time.perf_counter()
                    pool.pending_bytes = 0
                    shipped = self._ship(ds, pool)
                    # ship_ms observed BEFORE any blocking sample below, so
                    # the histogram (and its p99) measures the async
                    # dispatch path every batch, never the sampled wait
                    reg.histogram("prefetch.ship_ms").observe(
                        (time.perf_counter() - t_ship) * 1e3)
                    # periodic BLOCKING bandwidth sample (first batch of the
                    # epoch, then every 64th): device_put is async, so the
                    # unblocked ship time measures dispatch, not transfer —
                    # waiting for completion on a sampled batch gives the
                    # honest GB/s without serializing the steady state
                    if n_shipped % 64 == 0 and pool.pending_bytes:
                        # every array whose bytes were counted above —
                        # masks included, or the GB/s would overstate
                        jax.block_until_ready(
                            [v for v in (shipped.features, shipped.labels,
                                         shipped.features_mask,
                                         shipped.labels_mask)
                             if v is not None])
                        dt = time.perf_counter() - t_ship
                        if dt > 0:
                            self.host_to_device_gbps = \
                                pool.pending_bytes / dt / 1e9
                            if reg.enabled:
                                reg.gauge("prefetch.host_to_device_gbps") \
                                    .set(self.host_to_device_gbps)
                    n_shipped += 1
                    if not offer(shipped):
                        return
            except BaseException as e:     # surfaced on the consumer side
                err.append(e)
            finally:
                offer(_SENTINEL)

        t = threading.Thread(target=producer, daemon=True,
                             name="device-prefetch")
        t.start()
        try:
            while True:
                t0 = time.perf_counter()
                item = q.get()
                wait_ms = (time.perf_counter() - t0) * 1e3
                if item is _SENTINEL:
                    if err:
                        raise err[0]
                    return
                self.last_wait_ms = wait_ms
                self.total_wait_ms += wait_ms
                self.batches += 1
                if reg.enabled:
                    reg.histogram("prefetch.wait_ms").observe(wait_ms)
                    reg.gauge("prefetch.queue_depth").set(q.qsize())
                    reg.counter("prefetch.batches").inc()
                yield item
        finally:
            # break / exception / exhaustion: stop the producer and let it
            # notice within one tick (it polls `stop` on every queue-full
            # wait and before shipping each batch)
            stop.set()
            while True:                    # unblock a producer mid-put
                try:
                    q.get_nowait()
                except queue.Empty:
                    break
            # short, best-effort join: the producer notices the stop within
            # one tick unless it is parked inside a base that blocks
            # indefinitely (live stream, idle publishers) — the daemon
            # thread then dies with the process instead of stalling the
            # consumer here
            t.join(timeout=1.0)

    def etl_wait_ms_per_batch(self) -> float:
        """Mean consumer-side ETL wait over the current/last epoch."""
        return self.total_wait_ms / self.batches if self.batches else 0.0

    def windows(self, k: int):
        """Window mode: yield ``BatchWindow``s of ``k`` same-shape
        device-resident batches (the feed unit of the fused multi-step
        training path, ``fit(..., steps_per_dispatch=k)``), re-using the
        existing depth-bounded producer queue — the window is assembled
        from batches that were already shipped in the background, so
        windowing adds no transfer latency, only the ``jnp.stack``
        dispatch. Ragged/unstackable groups fall out as bare DataSets
        (see ``iter_windows``)."""
        return iter_windows(self, k)

    def reset(self):
        if hasattr(self.base, "reset"):
            self.base.reset()


class BatchWindow:
    """K same-shape batches destined for ONE fused K-step dispatch.

    Holds the individual ``DataSet``s (listeners still see per-step batch
    sizes) plus lazily-stacked ``[K, ...]`` feed arrays for the
    ``lax.scan`` training program. Stacking runs through ``jnp.stack`` on
    already-device-resident arrays, so it is one async dispatch, not a
    host round-trip.
    """

    __slots__ = ("datasets", "_stacked")

    def __init__(self, datasets):
        self.datasets = list(datasets)
        self._stacked = None

    def __len__(self):
        return len(self.datasets)

    def num_examples(self) -> int:
        return sum(d.num_examples() for d in self.datasets)

    def stacked(self, cast=None):
        """(xs, ys, lmasks, fmasks) stacked on a new leading K axis;
        masks are None when absent from every member batch. ``cast`` is
        applied per-array before stacking (the Solver's feed-boundary
        cast, so the fused path casts exactly like the per-step path)."""
        if self._stacked is None:
            import jax.numpy as jnp
            cast = cast if cast is not None else (lambda a: a)

            def stack(field):
                vals = [getattr(d, field) for d in self.datasets]
                if vals[0] is None:
                    return None
                return jnp.stack([cast(v) for v in vals])

            self._stacked = (stack("features"), stack("labels"),
                             stack("labels_mask"), stack("features_mask"))
        return self._stacked


def _window_stackable(group) -> bool:
    """Host-only metadata probe: can these batches be stacked into one
    [K, ...] feed? Requires single-array features/labels (multi-input
    MultiDataSet batches fall back to per-step), identical shapes, and
    consistent mask presence/shape across the group."""
    ref = group[0]
    if isinstance(ref, MultiDataSet):
        return False
    for field in ("features", "labels", "labels_mask", "features_mask"):
        vals = [getattr(d, field, None) for d in group]
        if any(isinstance(v, (list, tuple)) for v in vals):
            return False           # multi-input lists: per-step path
        none = [v is None for v in vals]
        if any(none):
            if not all(none):
                return False       # mask present in some batches only
            if field in ("features", "labels"):
                return False
            continue
        shapes = {np.shape(v) for v in vals}
        if len(shapes) != 1:
            return False           # ragged (e.g. short remainder batch)
    return True


def skip_batches(iterable, n: int):
    """Consume (without yielding) the first ``n`` batches and return an
    iterator over the rest — the mid-epoch-resume primitive shared by
    ``Solver._fit_epoch`` and ``ParallelWrapper._fit_epoch`` (the
    ElasticTrainer's bit-identical resume depends on both paths skipping
    identically). Tolerates streams shorter than ``n``."""
    src = iter(iterable)
    _miss = object()
    for _ in range(max(0, n)):
        if next(src, _miss) is _miss:
            break
    return src


def iter_windows(iterable, k: int):
    """Group a batch stream into ``BatchWindow``s of ``k``.

    Yields a ``BatchWindow`` for every run of ``k`` consecutive
    same-shape single-array batches, and bare ``DataSet``s for anything
    the fused path must not swallow: the ragged remainder at end of
    epoch, a batch whose shape differs mid-window (the whole group falls
    back — order is preserved), multi-input MultiDataSets, and windows of
    one. The consumer dispatches fused on windows and per-step on bare
    batches, so the stream stays order- and content-identical to the
    unwindowed iterator.
    """
    if k < 1:
        raise ValueError("steps_per_dispatch window size must be >= 1")
    buf = []
    for ds in iterable:
        buf.append(ds)
        if len(buf) == k:
            if k > 1 and _window_stackable(buf):
                yield BatchWindow(buf)
            else:
                yield from buf
            buf = []
    yield from buf        # ragged remainder: per-step fallback
