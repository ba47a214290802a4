"""Overlapped gradient synchronization: bucketed backward-overlap collectives.

Reference: the async ``VoidParameterServer``/``EncodingHandler`` exchange hid
collective cost behind compute by design (SilentTrainingDriver.java:109-142
streams updates while workers keep training). The TPU-native sync path lost
that: one monolithic post-backward sweep of per-leaf ``pmean`` binds —
O(leaves) collective launches, all serialized after the last gradient is
produced (``collective_overhead_by_mesh`` on virtual CPU devices: 6.9ms ->
41.2ms from mesh 1 to 8, ~44% of an 8-device step).

Two techniques close the gap (PAPERS.md):
- arXiv:2004.13336 (cross-replica weight-update sharding): collectives
  scheduled so ICI traffic overlaps the remaining backward FLOPs. Here the
  lever is DATA DEPENDENCE, not program order: each bucket's all-reduce
  depends only on its own leaves, so XLA's latency-hiding scheduler can
  launch it as soon as those gradients exist, while the rest of the
  backward is still computing. Buckets are packed in REVERSE leaf order
  because the backward produces the last layers' gradients first — the
  first bucket closes (and its collective becomes launchable) earliest.
- arXiv:1905.04035 (densifying assumed-sparse tensors): many small
  messages cost latency, not bandwidth. Small leaves are flattened into
  one contiguous bucket buffer and all-reduced as a SINGLE dense array —
  one launch per ~4MB bucket instead of one per leaf (161 for ResNet-50).
  Leaves at or above the bucket size skip the pack/unpack copy entirely
  (their own launch is already bandwidth-bound).

The schedule is host-side metadata (leaf indices + byte sizes); the psum
math is unchanged — ``bucketed_pmean`` is elementwise bit-identical to the
per-leaf sweep on the test backend (grouping does not change any element's
reduction), pinned by tests/test_overlap_sync.py across bucket sizes.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Any, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..telemetry import get_registry
from ..telemetry.spans import record_external_span

DEFAULT_BUCKET_BYTES = 4 * 2 ** 20      # ~4MB: the DDP-proven sweet spot


@dataclass(frozen=True)
class GradBucket:
    """One collective launch: ``indices`` are leaf positions (flatten
    order). A multi-leaf bucket is packed into one flat buffer; a
    singleton bucket ships its leaf directly (no pack/unpack copy)."""
    indices: Tuple[int, ...]
    nbytes: int

    def __len__(self) -> int:
        return len(self.indices)


class BucketSchedule:
    """Size-targeted partition of a gradient pytree into collective
    buckets. Built ONCE per (tree structure, bucket_bytes) on the host;
    applying it (``bucketed_pmean``) is pure traced math."""

    def __init__(self, buckets: List[GradBucket], treedef,
                 leaf_shapes: List[tuple], leaf_dtypes: List[Any],
                 bucket_bytes: int):
        self.buckets = buckets
        self.treedef = treedef
        self.leaf_shapes = leaf_shapes
        self.leaf_dtypes = leaf_dtypes
        self.bucket_bytes = bucket_bytes
        self.total_bytes = sum(b.nbytes for b in buckets)

    def __len__(self) -> int:
        return len(self.buckets)

    @property
    def num_leaves(self) -> int:
        return len(self.leaf_shapes)

    def describe(self) -> List[dict]:
        """Host-side summary rows (telemetry / dryrun)."""
        return [{"bucket": i, "leaves": len(b), "bytes": b.nbytes}
                for i, b in enumerate(self.buckets)]


def build_bucket_schedule(tree, bucket_bytes: int = DEFAULT_BUCKET_BYTES
                          ) -> BucketSchedule:
    """Partition ``tree``'s leaves into collective buckets of ~``bucket_bytes``.

    Packing runs over the leaves in REVERSE flatten order (the backward
    pass produces the last parameters' gradients first, so the tail-end
    bucket is complete — and its all-reduce launchable — while the head of
    the model is still differentiating). A leaf whose own size reaches
    ``bucket_bytes`` closes the current bucket and ships as a singleton;
    leaves of different dtypes never share a bucket (the packed buffer is
    one dense array).
    """
    if bucket_bytes < 1:
        raise ValueError(f"bucket_bytes must be >= 1, got {bucket_bytes}")
    leaves, treedef = jax.tree.flatten(tree)
    if not leaves:
        raise ValueError("cannot bucket an empty pytree")
    shapes = [tuple(np.shape(l)) for l in leaves]
    dtypes = [jnp.asarray(l).dtype if not hasattr(l, "dtype") else l.dtype
              for l in leaves]
    nbytes = [int(np.prod(s, dtype=np.int64)) * dt.itemsize
              for s, dt in zip(shapes, dtypes)]

    buckets: List[GradBucket] = []
    cur: List[int] = []
    cur_bytes = 0
    cur_dtype = None

    def close():
        nonlocal cur, cur_bytes, cur_dtype
        if cur:
            buckets.append(GradBucket(tuple(cur), cur_bytes))
        cur, cur_bytes, cur_dtype = [], 0, None

    for i in reversed(range(len(leaves))):
        if nbytes[i] >= bucket_bytes:
            close()
            buckets.append(GradBucket((i,), nbytes[i]))
            continue
        if cur_dtype is not None and dtypes[i] != cur_dtype:
            close()
        cur.append(i)
        cur_bytes += nbytes[i]
        cur_dtype = dtypes[i]
        if cur_bytes >= bucket_bytes:
            close()
    close()
    return BucketSchedule(buckets, treedef, shapes, dtypes, bucket_bytes)


def _check_tree(schedule: BucketSchedule, leaves, treedef) -> None:
    if treedef != schedule.treedef or len(leaves) != schedule.num_leaves:
        raise ValueError(
            f"tree does not match the bucket schedule it was built for "
            f"({len(leaves)} leaves vs {schedule.num_leaves}) — rebuild the "
            f"schedule when the parameter structure changes")


def bucketed_pmean(tree, schedule: BucketSchedule, axis: str = "data"):
    """Per-bucket all-reduce mean of ``tree`` (must be called with ``axis``
    in scope, i.e. inside shard_map). Multi-leaf buckets are packed into
    one flat buffer (ONE psum launch), singletons ship directly. Each
    bucket's launch depends only on its own leaves, so XLA's scheduler can
    start it while gradients for other buckets are still being computed.

    Elementwise identical to ``jax.tree.map(pmean)`` — grouping never
    changes any element's reduction — at O(buckets) launches instead of
    O(leaves)."""
    leaves, treedef = jax.tree.flatten(tree)
    _check_tree(schedule, leaves, treedef)
    out = list(leaves)
    for b in schedule.buckets:
        if len(b) == 1:
            i = b.indices[0]
            out[i] = jax.lax.pmean(leaves[i], axis)
            continue
        flat = jnp.concatenate([jnp.ravel(leaves[i]) for i in b.indices])
        red = jax.lax.pmean(flat, axis)
        off = 0
        for i in b.indices:
            n = int(np.prod(schedule.leaf_shapes[i], dtype=np.int64))
            out[i] = jax.lax.dynamic_slice_in_dim(red, off, n).reshape(
                schedule.leaf_shapes[i])
            off += n
    return jax.tree.unflatten(treedef, out)


def fused_pmean(tree, axis: str = "data"):
    """ONE variadic psum bind for a whole pytree (vs ``tree.map``'s
    per-leaf binds): ``lax.pmean`` flattens the tree and binds every leaf
    in a single primitive call. Used to collapse the averaging path's
    separate params/state/opt_state sweeps into one launch; for O(buckets)
    launch-count control use ``bucketed_pmean``."""
    return jax.lax.pmean(tree, axis)


# --------------------------------------------------------------- profiling
def profile_schedule(mesh, schedule: BucketSchedule, axis: str = "data",
                     repeats: int = 3) -> dict:
    """Time each bucket's all-reduce on ``mesh`` (one tiny jitted program
    per bucket, best-of-``repeats``), emit a per-bucket Chrome-trace event
    (cat="collective") under the current span path, and set the
    ``parallel.collective_ms`` gauge to the total. Host-side tooling for
    dryrun/traces — the training step itself never calls this."""
    from jax.sharding import PartitionSpec as P
    from .mesh import shard_map

    reg = get_registry()
    rows = []
    total_ms = 0.0
    # ONE jitted callable for every bucket: jax's jit cache then compiles
    # once per distinct (elems, dtype) instead of once per bucket (real
    # schedules repeat bucket shapes — ~4MB buckets of one dtype)
    fn = jax.jit(shard_map(lambda g: jax.lax.pmean(g, axis), mesh=mesh,
                           in_specs=P(), out_specs=P(), check_vma=False))
    for i, b in enumerate(schedule.buckets):
        elems = b.nbytes // schedule.leaf_dtypes[b.indices[0]].itemsize
        buf = jnp.zeros((max(1, elems),), schedule.leaf_dtypes[b.indices[0]])
        jax.block_until_ready(fn(buf))
        best = float("inf")
        for _ in range(max(1, repeats)):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(buf))
            best = min(best, time.perf_counter() - t0)
        ms = best * 1e3
        total_ms += ms
        rows.append({"bucket": i, "leaves": len(b), "bytes": b.nbytes,
                     "ms": round(ms, 4)})
        record_external_span("bucket_psum", ms, cat="collective",
                             bucket=i, bytes=b.nbytes, leaves=len(b))
    if reg.enabled:
        reg.gauge("parallel.collective_ms").set(total_ms)
        reg.gauge("parallel.bucket_count").set(len(schedule))
    return {"buckets": rows, "collective_ms": round(total_ms, 4)}
