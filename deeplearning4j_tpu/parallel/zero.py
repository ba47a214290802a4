"""ZeRO-style cross-replica sharding of the weight update.

Reference: "Automatic Cross-Replica Sharding of Weight Update in
Data-Parallel Training" (arXiv 2004.13336). In plain synchronous DP every
replica holds the FULL parameter tree plus the FULL updater state and
applies the identical update N times — for Adam that is 2x params of pure
duplication per chip, the single biggest cap on model size per device.
The fix is to exploit that the post-allreduce gradients are identical
everywhere: give each replica 1/N of the flattened update problem.

    reduce-scatter(grads)  ->  each replica owns the mean gradient for
                               ITS 1/N shard (half the collective bytes
                               of an all-reduce on top)
    local shard update     ->  updater state allocated SHARD-SIZED:
                               ~mesh-size x less optimizer memory
    all-gather(params)     ->  every replica re-materializes the full,
                               identical parameter tree for the forward

"Memory-efficient array redistribution through portable collective
communication" (arXiv 2112.01075) supplies the second half: the shard
layout is plain host metadata (bucket sizes + padding), so state saved on
one mesh shape re-shards onto another by all-gather -> re-slice — which is
what elastic recovery onto a shrunk mesh needs (see
:func:`make_zero_resharder`).

Layout. Leaves are grouped by ``(dtype, update rule, lr multiplier)`` so
every group's flat update is ONE homogeneous elementwise program — no
per-element masks, and therefore trivially bit-identical to the per-leaf
``MultiLayerUpdater.update`` math. Within a group, leaves are packed into
size-targeted buckets by :func:`~.overlap.build_bucket_schedule` (the same
schedule machinery as the overlapped-sync path, so each bucket's
reduce-scatter is an independently launchable collective that XLA can
overlap with the remaining backward). Each bucket is padded to a multiple
of the mesh size; shard ``k`` of a group is the concatenation of row ``k``
of every padded bucket reshaped ``[N, lb]``.

The engine plugs into the ``grad_sync`` + ``update_fn`` seam of
``train_step_math`` (optimize/solver.py) under ``shard_map``, so the fused
``steps_per_dispatch`` scan window carries the exact same sharded update
as the per-step path — structurally, not by convention. Stage 1 keeps the
bucketed all-reduce (identical collectives to ``overlap_sync``) and
slices the local shard; stage 2 replaces it with per-bucket
``psum_scatter`` (half the bytes on the wire). Both are pinned
bit-identical to the replicated update (tests/test_zero.py).
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..telemetry import get_registry, span
from ..telemetry.spans import record_external_span
from .overlap import DEFAULT_BUCKET_BYTES, build_bucket_schedule

__all__ = ["ZeroUpdateEngine", "is_zero_state", "make_zero_resharder",
           "ZERO_STATE_KEY"]

ZERO_STATE_KEY = "_zero_"


def is_zero_state(opt_state: Any) -> bool:
    """True if ``opt_state`` is the engine's sharded flat format (the
    marker is structural — a dict with the single ``_zero_`` key — so the
    tree stays pure arrays and flows through jit/scan/checkpointing)."""
    return isinstance(opt_state, dict) and set(opt_state) == {ZERO_STATE_KEY}


@dataclass(frozen=True)
class _ZeroBucket:
    """One reduce-scatter launch: ``indices`` are global leaf positions
    (params flatten order), packed flat to ``nb`` elements and padded to
    ``n_shards * lb``."""
    indices: Tuple[int, ...]
    sizes: Tuple[int, ...]
    nb: int
    lb: int


@dataclass(frozen=True)
class _ZeroGroup:
    """One homogeneous flat update: every member leaf shares ``dtype``,
    update ``rule`` and ``lr_mult``, so the whole shard updates as one
    elementwise program with a single traced-scalar learning rate."""
    rule: Any
    lr_mult: float
    dtype: Any
    buckets: Tuple[_ZeroBucket, ...]
    length: int                      # local shard elements (incl. padding)
    state_keys: Tuple[str, ...]


def _leaf_meta_from_net(net):
    """Per-leaf (rule-or-None, lr_mult, frozen_rule-or-None) aligned with
    ``jax.tree.leaves(net.params)``, derived from the updater's per-layer
    conf dispatch (``rule_for`` / ``_lr_mult``) via tree paths — the same
    resolution ``MultiLayerUpdater.update`` performs per leaf. A ``None``
    rule marks a frozen layer's leaf (excluded from the sharded update,
    params pass through untouched — the reference FrozenLayer contract);
    its underlying rule is returned separately so unshard can rebuild the
    init-shaped state the replicated format allocates for it."""
    upd = net.updater
    if getattr(upd, "grad_norm", None) not in (None, "none"):
        raise ValueError(
            "zero sharded update does not compose with gradient "
            "normalization: the per-layer norms need every full leaf, "
            "which no replica holds after the reduce-scatter — disable "
            "grad_norm or the zero_stage")
    paths, _ = jax.tree_util.tree_flatten_with_path(net.params)
    rules, mults, frozen = [], [], []
    for path, _leaf in paths:
        li = path[0].idx
        pname = path[1].key
        conf = upd.layer_confs[li]
        if getattr(conf, "frozen", False):
            rules.append(None)
            mults.append(1.0)
            frozen.append(upd.rule_for(conf))
            continue
        rules.append(upd.rule_for(conf))
        mults.append(float(upd._lr_mult(conf, pname)))
        frozen.append(None)
    return rules, mults, frozen


def _index_path(tree, path):
    """Follow a jax key path (SequenceKey/DictKey/GetAttrKey) into a
    pytree."""
    for k in path:
        if hasattr(k, "idx"):
            tree = tree[k.idx]
        elif hasattr(k, "key"):
            tree = tree[k.key]
        else:
            tree = getattr(tree, k.name)
    return tree


class ZeroUpdateEngine:
    """Sharded-update engine over one named mesh axis.

        eng = ZeroUpdateEngine.from_net(net, mesh, stage=2)
        ... inside shard_map:
        train_step_math(..., grad_sync=eng.grad_sync, update_fn=eng.update)

    ``stage=1``: grads are all-reduced per packed bucket (the same
    launch pattern as ``overlap_sync``) and each replica slices its
    shard; only the updater state is shard-sized. ``stage=2``: grads are
    reduce-scattered per bucket (``psum_scatter`` — each replica only
    ever receives its 1/N of the mean gradient, halving collective bytes
    vs the all-reduce). Both stages end in the same all-gather of
    updated params and are bit-identical to the replicated update on the
    test backend."""

    def __init__(self, params, rules, lr_mults, *, n_shards: int,
                 stage: int = 1, bucket_bytes: int = DEFAULT_BUCKET_BYTES,
                 axis: str = "data", mesh=None, frozen_rules=None):
        if stage not in (1, 2):
            raise ValueError(f"zero stage must be 1 or 2, got {stage}")
        if n_shards < 1:
            raise ValueError(f"n_shards must be >= 1, got {n_shards}")
        paths_leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
        leaves = [l for _, l in paths_leaves]
        if len(rules) != len(leaves) or len(lr_mults) != len(leaves):
            raise ValueError("rules/lr_mults must align with the params "
                             "leaves")
        self.n = int(n_shards)
        self.stage = stage
        self.axis = axis
        self.mesh = mesh
        self.bucket_bytes = bucket_bytes
        self.treedef = treedef
        self.leaf_paths = [p for p, _ in paths_leaves]
        self.leaf_shapes = [tuple(np.shape(l)) for l in leaves]
        self.leaf_dtypes = [jnp.asarray(l).dtype if not hasattr(l, "dtype")
                            else l.dtype for l in leaves]
        # frozen leaves keep their (never-updated) rule so unshard can
        # rebuild the init-shaped state the replicated format holds
        self.frozen_rules = (list(frozen_rules) if frozen_rules is not None
                             else [None] * len(leaves))
        self.groups = self._build_groups(leaves, rules, lr_mults)
        self._publish_gauges()

    @classmethod
    def from_net(cls, net, mesh, *, stage: int = 1,
                 bucket_bytes: int = DEFAULT_BUCKET_BYTES,
                 axis: str = "data") -> "ZeroUpdateEngine":
        rules, mults, frozen = _leaf_meta_from_net(net)
        # shard over the named axis only: on a (data, model) mesh the
        # update is sharded d ways along 'data' and replicated across
        # the model axis (identical to the 1-D layout on a 1-D mesh)
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        n_shards = int(sizes.get(axis, mesh.devices.size))
        return cls(net.params, rules, mults, n_shards=n_shards,
                   stage=stage, bucket_bytes=bucket_bytes, axis=axis,
                   mesh=mesh, frozen_rules=frozen)

    # ----------------------------------------------------------- layout
    def _build_groups(self, leaves, rules, lr_mults) -> Tuple[_ZeroGroup, ...]:
        order: List[tuple] = []
        members: Dict[tuple, List[int]] = {}
        for i, (rule, mult) in enumerate(zip(rules, lr_mults)):
            if rule is None:        # frozen: params pass through untouched
                continue
            key = (self.leaf_dtypes[i], rule, mult)
            if key not in members:
                members[key] = []
                order.append(key)
            members[key].append(i)
        groups = []
        for key in order:
            dtype, rule, mult = key
            idxs = members[key]
            sched = build_bucket_schedule([leaves[i] for i in idxs],
                                          self.bucket_bytes)
            buckets = []
            for b in sched.buckets:
                gidx = tuple(idxs[j] for j in b.indices)
                sizes = tuple(int(np.prod(self.leaf_shapes[i], dtype=np.int64))
                              for i in gidx)
                nb = sum(sizes)
                lb = -(-nb // self.n)        # ceil
                buckets.append(_ZeroBucket(gidx, sizes, nb, lb))
            length = sum(b.lb for b in buckets)
            state_keys = tuple(sorted(
                rule.init_one(jnp.zeros((1,), dtype)).keys()))
            groups.append(_ZeroGroup(rule, mult, dtype, tuple(buckets),
                                     length, state_keys))
        return tuple(groups)

    @property
    def num_reduce_launches(self) -> int:
        """Collective launches in the grad sync phase of one step (one
        per bucket, both stages)."""
        return sum(len(g.buckets) for g in self.groups)

    @property
    def collectives_per_step(self) -> int:
        """reduce launches + one all-gather per group (the fused
        state/loss pmean is the caller's extra launch)."""
        return self.num_reduce_launches + len(self.groups)

    @property
    def shard_state_bytes(self) -> int:
        """Per-replica updater-state bytes under sharding (set against
        ``replicated_state_bytes``, about 1/n of it)."""
        return sum(g.length * g.dtype.itemsize * len(g.state_keys)
                   for g in self.groups)

    @property
    def replicated_state_bytes(self) -> int:
        """What the same updater state costs per replica unsharded."""
        return sum(sum(b.nb for b in g.buckets) * g.dtype.itemsize
                   * len(g.state_keys) for g in self.groups)

    @property
    def gathered_bytes(self) -> int:
        """Bytes all-gathered per step (padded param shards, all groups)."""
        return sum(g.length * self.n * g.dtype.itemsize for g in self.groups)

    def _publish_gauges(self) -> None:
        reg = get_registry()
        if reg.enabled:
            reg.gauge("zero.shard_bytes").set(float(self.shard_state_bytes))
            reg.gauge("zero.gathered_bytes").set(float(self.gathered_bytes))
            reg.gauge("zero.groups").set(float(len(self.groups)))

    def sharding_meta(self) -> dict:
        """The checkpoint-manifest ``sharding`` block: enough host
        metadata to rebuild the exact shard layout (and to re-shard it
        onto a different mesh size — bucket element counts are
        mesh-size-independent, only ``lb`` padding changes)."""
        return {"format": "zero-flat", "axis": self.axis,
                "num_shards": self.n, "stage": self.stage,
                "bucket_bytes": int(self.bucket_bytes),
                "groups": [{"dtype": str(g.dtype),
                            "state_keys": list(g.state_keys),
                            "bucket_elems": [b.nb for b in g.buckets]}
                           for g in self.groups]}

    def meta_matches(self, meta: Optional[dict]) -> bool:
        """True if a manifest ``sharding`` block describes THIS layout
        (same mesh size and same per-group bucketing) — i.e. the saved
        state restores directly, no re-shard needed."""
        if not meta or meta.get("format") != "zero-flat":
            return False
        mine = self.sharding_meta()
        return (meta.get("num_shards") == mine["num_shards"]
                and meta.get("axis") == mine["axis"]
                and meta.get("groups") == mine["groups"])

    # ------------------------------------------------- traced pack/unpack
    def _pack_bucket(self, b: _ZeroBucket, leaves):
        """Flatten + pad one bucket's leaves to ``[n, lb]``."""
        if len(b.indices) == 1:
            flat = jnp.ravel(leaves[b.indices[0]])
        else:
            flat = jnp.concatenate([jnp.ravel(leaves[i]) for i in b.indices])
        pad = self.n * b.lb - b.nb
        if pad:
            flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
        return flat.reshape(self.n, b.lb)

    def _pack_group_local(self, g: _ZeroGroup, leaves, k):
        """This replica's shard of the group: row ``k`` of every padded
        bucket, concatenated."""
        parts = [jax.lax.dynamic_index_in_dim(self._pack_bucket(b, leaves),
                                              k, 0, keepdims=False)
                 for b in g.buckets]
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts)

    def _unpack_group(self, g: _ZeroGroup, full, out: list) -> None:
        """Scatter the all-gathered ``[n, length]`` group back into the
        param leaf list (row-major ``[n, lb]`` is exactly the padded
        bucket layout)."""
        off = 0
        for b in g.buckets:
            flat = full[:, off:off + b.lb].reshape(self.n * b.lb)
            pos = 0
            for i, size in zip(b.indices, b.sizes):
                out[i] = flat[pos:pos + size].reshape(self.leaf_shapes[i])
                pos += size
            off += b.lb

    # --------------------------------------------------- the update seam
    def grad_sync(self, grads):
        """The cross-replica gradient combine (must run with ``axis`` in
        scope, i.e. inside shard_map): per-group local mean-gradient
        shards, one collective launch per bucket — each an independent
        collective XLA can start while the backward still computes (the
        overlap_sync scheduling argument, same bucket machinery).
        Stage 1 all-reduces the packed bucket and slices this replica's
        row (full-bytes exchange, as arXiv 2004.13336's baseline
        sharding); stage 2 replaces it with ``psum_scatter`` so each
        replica only ever RECEIVES its 1/N of the mean gradient — half
        the bytes on the wire, elementwise the same reduction (pinned
        bit-identical). Both stages share one packing graph, so the
        backward fuses identically whichever collective is picked."""
        g_leaves, treedef = jax.tree.flatten(grads)
        if treedef != self.treedef:
            raise ValueError("grad tree does not match the zero layout — "
                             "rebuild the engine when the parameter "
                             "structure changes")
        shards = []
        for g in self.groups:
            parts = []
            for b in g.buckets:
                packed = self._pack_bucket(b, g_leaves)
                if self.stage == 1:
                    red = jax.lax.pmean(packed, self.axis)
                    k = jax.lax.axis_index(self.axis)
                    parts.append(jax.lax.dynamic_index_in_dim(
                        red, k, 0, keepdims=False))
                else:
                    parts.append(jax.lax.psum_scatter(
                        packed, self.axis, scatter_dimension=0,
                        tiled=False) / self.n)
            shards.append(parts[0] if len(parts) == 1
                          else jnp.concatenate(parts))
        return tuple(shards)

    def update(self, grads, opt_state, params, step):
        """Drop-in for ``MultiLayerUpdater.update`` under shard_map:
        apply the update rule to THIS replica's shard only (state is
        shard-sized), then all-gather the updated params. ``grads`` is
        whatever :meth:`grad_sync` produced. The per-element math is the
        per-leaf updater math verbatim — same rule, same traced-scalar
        lr, same dtype casts — so the gathered params are bit-identical
        to the replicated path."""
        if not is_zero_state(opt_state):
            raise ValueError(
                "zero update needs the engine's sharded opt state — "
                "convert with shard_opt_state() before dispatch")
        leaves, treedef = jax.tree.flatten(params)
        if treedef != self.treedef:
            raise ValueError("param tree does not match the zero layout — "
                             "rebuild the engine when the parameter "
                             "structure changes")
        st = opt_state[ZERO_STATE_KEY]
        k = jax.lax.axis_index(self.axis)
        out = list(leaves)
        new_st = []
        for gi, g in enumerate(self.groups):
            g_loc = grads[gi]
            p_loc = self._pack_group_local(g, leaves, k)
            s_loc = {key: v[0] for key, v in st[gi].items()}
            lr = g.rule.lr(step, g.lr_mult)
            upd, ns = g.rule.update_one(g_loc, s_loc, lr, step)
            new_loc = p_loc - upd.astype(p_loc.dtype)
            new_st.append({key: ns[key].astype(s_loc[key].dtype)[None]
                           for key in s_loc})
            full = jax.lax.all_gather(new_loc, self.axis, axis=0,
                                      tiled=False)
            self._unpack_group(g, full, out)
        return jax.tree.unflatten(treedef, out), \
            {ZERO_STATE_KEY: tuple(new_st)}

    # ------------------------------------------- host-side state plumbing
    def _sharding(self):
        from jax.sharding import NamedSharding, PartitionSpec as P
        if self.mesh is None:
            return None
        return NamedSharding(self.mesh, P(self.axis))

    def _place(self, arr):
        sh = self._sharding()
        return jax.device_put(arr, sh) if sh is not None else jnp.asarray(arr)

    def init_opt_state(self) -> dict:
        """Fresh (zeros) sharded updater state — the ``like`` tree for
        checkpoint restore, and the init for a net that has none yet."""
        groups = []
        for g in self.groups:
            groups.append({key: self._place(
                np.zeros((self.n, g.length), jnp.dtype(g.dtype)))
                for key in g.state_keys})
        return {ZERO_STATE_KEY: tuple(groups)}

    def shard_opt_state(self, opt_state) -> dict:
        """Pack a replicated per-leaf updater-state tree (the
        ``MultiLayerUpdater.init`` format) into the sharded flat format.
        Pure redistribution for every updated leaf —
        ``unshard_opt_state()`` round-trips them bitwise. A frozen leaf's
        state is not stored (the update never touches it; unshard
        rebuilds its init zeros) — NONZERO frozen state is refused
        loudly rather than silently zeroed."""
        if is_zero_state(opt_state):
            self.check_state(opt_state)
            return opt_state
        flat_state = self._leaf_state_list(opt_state)
        for i, fr in enumerate(self.frozen_rules):
            if fr is None:
                continue
            for key, v in flat_state[i].items():
                if np.any(np.asarray(v)):
                    raise ValueError(
                        f"frozen leaf {i} carries nonzero updater state "
                        f"({key!r}); the sharded format does not store "
                        f"frozen state (it is never updated) — zero it "
                        f"or unfreeze the layer before zero_stage "
                        f"training")
        groups = []
        for g in self.groups:
            per_key = {}
            for key in g.state_keys:
                rows = []
                for b in g.buckets:
                    flat = np.concatenate(
                        [np.asarray(flat_state[i][key]).ravel()
                         for i in b.indices])
                    pad = self.n * b.lb - b.nb
                    if pad:
                        flat = np.concatenate(
                            [flat, np.zeros((pad,), flat.dtype)])
                    rows.append(flat.reshape(self.n, b.lb))
                per_key[key] = self._place(np.concatenate(rows, axis=1))
            groups.append(per_key)
        return {ZERO_STATE_KEY: tuple(groups)}

    def unshard_opt_state(self, opt_state):
        """Rebuild the replicated per-leaf state tree from the sharded
        format (all-gather on host): the ``MultiLayerUpdater.init``
        shape. Frozen leaves get their rule's init (zeros) state back —
        the update never touched it, and ``shard_opt_state`` refused any
        nonzero frozen state — so the result serializes/loads like an
        ``updater.init`` tree; stateless leaves stay empty dicts."""
        self.check_state(opt_state)
        flat_state = [None] * len(self.leaf_shapes)
        for gi, g in enumerate(self.groups):
            for key in g.state_keys:
                full = np.asarray(opt_state[ZERO_STATE_KEY][gi][key])
                off = 0
                for b in g.buckets:
                    flat = full[:, off:off + b.lb].reshape(self.n * b.lb)
                    pos = 0
                    for i, size in zip(b.indices, b.sizes):
                        d = flat_state[i] or {}
                        d[key] = jnp.asarray(
                            flat[pos:pos + size].reshape(
                                self.leaf_shapes[i]))
                        flat_state[i] = d
                        pos += size
                    off += b.lb
        for i in range(len(flat_state)):
            if flat_state[i] is not None:
                continue
            fr = self.frozen_rules[i]
            if fr is not None:              # frozen: init-shaped zeros
                flat_state[i] = fr.init_one(
                    jnp.zeros(self.leaf_shapes[i], self.leaf_dtypes[i]))
            else:                           # stateless rule
                flat_state[i] = {}
        # re-nest per-leaf state dicts into the params treedef (each
        # param leaf position holds its state dict)
        return jax.tree.unflatten(self.treedef, flat_state)

    def _leaf_state_list(self, opt_state):
        """Per-param-leaf state dicts, aligned with the params flatten
        order: the replicated format mirrors the params containers with a
        ``{state_key: arr}`` dict at every param-leaf position, so each
        param leaf's PATH indexes its state dict directly. (Flattening
        with an is_leaf predicate instead cannot tell a stateless leaf's
        ``{}`` from a parameterless layer's empty container.)"""
        try:
            out = [_index_path(opt_state, p) for p in self.leaf_paths]
        except (KeyError, IndexError, TypeError) as e:
            raise ValueError(
                "replicated opt state does not align with the zero "
                "layout's param tree — was it built by this net's "
                f"updater.init? ({e})") from e
        if not all(isinstance(s, dict) for s in out):
            raise ValueError(
                "replicated opt state does not align with the zero "
                "layout's param tree: expected a {state_key: array} dict "
                "at every param-leaf position")
        return out

    def check_state(self, opt_state) -> None:
        """Validate a zero-format state against THIS layout (mesh size
        and group lengths) — a state restored for a different mesh must
        be re-sharded, not silently mis-sliced."""
        if not is_zero_state(opt_state):
            raise ValueError("not a zero sharded opt state")
        st = opt_state[ZERO_STATE_KEY]
        if len(st) != len(self.groups):
            raise ValueError(
                f"zero state has {len(st)} groups, layout has "
                f"{len(self.groups)} — re-shard it for this mesh")
        for g, s in zip(self.groups, st):
            if set(s) != set(g.state_keys):
                raise ValueError(
                    f"zero state keys {sorted(s)} != layout "
                    f"{sorted(g.state_keys)}")
            for key, v in s.items():
                if tuple(v.shape) != (self.n, g.length):
                    raise ValueError(
                        f"zero state leaf {key} has shape "
                        f"{tuple(v.shape)}, layout wants "
                        f"{(self.n, g.length)} — state saved on a "
                        f"different mesh size must be re-sharded "
                        f"(make_zero_resharder)")

    def reshard_state_leaf(self, gi: int, old_arr: np.ndarray,
                           old_n: int) -> np.ndarray:
        """Re-slice one group's state array saved on an ``old_n``-shard
        mesh into THIS layout (all-gather -> unpad per old bucket ->
        repad per new bucket) — arXiv 2112.01075's portable
        redistribution, done on host at restore time."""
        g = self.groups[gi]
        old_lbs = [-(-b.nb // old_n) for b in g.buckets]
        if old_arr.shape != (old_n, sum(old_lbs)):
            raise ValueError(
                f"state array shape {old_arr.shape} does not match an "
                f"{old_n}-shard layout of group {gi} "
                f"({(old_n, sum(old_lbs))})")
        rows, off = [], 0
        for b, old_lb in zip(g.buckets, old_lbs):
            flat = old_arr[:, off:off + old_lb].reshape(old_n * old_lb)[:b.nb]
            pad = self.n * b.lb - b.nb
            if pad:
                flat = np.concatenate([flat, np.zeros((pad,), flat.dtype)])
            rows.append(flat.reshape(self.n, b.lb))
            off += old_lb
        return np.concatenate(rows, axis=1)

    # ----------------------------------------------------------- profiling
    def profile(self, mesh=None, repeats: int = 3) -> dict:
        """Time each bucket's grad collective — THIS stage's collective:
        the stage-2 ``psum_scatter`` (events named ``reduce_scatter``) or
        the stage-1 bucket all-reduce + slice (``grad_allreduce``) — and
        each group's all-gather on the mesh (tiny jitted programs,
        best-of-``repeats``), emit cat="collective" trace events that
        tools/trace2summary.py folds into their own phase buckets, the
        gather half under a ``zero.allgather`` span, and refresh the
        ``zero.*`` gauges. Per-row ``bytes`` is the padded buffer the
        collective actually moves. Host-side tooling for dryrun/traces —
        the training step never calls this."""
        from jax.sharding import PartitionSpec as P
        from .mesh import shard_map
        mesh = mesh if mesh is not None else self.mesh
        if mesh is None:
            raise ValueError("profile() needs a mesh")
        reg = get_registry()
        reduce_name = "reduce_scatter" if self.stage == 2 else \
            "grad_allreduce"

        def scat(x):
            if self.stage == 1:
                red = jax.lax.pmean(x, self.axis)
                k = jax.lax.axis_index(self.axis)
                return jax.lax.dynamic_index_in_dim(red, k, 0)
            return jax.lax.psum_scatter(
                x, self.axis, scatter_dimension=0, tiled=False)[None] / self.n

        def gath(x):
            return jax.lax.all_gather(x[0], self.axis, axis=0, tiled=False)

        # ONE jitted callable per collective flavor, hoisted out of the
        # loops: jax's jit cache then compiles once per distinct
        # (shape, dtype) instead of once per bucket (real schedules
        # repeat bucket shapes — same fix as overlap.profile_schedule)
        jscat = jax.jit(shard_map(scat, mesh=mesh, in_specs=P(),
                                  out_specs=P(self.axis), check_vma=False))
        jgath = jax.jit(shard_map(gath, mesh=mesh, in_specs=P(),
                                  out_specs=P(), check_vma=False))

        def timed(jfn, buf):
            jax.block_until_ready(jfn(buf))
            best = float("inf")
            for _ in range(max(1, repeats)):
                t0 = time.perf_counter()
                jax.block_until_ready(jfn(buf))
                best = min(best, time.perf_counter() - t0)
            return best * 1e3

        rows = {"reduce_scatter": [], "all_gather": []}
        rs_ms = 0.0
        for gi, g in enumerate(self.groups):
            for bi, b in enumerate(g.buckets):
                buf = jnp.zeros((self.n, b.lb), g.dtype)
                ms = timed(jscat, buf)
                rs_ms += ms
                nbytes = self.n * b.lb * g.dtype.itemsize
                rows["reduce_scatter"].append(
                    {"group": gi, "bucket": bi, "bytes": nbytes,
                     "ms": round(ms, 4)})
                record_external_span(reduce_name, ms, cat="collective",
                                     bucket=bi, group=gi, bytes=nbytes)
        ag_ms = 0.0
        with span("zero.allgather", groups=len(self.groups)):
            for gi, g in enumerate(self.groups):
                buf = jnp.zeros((self.n, g.length), g.dtype)
                ms = timed(jgath, buf)
                ag_ms += ms
                rows["all_gather"].append(
                    {"group": gi,
                     "bytes": g.length * self.n * g.dtype.itemsize,
                     "ms": round(ms, 4)})
                record_external_span("all_gather", ms, cat="collective",
                                     group=gi,
                                     bytes=g.length * self.n
                                     * g.dtype.itemsize)
        self._publish_gauges()
        if reg.enabled:
            reg.gauge("zero.reduce_scatter_ms").set(rs_ms)
            reg.gauge("zero.allgather_ms").set(ag_ms)
        return {"reduce_scatter": rows["reduce_scatter"],
                "all_gather": rows["all_gather"],
                "reduce_scatter_ms": round(rs_ms, 4),
                "allgather_ms": round(ag_ms, 4),
                "shard_state_bytes": self.shard_state_bytes,
                "replicated_state_bytes": self.replicated_state_bytes}


def make_zero_resharder(engine: ZeroUpdateEngine):
    """A ``resharder`` for ``restore_latest_sharded_checkpoint``: when a
    checkpoint's manifest ``sharding`` block describes a DIFFERENT mesh
    size than ``engine``'s layout, rebuild the whole tree from the raw
    per-shard blocks on host, re-slicing every zero state array to the
    current layout (all-gather -> re-slice) and re-homing every other
    leaf onto its ``like`` sharding. Returns ``None`` when the saved
    layout already matches (caller restores directly). Needs every shard
    file visible (shared storage) — the elastic single-controller
    deployment this repo targets."""

    def _reshard(directory: str, step: int, like, manifest: dict):
        meta = (manifest or {}).get("sharding")
        if not meta or meta.get("format") != "zero-flat":
            return None
        if engine.meta_matches(meta):
            return None
        mine = engine.sharding_meta()
        if [g["bucket_elems"] for g in meta.get("groups", [])] != \
                [g["bucket_elems"] for g in mine["groups"]]:
            raise ValueError(
                "checkpoint zero layout has different group bucketing "
                "than the current engine (different net or bucket_bytes) "
                "— cannot re-shard")
        from ..util.distributed_checkpoint import load_checkpoint_arrays
        old_n = int(meta["num_shards"])
        leaves_np = load_checkpoint_arrays(directory, step)
        like_leaves, treedef = jax.tree.flatten(like)
        if len(leaves_np) != len(like_leaves):
            raise ValueError(
                f"checkpoint has {len(leaves_np)} leaves; 'like' tree "
                f"has {len(like_leaves)}")
        # zero state leaves appear in group order, one per state key —
        # the only leaves whose shapes legitimately differ from `like`
        expected = [gi for gi, g in enumerate(engine.groups)
                    for _ in g.state_keys]
        out, zi = [], 0
        for ln, lk in zip(leaves_np, like_leaves):
            shape = tuple(np.shape(lk))
            dtype = getattr(lk, "dtype", ln.dtype)
            if ln.shape == shape:
                arr = ln
            else:
                if zi >= len(expected):
                    raise ValueError(
                        f"unexpected shape mismatch: checkpoint "
                        f"{ln.shape} vs like {shape}")
                arr = engine.reshard_state_leaf(expected[zi], ln, old_n)
                zi += 1
                if arr.shape != shape:
                    raise ValueError(
                        f"re-sharded state {arr.shape} still does not "
                        f"match like {shape}")
            arr = arr.astype(dtype, copy=False)
            sharding = getattr(lk, "sharding", None)
            out.append(jax.device_put(arr, sharding)
                       if sharding is not None else jnp.asarray(arr))
        return jax.tree.unflatten(treedef, out)

    return _reshard
