"""Device mesh helpers.

The TPU replacement for the reference's device-thread plumbing
(ParallelWrapper worker threads, Spark executors): a `jax.sharding.Mesh`
over which pjit/GSPMD emits the collectives (SURVEY.md §5.8).
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax import shard_map  # noqa: F401  (the sharded modules import it here)
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def make_mesh(shape: Optional[Tuple[int, ...]] = None,
              axis_names: Sequence[str] = ("data",),
              devices=None) -> Mesh:
    """Build a mesh. Default: all local devices on one 'data' axis."""
    devices = list(devices if devices is not None else jax.devices())
    if shape is None:
        shape = (len(devices),)
    n = int(np.prod(shape))
    if n > len(devices):
        raise ValueError(f"Mesh shape {shape} needs {n} devices, "
                         f"have {len(devices)}")
    arr = np.array(devices[:n]).reshape(shape)
    return Mesh(arr, tuple(axis_names))


def data_sharding(mesh: Mesh, axis: str = "data") -> NamedSharding:
    """Batch-dim sharding: leading dim split across the data axis."""
    return NamedSharding(mesh, P(axis))


def window_sharding(mesh: Mesh, axis: str = "data") -> NamedSharding:
    """[K, batch, ...] feed sharding for fused K-step windows: the scan
    axis replicated, the batch dim split across ``axis``."""
    return NamedSharding(mesh, P(None, axis))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
