"""Device mesh construction and sharding specs.

The TPU pod *is* the worker cluster: keyed operator state is sharded
over the ``shard`` mesh axis (the analog of the reference's worker
threads, ``/root/reference/src/run.rs:235-247``), and keyed exchange
rides ICI collectives instead of the reference's TCP mesh
(``src/timely.rs:806-812``).
"""

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "SHARD_AXIS",
    "key_sharding",
    "make_mesh",
    "replicated",
    "shard_map",
]


#: Mesh axis over which keyed state is sharded.
SHARD_AXIS = "shard"

shard_map = jax.shard_map


def make_mesh(
    n_devices: Optional[int] = None,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build a 1-D mesh over ``n_devices`` (default: all local
    devices) with the keyed-state shard axis."""
    if devices is None:
        devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    return Mesh(np.asarray(devices), (SHARD_AXIS,))


def key_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding for per-key state arrays: leading (slot) dim split
    over the shard axis."""
    return NamedSharding(mesh, P(SHARD_AXIS))


def replicated(mesh: Mesh) -> NamedSharding:
    """Fully-replicated sharding (for small broadcast operands)."""
    return NamedSharding(mesh, P())
