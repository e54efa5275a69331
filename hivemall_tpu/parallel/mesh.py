"""Device mesh helpers.

The reference scales out over Hadoop mappers + a Netty parameter-server fleet
(ref: SURVEY.md §2.18). TPU-native, the workers are devices in a
jax.sharding.Mesh and synchronization is XLA collectives over ICI (single
slice) / DCN (multi-slice) — no TCP path exists.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

WORKER_AXIS = "workers"
SHARD_AXIS = "shards"


def make_mesh(n_devices: Optional[int] = None, axis_name: str = WORKER_AXIS,
              devices: Optional[Sequence] = None) -> Mesh:
    """A 1-D data-parallel mesh over the available devices.

    Multi-host note: jax.devices() returns the global device list under
    multi-process JAX, so the same code scales from 1 chip to a multi-host pod
    with DCN collectives inserted by XLA automatically.
    """
    if devices is None:
        devices = jax.devices()
    if n_devices is not None:
        if len(devices) < n_devices:
            raise ValueError(
                f"need {n_devices} devices, have {len(devices)}")
        devices = devices[:n_devices]
    return Mesh(np.asarray(devices), (axis_name,))


def make_mesh_2d(n_replicas: int, n_shards: int,
                 replica_axis: str = WORKER_AXIS, shard_axis: str = SHARD_AXIS,
                 devices: Optional[Sequence] = None) -> Mesh:
    """A 2-D mesh: `n_replicas` data-parallel replicas x `n_shards` feature
    stripes — the reference's actual topology of N mapper clients training
    against M feature-sharded MIX servers (ref: MixRequestRouter.java:56-60
    routing under multiple concurrent clients, MixServerHandler.java:118-158).
    Lay the shard axis innermost so the per-row psums ride the fastest ICI
    links."""
    if devices is None:
        devices = jax.devices()
    need = n_replicas * n_shards
    if len(devices) < need:
        raise ValueError(f"need {need} devices, have {len(devices)}")
    grid = np.asarray(devices[:need]).reshape(n_replicas, n_shards)
    return Mesh(grid, (replica_axis, shard_axis))
