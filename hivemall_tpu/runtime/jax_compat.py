"""The one sanctioned spelling of the shard_map API for the distributed paths.

Every trainer imports ``shard_map`` / ``pcast`` / ``named_mesh`` from here
instead of touching ``jax.shard_map`` / ``jax.lax.pcast`` directly;
graftcheck rule G009 enforces that (and its autofix performs the rewrite).
This module is the only file allowed to reference the raw APIs — it is
excluded from G009 by path. It targets the installed jax only
(``jax.shard_map`` with the ``check_vma=`` varying-manual-axes checker,
``jax.lax.pcast`` to re-tag device-invariant values as mesh-varying); when
the API moves again, this is the one file that changes.
"""

from __future__ import annotations

from typing import Callable, Optional, Sequence, Tuple

import jax

__all__ = ["shard_map", "pcast", "named_mesh"]


def named_mesh(axis_sizes: Sequence[int],
               axis_names: Tuple[str, ...] = ("batch", "model"),
               devices: Optional[Sequence] = None):
    """A ``jax.sharding.Mesh`` of shape ``axis_sizes`` over the FIRST
    ``prod(axis_sizes)`` devices, in enumeration order.

    This is the one sanctioned mesh-construction spelling for the serving
    placements (serving/placement.py) and the G008 analyzer resolves its
    axis names (default ``("batch", "model")`` — the serving convention).
    ``jax.make_mesh`` may REORDER devices for ICI locality; that reordering is a perf nicety training can afford but
    serving cannot take by default — stripe ownership must be a pure
    function of device index so (a) the process-wide sharded-jit cache can
    key on the device list and (b) a re-deploy on the same host places
    every stripe on the same chip it was warmed on. Enumeration order is
    also exactly what parallel/mesh.make_mesh{,_2d} use, so serving and
    training stripes of the same table land on the same devices."""
    import numpy as np

    if devices is None:
        devices = jax.devices()
    need = 1
    for s in axis_sizes:
        need *= int(s)
    if len(devices) < need:
        raise ValueError(
            f"named_mesh{tuple(axis_sizes)}: needs {need} devices, have "
            f"{len(devices)}")
    grid = np.asarray(devices[:need]).reshape(tuple(axis_sizes))
    from jax.sharding import Mesh

    return Mesh(grid, tuple(axis_names))


def shard_map(f: Optional[Callable] = None, *, mesh, in_specs, out_specs,
              check_vma: bool = True, **kwargs):
    """``jax.shard_map``, usable directly or decorator-style
    (``shard_map(mesh=..., ...)(fn)``)."""
    if f is None:
        return lambda g: shard_map(g, mesh=mesh, in_specs=in_specs,
                                   out_specs=out_specs,
                                   check_vma=check_vma, **kwargs)
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma,
                         **kwargs)


pcast = jax.lax.pcast
