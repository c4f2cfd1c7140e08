"""Device mesh for the block-sharded analytics field store.

Importing this module never touches jax device state: meshes are built by
the functions below, on demand.
"""
from __future__ import annotations


import numpy as np
import jax


def auto_axis_types(n_axes: int) -> dict[str, tuple]:
    """``axis_types`` kwargs for ``jax.make_mesh``: every axis Auto."""
    return {"axis_types": (jax.sharding.AxisType.Auto,) * n_axes}


#: mesh axis name of the analytics block-shard dimension.
SHARD_AXIS = "shard"


def make_analytics_mesh(n_shards: int | None = None):
    """1-D ``("shard",)`` mesh for block-sharded analytics field stores.

    A :class:`repro.shard.ShardedFieldStore` partitions the *blocks* of
    one encoded field, which wants a single flat axis.  The
    mesh is host-count aware: devices are ordered by ``process_index``
    first, so consecutive shards land on co-located devices and a block
    stripe's scatter/psum merge crosses hosts as few times as the device
    topology allows.  ``n_shards`` caps the axis (default: every
    addressable device); asking for more shards than devices is an error —
    placement is physical, never oversubscribed.
    """
    devices = sorted(jax.devices(), key=lambda d: (d.process_index, d.id))
    n = len(devices) if n_shards is None else int(n_shards)
    if not (1 <= n <= len(devices)):
        raise ValueError(
            f"n_shards must be in [1, {len(devices)}] "
            f"(addressable devices), got {n_shards}")
    return jax.sharding.Mesh(np.asarray(devices[:n]), (SHARD_AXIS,),
                             **auto_axis_types(1))
