"""Production mesh construction and logical-axis sharding rules.

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state).  Single pod: 16x16 = 256 chips (data x model); multi-pod:
2x16x16 = 512 chips (pod x data x model).  The ``pod`` axis extends data
parallelism across pods (gradient reduction crosses the inter-pod links —
exactly the collective the homomorphic compressed all-reduce targets).
"""
from __future__ import annotations


import numpy as np
import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.models import common as model_common


def auto_axis_types(n_axes: int) -> dict[str, tuple]:
    """``axis_types`` kwargs for ``jax.make_mesh``: every axis Auto."""
    return {"axis_types": (jax.sharding.AxisType.Auto,) * n_axes}


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **auto_axis_types(len(axes)))


#: mesh axis name of the analytics block-shard dimension.
SHARD_AXIS = "shard"


def make_analytics_mesh(n_shards: int | None = None):
    """1-D ``("shard",)`` mesh for block-sharded analytics field stores.

    The production mesh's ``(data, model)`` axes partition batches and
    weights; a :class:`repro.shard.ShardedFieldStore` partitions the
    *blocks* of one encoded field, which wants a single flat axis.  The
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


def make_host_mesh(shape: tuple[int, ...] = (1, 1), axes=("data", "model")):
    """Tiny mesh over however many (CPU) devices exist — smoke tests."""
    n = len(jax.devices())
    shape = (n, 1)
    return jax.make_mesh(shape, axes, **auto_axis_types(len(axes)))


def logical_rules(mesh, *, seq_shard: bool = False) -> dict[str, str | None]:
    """Logical axis -> mesh axis mapping for the current mesh.

    ``seq_shard`` additionally maps kv_seq -> model (sequence parallelism
    for very long KV caches / states).
    """
    has_pod = "pod" in mesh.axis_names
    batch_axes = ("pod", "data") if has_pod else ("data",)
    rules = dict(model_common.DEFAULT_RULES)
    rules.update({
        "batch": batch_axes if len(batch_axes) > 1 else batch_axes[0],
        "embed_w": "data",      # FSDP weight shard over data
        "heads": "model",
        "kv_heads": "model",
        "mlp": "model",
        "vocab": "model",
        "experts": "model",
        "expert_cap": batch_axes if len(batch_axes) > 1 else batch_axes[0],
        "lora": None,
        "kv_seq": "model" if seq_shard else None,
    })
    return rules


def activate(mesh, *, seq_shard: bool = False):
    """Install the mesh + rules into the model sharding context."""
    model_common.CTX.activate(mesh, logical_rules(mesh, seq_shard=seq_shard))


def deactivate():
    model_common.CTX.deactivate()


def spec_to_sharding(mesh, logical_spec: tuple[str | None, ...],
                     shape: tuple[int, ...], rules: dict[str, str | None]
                     ) -> NamedSharding:
    """One logical spec -> NamedSharding with divisibility fallback."""
    axes = []
    used = set()
    for dim, name in zip(shape, logical_spec):
        mesh_axis = rules.get(name) if name else None
        if mesh_axis is None:
            axes.append(None)
            continue
        ax_tuple = mesh_axis if isinstance(mesh_axis, tuple) else (mesh_axis,)
        if any(a in used for a in ax_tuple):
            axes.append(None)  # an axis may shard only one dim
            continue
        size = int(np.prod([mesh.shape[a] for a in ax_tuple]))
        if dim % size:
            axes.append(None)  # fallback: replicate non-divisible dims
        else:
            axes.append(mesh_axis)
            used.update(ax_tuple)
    return NamedSharding(mesh, P(*axes))


def tree_shardings(mesh, spec_tree, shape_tree, *, seq_shard: bool = False):
    """Map a logical-spec tree + shape tree -> NamedSharding tree."""
    rules = logical_rules(mesh, seq_shard=seq_shard)
    is_spec = lambda x: isinstance(x, tuple) and (
        len(x) == 0 or isinstance(x[0], (str, type(None))))
    return jax.tree.map(
        lambda spec, leaf: spec_to_sharding(mesh, spec, leaf.shape, rules),
        spec_tree, shape_tree, is_leaf=is_spec)


def batch_shardings(mesh, batch_specs):
    """Batch inputs: leading dim over (pod,)data, rest replicated."""
    has_pod = "pod" in mesh.axis_names
    baxes = ("pod", "data") if has_pod else "data"

    def of(leaf):
        if leaf.ndim == 0:
            return NamedSharding(mesh, P())
        b = leaf.shape[0]
        size = int(np.prod([mesh.shape[a] for a in (baxes if isinstance(baxes, tuple) else (baxes,))]))
        if b % size == 0:
            return NamedSharding(mesh, P(baxes, *([None] * (leaf.ndim - 1))))
        if not isinstance(baxes, tuple) or b % mesh.shape["data"] != 0:
            return NamedSharding(mesh, P(*([None] * leaf.ndim)))
        return NamedSharding(mesh, P("data", *([None] * (leaf.ndim - 1))))

    return jax.tree.map(of, batch_specs)
