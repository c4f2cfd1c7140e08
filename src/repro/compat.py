"""Thin wrappers over the jax API spellings this repo uses."""
from __future__ import annotations


import jax


def shard_map(f, *, mesh, in_specs, out_specs,
              axis_names: set[str] | None = None,
              check: bool | None = None):
    """``jax.shard_map`` with manual axes ``axis_names`` (all axes if None).

    ``check=None`` keeps the upstream default (replication checking ON) —
    callers opt *out* explicitly, never silently.
    """
    kwargs = {} if axis_names is None else {"axis_names": set(axis_names)}
    if check is not None:
        kwargs["check_vma"] = check
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kwargs)
