"""Bytes that a request's work needs, from shapes alone.

The yardstick of the roofline shares: each distinct field a request
names is read once as its stage input plane (int32 indices at stages 2 and
3, float32 values at stage 4: 4 bytes a value over the field or its
region), and each output is written once as float32.  It counts what the
request needs, not what one implementation moves, so a later kernel reads
against the same number.
"""
from __future__ import annotations

import math

WORD = 4  # bytes of one int32 index or float32 value


def interior(shape) -> tuple[int, ...]:
    """Shape of a stencil output: one cell trimmed on each side."""
    return tuple(max(0, d - 2) for d in shape)


def output_values(op: str, shape, n_components: int = 1) -> int:
    """Float32 values ``op`` writes for a field (or region) of ``shape``;
    a vector op over ``n_components`` fields writes one answer for all."""
    if op in ("mean", "std"):
        return 1
    n = math.prod(interior(shape))
    if op == "gradient":
        return len(shape) * n
    if op.startswith("derivative") or op in ("laplacian", "divergence"):
        return n
    if op == "curl":
        return n if n_components == 2 else 3 * n
    raise ValueError(f"no byte count for op {op!r}")


def request_bytes(ops, n_fields: int, shape, vector=(),
                  n_read: int | None = None) -> dict:
    """``{"read", "write"}`` bytes of the per-field ``ops`` over
    ``n_fields`` distinct fields and the ``vector`` ops (``(op,
    n_components)`` pairs), each field of ``shape`` (the field's, or its
    region's); ``n_read`` distinct fields are read (``n_fields`` by
    default)."""
    n_read = n_fields if n_read is None else n_read
    read = WORD * math.prod(shape) * n_read
    write = WORD * (n_fields * sum(output_values(op, shape) for op in ops)
                    + sum(output_values(op, shape, c) for op, c in vector))
    return {"read": read, "write": write}


def covered_bytes(n_fields: int, shape) -> int:
    """Bytes of original float32 field a request covers (``field_gbps``)."""
    return WORD * math.prod(shape) * n_fields
