"""Blockwise fixed-rate encoding (paper §IV "Encoding").

The paper's encoder records, per block, the number of bits needed for the
largest-magnitude residual plus a sign plane.  We use the equivalent zigzag
formulation (``u = (p << 1) ^ (p >> 31)``): the zigzag width equals the
paper's (magnitude bits + 1 sign bit) and packs signs and magnitudes in one
plane — identical size accounting, branch-free SIMD decode.

Two packers are provided:

* **Device packer** (`pack_uniform` / `unpack_uniform`): packs at a *uniform*
  static width (shape-stable under jit; see DESIGN.md §3) using a
  segment-sum shift-or — O(n) memory, no per-bit materialization.  This is
  the wire/in-memory format used by compressed collectives and the KV cache.

* **Host serializer** (`serialize` / `deserialize`): exact per-block
  variable-rate byte stream (the paper's storage format) for checkpoints and
  compression-ratio benchmarks.  Vectorized numpy, no Python per-value loops.
"""
from __future__ import annotations

import struct

import numpy as np
import jax
import jax.numpy as jnp

from repro.kernels import ops as kernel_ops

from . import blocking
from .stages import Compressed, Encoded, Scheme

# v2: padding values are stored at width 0 (stream length == the valid-only
# `serialized_bits` accounting); v1 packed them at full block width, so v1
# blobs must be rejected, not misaligned-decoded.
_MAGIC = b"HSZ2"

# ---------------------------------------------------------------------------
# zigzag
# ---------------------------------------------------------------------------

def zigzag(p: jax.Array) -> jax.Array:
    """Map signed int32 -> unsigned-ordered uint32 (small |p| -> small u)."""
    return ((p << 1) ^ (p >> 31)).astype(jnp.uint32)


def unzigzag(u: jax.Array) -> jax.Array:
    ui = u.astype(jnp.int32)
    return (ui >> 1) ^ -(ui & 1)


# ---------------------------------------------------------------------------
# per-block exact bitwidths (size accounting / serialization)
# ---------------------------------------------------------------------------

def bitwidth_per_block(residuals: jax.Array, block: tuple[int, ...]) -> jax.Array:
    """Exact fixed-rate width (bits/value, sign incl.) per block, grid order."""
    u = zigzag(residuals)
    blocked = blocking.to_blocked(u, block)
    nd = len(block)
    maxu = jnp.max(blocked, axis=tuple(range(nd, 2 * nd)))
    # bits = 32 - clz(maxu); clz(0) == 32 -> width 0 (constant block fast path)
    bw = 32 - jax.lax.clz(maxu.astype(jnp.int32))
    return jnp.maximum(bw, 0).reshape(-1).astype(jnp.int32)


def serialized_bits(bitwidths: jax.Array, valid_counts: jax.Array, *,
                    meta_bits_per_block: int, global_meta_bits: int = 0) -> jax.Array:
    """Exact serialized size in bits: payload + per-block header + metadata.

    Per-block header = 6-bit width field (packed to a byte in `serialize`)
    + per-block scheme metadata (32-bit block mean for HSZx-family, 0 for
    HSZp-family).  ``global_meta_bits`` accounts metadata serialized once per
    stream (the HSZp-family 32-bit anchor slot) so Lorenzo compression ratios
    are not inflated relative to HSZx.

    The payload sum accumulates in f32 (int32 overflows past 2^31 payload
    bits — a ~1e8-element field at 16 bits/value; f32 keeps the sum exact up
    to 2^24 and within ~1e-7 relative beyond, ample for size accounting).
    """
    payload = jnp.sum(bitwidths * valid_counts, dtype=jnp.float32)
    header = bitwidths.shape[0] * (8 + meta_bits_per_block)
    return payload + header + global_meta_bits + 8 * 64  # + fixed global header


# ---------------------------------------------------------------------------
# device packer: uniform width, shape-stable
# ---------------------------------------------------------------------------

def words_for(n_values: int, bits: int) -> int:
    return -(-(n_values * bits) // 32) if bits > 0 else 0


def pack_uniform(u_flat: jax.Array, bits: int) -> jax.Array:
    """Pack ``n`` zigzag values at static width ``bits`` into uint32 words.

    Each value lands at bit offset ``i*bits``; its (<=2) word contributions are
    scatter-summed.  Fixed-rate => bit ranges are disjoint => sum == bitwise-or.
    """
    n = u_flat.shape[0]
    if bits == 0:
        return jnp.zeros((0,), jnp.uint32)
    if bits == 32:
        return u_flat.astype(jnp.uint32)
    nw = words_for(n, bits)
    mask = jnp.uint32((1 << bits) - 1)
    u = u_flat.astype(jnp.uint32) & mask
    offs = jnp.arange(n, dtype=jnp.uint32) * jnp.uint32(bits)
    widx = (offs >> 5).astype(jnp.int32)
    shift = offs & jnp.uint32(31)
    low = u << shift                      # uint32 shift drops overflow bits
    carry = shift > jnp.uint32(32 - bits)  # spills into the next word?
    high_shift = jnp.where(carry, jnp.uint32(32) - shift, jnp.uint32(31))
    high = jnp.where(carry, u >> high_shift, jnp.uint32(0))
    out = jax.ops.segment_sum(low, widx, num_segments=nw + 1)
    out = out + jax.ops.segment_sum(high, widx + 1, num_segments=nw + 1)
    return out[:nw].astype(jnp.uint32)


def unpack_uniform(payload: jax.Array, n: int, bits: int) -> jax.Array:
    """Inverse of :func:`pack_uniform`: recover ``n`` zigzag values."""
    if bits == 0:
        return jnp.zeros((n,), jnp.uint32)
    if bits == 32:
        return payload[:n].astype(jnp.uint32)
    offs = jnp.arange(n, dtype=jnp.uint32) * jnp.uint32(bits)
    widx = (offs >> 5).astype(jnp.int32)
    return unpack_words(payload, widx, widx + 1, offs & jnp.uint32(31), bits)


def encode_device(c: Compressed, bits: int) -> Encoded:
    """Bit-pack a :class:`Compressed` field at uniform static width ``bits``.

    Residuals wider than ``bits`` saturate in zigzag space, which keeps the
    error bounded by the *dequantization* of the clamp — callers choose
    ``bits`` >= max bitwidth (host-read) for losslessness, or budget bits and
    rely on error feedback (``repro.comm``).
    """
    u = zigzag(c.residuals.reshape(-1))
    if bits < 32:
        u = jnp.minimum(u, jnp.uint32((1 << bits) - 1))
    payload = pack_uniform(u, bits)
    return Encoded(
        payload=payload, metadata=c.metadata, bitwidths=c.bitwidths, eps=c.eps,
        valid_counts=c.valid_counts, scheme=c.scheme, shape=c.shape,
        padded_shape=c.padded_shape, block=c.block, orig_dtype=c.orig_dtype, bits=bits,
    )


def decode_device(e: Encoded) -> Compressed:
    """Stage-2 decode: unpack the payload back to residuals (D_p).

    Runs the Pallas bitplane-unpack kernel when kernels are enabled
    (``REPRO_KERNELS`` != ``off``), the XLA gather-shift path otherwise —
    both recover the exact packed integers, so the choice is invisible
    downstream (pinned in ``tests/test_fused_kernels.py``).  The region
    path (:func:`decode_region`) stays on the XLA word-gather: its cost
    scales with the gathered words, which a dense-grid kernel would void.
    """
    n = 1
    for s in e.padded_shape:
        n *= s
    if kernel_ops.kernels_enabled():
        u = kernel_ops.unpack(e.payload, n, e.bits)
    else:
        u = unpack_uniform(e.payload, n, e.bits)
    residuals = unzigzag(u).reshape(e.padded_shape)
    return Compressed(
        residuals=residuals, metadata=e.metadata, bitwidths=e.bitwidths, eps=e.eps,
        valid_counts=e.valid_counts, scheme=e.scheme, shape=e.shape,
        padded_shape=e.padded_shape, block=e.block, orig_dtype=e.orig_dtype,
    )


# ---------------------------------------------------------------------------
# region fast path: gather-unpack only the words covering a block subset
# ---------------------------------------------------------------------------

def unpack_words(words: jax.Array, pos0, pos1, shift, bits: int) -> jax.Array:
    """Unpack values whose low / carry words sit at ``pos0`` / ``pos1`` of
    ``words``, ``shift`` bits in (the packer's word/shift/mask arithmetic).

    ``pos1`` may be ``len(words)``: that carry word reads as zero through a
    fill-mode gather, never from a zero-padded copy of ``words`` — XLA
    compiles a gather from a padded copy of a large payload slowly."""
    mask = jnp.uint32(0xFFFFFFFF if bits == 32 else (1 << bits) - 1)
    shift = jnp.asarray(shift)
    lo = words[jnp.asarray(pos0)] >> shift
    carry = shift > jnp.uint32(32 - bits)
    hi_shift = jnp.where(carry, jnp.uint32(32) - shift, jnp.uint32(31))
    hi = words.at[jnp.asarray(pos1)].get(mode="fill", fill_value=0)
    return (lo | jnp.where(carry, hi << hi_shift, jnp.uint32(0))) & mask


def unpack_gather(payload: jax.Array, *, word_idx=None, pos0, pos1, shift,
                  bits: int) -> jax.Array:
    """Unpack a *subset* of a uniform-width payload via static word gathers.

    ``word_idx`` selects the only payload words read; ``pos0``/``pos1``/
    ``shift`` (host-computed, static — see ``repro.core.region``) address each
    requested value's low/high word within that gathered set.  Cost scales
    with the gathered words, not the field.  ``word_idx=None`` means
    ``payload`` *is* the gathered word set already (the sharded store's
    scatter/psum word merge produces exactly that — ``repro.shard.exec``).
    """
    m = int(jnp.shape(pos0)[0])
    if bits == 0:
        return jnp.zeros((m,), jnp.uint32)
    words = payload if word_idx is None else payload[jnp.asarray(word_idx)]
    return unpack_words(words, pos0, pos1, shift, bits)


def decode_region(e: Encoded, plan) -> Compressed:
    """Region fast path: stage-2 decode of only ``plan``'s gathered blocks.

    ``plan`` is a :class:`repro.core.region.RegionPlan`; the result is the
    honest sub-field over the gathered blocks (metadata / bitwidths / valid
    counts restricted to them), never the full residual array.  nd plans
    address the payload by indices built on device
    (:meth:`~repro.core.region.RegionPlan.value_words`); 1-D plans gather
    their static word set.
    """
    if plan.scheme.is_nd and e.bits > 0:
        w0, shift = plan.value_words(e.bits)
        u = unpack_words(e.payload, w0, w0 + 1, shift, e.bits)
    else:
        gi = plan.payload_gather(e.bits)
        u = unpack_gather(e.payload, word_idx=gi.word_idx, pos0=gi.pos0,
                          pos1=gi.pos1, shift=gi.shift, bits=e.bits)
    residuals = unzigzag(u).reshape(plan.sub_padded_shape)
    return plan.assemble(residuals, e)


# ---------------------------------------------------------------------------
# host serializer: exact per-block variable rate (the paper's storage format)
# ---------------------------------------------------------------------------

def _np_pack_bits(values: np.ndarray, widths_per_value: np.ndarray, total_bits: int) -> np.ndarray:
    """Scatter-pack uint32 ``values`` with per-value ``widths`` into a bitstream."""
    offs = np.zeros(values.shape[0], dtype=np.int64)
    np.cumsum(widths_per_value[:-1], out=offs[1:])
    nw = int(-(-total_bits // 32))
    # +2: zero-width values (padding / constant blocks) sitting at the very
    # end of the stream index up to word nw+1 with a zero contribution
    buf = np.zeros(nw + 2, dtype=np.uint64)
    widx = offs >> 5
    shift = (offs & 31).astype(np.uint64)
    v = values.astype(np.uint64)
    np.add.at(buf, widx, v << shift)          # 64-bit shift keeps spill bits
    hi = v >> (np.uint64(32) - shift.clip(max=31))
    spill = (v << shift) >> np.uint64(32)
    np.add.at(buf, widx + 1, spill)
    del hi
    # fold carries: low 32 bits of each word + nothing else (disjoint ranges)
    out = (buf & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    # add spilled-in-buf-high contributions of word k into word k+1 (already
    # handled via `spill`); buf high bits beyond that are zero by construction
    return out[:nw]


def _np_unpack_bits(stream: np.ndarray, offs: np.ndarray, widths: np.ndarray) -> np.ndarray:
    """Gather per-value uint32 values with per-value bit offsets/widths."""
    pad = np.concatenate([stream, np.zeros(2, np.uint32)]).astype(np.uint64)
    widx = offs >> 5
    shift = (offs & 31).astype(np.uint64)
    raw = (pad[widx] | (pad[widx + 1] << np.uint64(32))) >> shift
    mask = (np.uint64(1) << widths.astype(np.uint64)) - np.uint64(1)
    return (raw & mask).astype(np.uint32)


_SCHEME_CODE = {Scheme.HSZP: 0, Scheme.HSZP_ND: 1, Scheme.HSZX: 2, Scheme.HSZX_ND: 3}
_CODE_SCHEME = {v: k for k, v in _SCHEME_CODE.items()}


def _valid_mask_blocked(shape, block) -> np.ndarray:
    """0/1 per-value validity in blocked (grid-major) order.

    Padding values get width 0 in the serialized stream, so the stream length
    equals the :func:`serialized_bits` accounting exactly (padding is never
    information: every valid reconstruction is independent of it).
    """
    work_shape = shape if len(shape) == len(block) else (int(np.prod(shape)),)
    mask = blocking.valid_mask(work_shape, block)
    return np.asarray(blocking.to_blocked(jnp.asarray(mask.astype(np.int64)),
                                          block)).reshape(-1)


def serialize(c: Compressed) -> bytes:
    """Exact per-block fixed-rate byte stream (paper's storage format)."""
    residuals = np.asarray(c.residuals).reshape(-1)
    bitwidths = np.asarray(c.bitwidths, dtype=np.uint8)
    metadata = np.asarray(c.metadata, dtype=np.int32)
    block_elems = c.block_elems
    vmask = _valid_mask_blocked(c.shape, c.block)
    widths_per_value_blocked = np.repeat(bitwidths.astype(np.int64), block_elems) * vmask
    # residuals are spatial; reorder to blocked (grid-major) order
    blocked = np.asarray(
        blocking.to_blocked(jnp.asarray(residuals.reshape(c.padded_shape)), c.block)
    ).reshape(-1)
    ub = np.asarray(zigzag(jnp.asarray(blocked))) * vmask.astype(np.uint32)
    total_bits = int(widths_per_value_blocked.sum())
    stream = _np_pack_bits(ub, widths_per_value_blocked, max(total_bits, 1))

    hdr = struct.pack(
        "<4sBBBdi", _MAGIC, _SCHEME_CODE[c.scheme], len(c.shape), len(c.block),
        float(np.asarray(c.eps)), int(c.n_blocks),
    )
    dims = struct.pack(f"<{len(c.shape)}q{len(c.block)}q", *c.shape, *c.block)
    return b"".join([
        hdr, dims,
        bitwidths.tobytes(), metadata.tobytes(),
        np.int64(total_bits).tobytes(), stream.tobytes(),
    ])


def deserialize(data: bytes) -> Compressed:
    magic, scheme_code, ndim, bdim, eps, n_blocks = struct.unpack_from("<4sBBBdi", data, 0)
    if magic != _MAGIC:
        raise ValueError("not an HSZ stream")
    off = struct.calcsize("<4sBBBdi")
    dims = struct.unpack_from(f"<{ndim + bdim}q", data, off)
    off += 8 * (ndim + bdim)
    shape, block = tuple(dims[:ndim]), tuple(dims[ndim:])
    scheme = _CODE_SCHEME[scheme_code]
    bitwidths = np.frombuffer(data, np.uint8, n_blocks, off).astype(np.int32)
    off += n_blocks
    meta_count = n_blocks if scheme in (Scheme.HSZX, Scheme.HSZX_ND) else 1
    metadata = np.frombuffer(data, np.int32, meta_count, off)
    off += 4 * meta_count
    total_bits = int(np.frombuffer(data, np.int64, 1, off)[0])
    off += 8
    stream = np.frombuffer(data, np.uint32, -(-max(total_bits, 1) // 32), off)

    # 1-D schemes flatten n-D data; recover the blocking work-shape
    work_shape = shape if len(block) == len(shape) else (int(np.prod(shape)),)
    pshape = blocking.padded_shape(work_shape, block)
    block_elems = int(np.prod(block))
    widths = np.repeat(bitwidths.astype(np.int64), block_elems)
    widths *= _valid_mask_blocked(shape, block)
    if total_bits != int(widths.sum()):
        raise ValueError(
            f"corrupt HSZ stream: header claims {total_bits} payload bits, "
            f"metadata implies {int(widths.sum())}")
    offs = np.zeros(widths.shape[0], dtype=np.int64)
    np.cumsum(widths[:-1], out=offs[1:])
    u = _np_unpack_bits(stream, offs, widths)
    blocked = np.asarray(unzigzag(jnp.asarray(u)))
    grid = tuple(p // b for p, b in zip(pshape, block))
    residuals = np.asarray(
        blocking.from_blocked(jnp.asarray(blocked.reshape(grid + block)), block)
    )
    vc = blocking.valid_counts(work_shape, block)
    if scheme in (Scheme.HSZX, Scheme.HSZX_ND):
        meta = jnp.asarray(metadata.reshape(grid))
    else:
        meta = jnp.asarray(metadata)
    return Compressed(
        residuals=jnp.asarray(residuals), metadata=meta,
        bitwidths=jnp.asarray(bitwidths), eps=jnp.float32(eps),
        valid_counts=jnp.asarray(vc), scheme=scheme, shape=shape,
        padded_shape=tuple(pshape), block=block, orig_dtype=jnp.float32,
    )
