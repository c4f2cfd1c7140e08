"""Fixed-rate bitplane pack/unpack Pallas kernels.

The encode/decode hot loop of the paper's fixed-rate coder (§IV "Encoding").
Each grid step packs ``VALS`` zigzag values at a static width ``bits`` into
``VALS*bits/32`` uint32 words entirely in VMEM via a bit-matrix contraction:

    values (V,)  ->  bits (V, bits)  ->  reshape (V*bits/32, 32)  ->  · 2^j

``VALS`` is chosen so V*bits is a multiple of 32 for every bits in 1..32
(V = multiple of 32) and the bit matrix fits VMEM comfortably.

The unpacker — the one on the decode path — works on a 2-D layout instead:
rows of ``ROW_VALS`` values, whose words start on a word boundary, unpacked
by :func:`unpack_lanes` one 128-value lane tile at a time.  A tile of 128
values spans exactly ``4*bits`` words, so every value's word lies in a
128-word window of its row and the word fetch is a gather within one
vector register (Mosaic's lane gather), never a gather across the row.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

VALS = 4096  # values per grid step; V*bits <= 128K int32 = 512 KiB VMEM
LANES = 128
ROW_VALS = 1024   # values per row of the unpacker's 2-D layout (8 lane tiles)
ROW_BLOCK = 64    # rows per unpack grid step (64K values)


def _words_for(n: int, bits: int) -> int:
    """uint32 words holding ``n`` values at ``bits`` (= encode.words_for)."""
    return -(-(n * bits) // 32) if bits > 0 else 0


def _pack_kernel(u_ref, o_ref, *, bits: int):
    u = u_ref[...].astype(jnp.uint32)
    shifts = jnp.arange(bits, dtype=jnp.uint32)
    bitmat = (u[:, None] >> shifts[None, :]) & jnp.uint32(1)   # (V, bits)
    stream = bitmat.reshape(-1, 32)                            # (V*bits/32, 32)
    powers = (jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32))
    o_ref[...] = jnp.sum(stream * powers[None, :], axis=1, dtype=jnp.uint32)


def window_words(nv: int, bits: int) -> int:
    """Word columns :func:`unpack_lanes` reads for a row of ``nv`` values:
    the last lane tile's 128-word window starts at word ``4*bits*(T-1)``."""
    return 4 * bits * (pl.cdiv(nv, LANES) - 1) + LANES


def unpack_lanes(w: jax.Array, s, nv: int, bits: int) -> jax.Array:
    """In-kernel unpack of one value row per word row (int32 throughout).

    Value ``j`` of row ``i`` starts at bit ``s[i] + j*bits`` of the row's
    words ``w[i]`` (``s``: ``(rows, 1)`` in-word offsets, or ``0``); ``w``
    has at least :func:`window_words` columns.  Lane tile ``c`` holds
    values ``128c ..``, which start ``128*bits*c`` bits = ``4*bits*c``
    words in, so its words are a 128-word window of the row and each lane
    fetches its low and carry words with a gather inside that window.  The
    shifts and masks are ``encode.unpack_uniform``'s, so the integers are
    the same bits.  Returns the ``(rows, nv)`` zigzag values.

    ``w`` may instead hold just the row's own words (at least ``LANES``
    columns): a window that would run past them starts ``LANES`` words
    before their end, and its gather indices, shifted by as much, are
    clamped into the window (lanes past the row's last word read a word
    that is then masked off or dropped).
    """
    rows, width = w.shape
    off = jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 1) * bits + s
    lo_idx = off >> 5
    hi_idx = lo_idx + 1
    shift = off & 31
    carry = shift > 32 - bits
    hi_shift = jnp.where(carry, 32 - shift, 0)
    mask = (1 << bits) - 1
    tiles = []
    for c in range(pl.cdiv(nv, LANES)):
        start = min(4 * bits * c, width - LANES)
        win = w[:, start:start + LANES]
        lo_c, hi_c = lo_idx, hi_idx
        if start < 4 * bits * c:
            lo_c = jnp.minimum(lo_idx + (4 * bits * c - start), LANES - 1)
            hi_c = jnp.minimum(hi_idx + (4 * bits * c - start), LANES - 1)
        lo = jnp.take_along_axis(win, lo_c, axis=1,
                                 mode="promise_in_bounds")
        hi = jnp.take_along_axis(win, hi_c, axis=1,
                                 mode="promise_in_bounds")
        v = (jax.lax.shift_right_logical(lo, shift)
             | jnp.where(carry, jax.lax.shift_left(hi, hi_shift), 0))
        tiles.append(v & mask)
    out = tiles[0] if len(tiles) == 1 else jnp.concatenate(tiles, axis=1)
    return out[:, :nv]


def _unpack_kernel(w_ref, o_ref, *, bits: int):
    o_ref[...] = unpack_lanes(w_ref[...], 0, ROW_VALS, bits)


@functools.partial(jax.jit, static_argnames=("bits", "interpret"))
def pack(u: jax.Array, bits: int, *, interpret: bool = False) -> jax.Array:
    """Pack flat zigzag uint32 values at static width ``bits``.

    Matches ``repro.core.encode.pack_uniform`` bit-exactly for any length:
    a non-multiple-of-``VALS`` tail is zero-padded to the next grid step —
    zero values contribute zero bits, and fixed-rate bit ranges are
    disjoint, so slicing the word stream back to ``words_for(n, bits)``
    words is word-identical to packing the unpadded input.
    """
    if bits == 0:
        return jnp.zeros((0,), jnp.uint32)
    if bits == 32:
        return u.astype(jnp.uint32)
    n = u.shape[0]
    pad = -n % VALS
    if pad:
        u = jnp.concatenate(
            [u.astype(jnp.uint32), jnp.zeros((pad,), jnp.uint32)])
    n_pad = n + pad
    words_per = VALS * bits // 32
    grid = (n_pad // VALS,)
    out = pl.pallas_call(
        functools.partial(_pack_kernel, bits=bits),
        grid=grid,
        in_specs=[pl.BlockSpec((VALS,), lambda i: (i,))],
        out_specs=pl.BlockSpec((words_per,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n_pad * bits // 32,), jnp.uint32),
        interpret=interpret,
        name="bitpack_pack",
    )(u.astype(jnp.uint32))
    return out[:_words_for(n, bits)]


@functools.partial(jax.jit, static_argnames=("n", "bits", "interpret"))
def unpack(words: jax.Array, n: int, bits: int, *, interpret: bool = False) -> jax.Array:
    """Inverse of :func:`pack` for any ``n`` (tail words zero-padded).

    The stream is cut into rows of ``ROW_VALS`` values (``32*bits`` words,
    so every row starts on a word boundary), each row padded to the
    :func:`window_words` columns the lane-tile unpack reads.
    """
    if bits == 0:
        return jnp.zeros((n,), jnp.uint32)
    if bits == 32:
        return words[:n].astype(jnp.uint32)
    rows = pl.cdiv(pl.cdiv(n, ROW_VALS), 8) * 8
    blk = min(ROW_BLOCK, rows)
    rows = pl.cdiv(rows, blk) * blk
    wpr = ROW_VALS * bits // 32
    w = jax.lax.bitcast_convert_type(words.astype(jnp.uint32), jnp.int32)
    w = jnp.pad(w[:rows * wpr], (0, max(0, rows * wpr - w.shape[0])))
    w = jnp.pad(w.reshape(rows, wpr),
                ((0, 0), (0, window_words(ROW_VALS, bits) - wpr)))
    out = pl.pallas_call(
        functools.partial(_unpack_kernel, bits=bits),
        grid=(rows // blk,),
        in_specs=[pl.BlockSpec((blk, w.shape[1]), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((blk, ROW_VALS), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, ROW_VALS), jnp.int32),
        interpret=interpret,
        name="bitpack_unpack",
    )(w)
    return out.reshape(-1)[:n].astype(jnp.uint32)
