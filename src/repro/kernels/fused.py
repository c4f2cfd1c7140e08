"""Fused recorrelation + op-postlude Pallas kernels (2-D stage hot path).

The paper's multi-stage design exists to avoid paying full decompression per
analytical operation; these kernels take the argument one level lower: a
stage reconstruction feeding a stencil never materializes its *integer
intermediate* (the Lorenzo cumsum planes, the upsampled block means, the
stage-③ q array) in HBM at all.  One pass reads the residual band into
VMEM, recorrelates in registers, and writes only the stencil plane.

Each family has two kernel variants sharing one band body: the
*residual-plane* kernels (``lorenzo2d`` / ``blockmean2d``) read a decoded
``(r, n1)`` residual band, and the *payload-input* kernels
(``lorenzo_enc2d`` / ``blockmean_enc2d``) go one step further for
:class:`~repro.core.stages.Encoded` fields — each grid cell takes its
band's *gathered payload words*, bitplane-unpacks them in VMEM
(``bitpack.unpack_lanes``, the same word/shift/mask arithmetic as
``encode.unpack_uniform``, hence bit-identical integers), recorrelates,
and writes only the stencil plane: decode + op in a single pass, with the
residual plane never existing in HBM either.  Cross-band state stays
tiny: halo rows are unpacked outside the kernel at row cost, and the
Lorenzo cross-band ``base`` prefix comes from a payload-input column-sum
pass (int32 modular, so any summation order is exact).

Design constraints (why these kernels look the way they do):

* **Carry-free / vmap-safe.**  The batched analytics engine runs every
  lowering under ``jax.vmap``; Pallas batching prepends a grid dimension,
  which silently breaks ``pl.program_id``-keyed sequential carries (see
  ``prefix_stats.py``, which is why *that* kernel stays unwired).  Here
  every grid cell is independent: cross-band prefix state enters as a tiny
  precomputed ``base`` row (exclusive band prefix of per-band column
  sums), and ±1-row halos enter as row gathers; both ride in one
  ``(8, n1)`` halo tile per band.

* **Mosaic-shaped.**  Every block's last two dims are multiples of
  ``(8, 128)`` or the whole array dim: bands are multiples of 8 rows sized
  by bytes (:func:`band_rows`), per-band rows travel as 8-row halo tiles,
  and payload words as one word row per plane row (``row_words``).  Inside
  a kernel only rolls, selects, broadcasts and elementwise int32/f32 math
  run: row and column shifts are ``pltpu.roll`` plus a halo select,
  prefix sums are log-step shifted adds (exact in modular int32), the
  block-mean upsample is a sublane broadcast of a column-upsampled metadata
  band, and the unpack gathers only inside 128-lane windows.

* **Bit-identity via integer outputs.**  Each kernel emits the *exact
  integer* stencil plane (int32, modular — associative, so any in-kernel
  regrouping is exact); the float tail (cast + eps multiply) is applied by
  the lowering rules in ``repro.core.fused`` with the identical operations
  the XLA rules use.  Keeping the float tail outside the kernel is what
  makes composition bit-stable: a trailing in-kernel multiply can be
  duplicated into a downstream consumer and FMA-contracted by XLA's CPU
  fusion *shape-dependently* (the interpret-mode grid loop unrolls for
  small fields), which broke batched-vs-per-field bit-identity for
  divergence.  The block-mean laplacians are the one exception — their
  contract is a specific f32 accumulation *sequence* — so they emit that
  f32 sum (final op an add, same producer pattern as the XLA rule) and
  leave only the eps multiply outside.  The 3-D vector kernel
  (:func:`vector3d_q`) runs its whole float tail inside: its outputs are
  the stencil interior, and it moves only integers, so its float ops are
  the rules' own expressions (see its docstring).

* **Full-shape outputs, window slicing outside.**  Stencil-then-slice
  equals slice-then-stencil for every interior element, so kernels emit
  full padded-shape planes (boundary rows/columns are don't-care) and the
  lowering rule applies the same window/interior slices the XLA rules use.
  That keeps one kernel per (family, op) serving full-field, cropped, and
  region-windowed queries alike.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .bitpack import LANES, unpack_lanes, window_words

BAND_BYTES = 256 << 10  # target int32 bytes of one band plane in VMEM
SUBLANES = 8


@functools.lru_cache(maxsize=256)
def band_rows(n0: int, n1: int, mult: int = 1) -> int | None:
    """Rows per grid step: the largest multiple of ``lcm(8, mult)`` that
    divides ``n0`` and keeps an int32 band within ``BAND_BYTES`` (at least
    one such step), or ``None`` when no multiple of it divides ``n0`` — the
    kernels then do not cover the field."""
    step = math.lcm(SUBLANES, mult)
    if n0 % step:
        return None
    best = step
    for r in range(step, n0 + 1, step):
        if n0 % r == 0 and r * n1 * 4 <= BAND_BYTES:
            best = r
    return best


def _bands(n0: int, n1: int, mult: int = 1) -> tuple[int, int]:
    r = band_rows(n0, n1, mult)
    if r is None:
        raise ValueError(
            f"no band of a multiple of {math.lcm(SUBLANES, mult)} rows "
            f"divides {n0}; the fused kernels do not cover this shape")
    return r, n0 // r


def _halo_tiles(*rows: jax.Array) -> jax.Array:
    """Stack per-band rows (each ``(nb, n)``) into the ``(nb*8, n)`` halo
    array: band ``b``'s rows sit at ``8b, 8b+1, ...`` of its 8-row tile."""
    nb, n = rows[0].shape
    h = jnp.stack(rows, axis=1)
    h = jnp.pad(h, ((0, 0), (0, SUBLANES - len(rows)), (0, 0)))
    return h.reshape(nb * SUBLANES, n)


def _prev_rows(x: jax.Array, r: int) -> jax.Array:
    """``prev[b] = x[b*r - 1]`` (zeros for band 0)."""
    zero = jnp.zeros((1, x.shape[1]), x.dtype)
    return jnp.concatenate([zero, x[r - 1::r][:-1]], axis=0)


def _next_rows(x: jax.Array, r: int) -> jax.Array:
    """``next[b] = x[(b+1)*r]`` (zeros for the last band)."""
    zero = jnp.zeros((1, x.shape[1]), x.dtype)
    return jnp.concatenate([x[r::r], zero], axis=0)


def _exclusive_band_prefix(colsums: jax.Array) -> jax.Array:
    zero = jnp.zeros((1, colsums.shape[1]), colsums.dtype)
    return jnp.concatenate([zero, jnp.cumsum(colsums, axis=0)[:-1]], axis=0)


def _iota(x: jax.Array, axis: int) -> jax.Array:
    return jax.lax.broadcasted_iota(jnp.int32, x.shape, axis)


def _shift_rows(x, prev, nxt):
    """(x_{i-1}, x_{i+1}) with cross-band halo rows ``prev``/``nxt``."""
    r = x.shape[0]
    row = _iota(x, 0)
    up = jnp.where(row == 0, prev, pltpu.roll(x, 1, 0))
    dn = jnp.where(row == r - 1, nxt, pltpu.roll(x, r - 1, 0))
    return up, dn


def _shift_cols(x):
    """(x_{j-1}, x_{j+1}); boundary columns are don't-care (sliced off)."""
    return pltpu.roll(x, 1, 1), pltpu.roll(x, x.shape[1] - 1, 1)


def _cumsum(x: jax.Array, axis: int) -> jax.Array:
    """Inclusive prefix sum by log-step shifted adds (Hillis-Steele):
    exact in modular int32, so it equals ``jnp.cumsum`` bit for bit."""
    idx = _iota(x, axis)
    k = 1
    while k < x.shape[axis]:
        x = x + jnp.where(idx >= k, pltpu.roll(x, k, axis), 0)
        k *= 2
    return x


def _unzigzag(u: jax.Array) -> jax.Array:
    """signed residuals from zigzag words — ``encode.unzigzag`` verbatim."""
    ui = u.astype(jnp.int32)
    return (ui >> 1) ^ -(ui & 1)


# ---------------------------------------------------------------------------
# payload words (payload-input kernel variants)
# ---------------------------------------------------------------------------

def row_words(payload: jax.Array, n0: int, n1: int,
              bits: int) -> tuple[jax.Array, jax.Array]:
    """One payload word row per plane row, for in-kernel unpacking.

    Row ``i``'s values start at bit ``i*n1*bits``: word ``w_i`` (split as
    ``i*(A>>5) + (i*(A&31))>>5`` with ``A = n1*bits`` so no int32 product
    overflows) and in-word offset ``s_i``.  Returns the ``(n0, W)`` int32
    word matrix (``W`` = :func:`bitpack.window_words`) and the ``(n0, 1)``
    offsets — the only payload-sized transfer of the fused-decode path.
    """
    a = n1 * bits
    i = jnp.arange(n0, dtype=jnp.int32)
    frac = i * (a & 31)
    w0 = i * (a >> 5) + (frac >> 5)
    width = window_words(n1, bits)
    idx = w0[:, None] + jnp.arange(width, dtype=jnp.int32)[None, :]
    words = jax.lax.bitcast_convert_type(payload, jnp.int32).at[idx].get(
        mode="fill", fill_value=0)
    return words, (frac & 31).reshape(n0, 1)


def _unpack_band(w_ref, s_ref, n1: int, bits: int) -> jax.Array:
    return _unzigzag(unpack_lanes(w_ref[...], s_ref[...], n1, bits))


def unpack_rows(payload: jax.Array, rows: jax.Array, n1: int,
                bits: int) -> jax.Array:
    """Unpack whole rows of the padded plane (halo rows for the payload
    kernels) — ``unpack_uniform``'s arithmetic restricted to the requested
    rows, cost proportional to the rows, not the field."""
    from repro.core.encode import unpack_words  # core imports the kernels

    offs = ((rows[:, None].astype(jnp.uint32) * jnp.uint32(n1)
             + jnp.arange(n1, dtype=jnp.uint32)[None, :])
            * jnp.uint32(bits))
    widx = (offs >> 5).astype(jnp.int32)
    return _unzigzag(unpack_words(payload, widx, widx + 1,
                                  offs & jnp.uint32(31), bits))


def _specs(r: int, n1: int, width: int | None = None):
    """BlockSpecs shared by the band kernels: the ``(r, n1)`` band, the
    ``(8, n1)`` halo tile, and (payload variants) the ``(r, W)`` word rows
    with their ``(r, 1)`` in-word offsets."""
    band = pl.BlockSpec((r, n1), lambda b: (b, 0))
    halo = pl.BlockSpec((SUBLANES, n1), lambda b: (b, 0))
    if width is None:
        return band, halo
    words = pl.BlockSpec((r, width), lambda b: (b, 0))
    offs = pl.BlockSpec((r, 1), lambda b: (b, 0))
    return band, halo, words, offs


def _outputs(band, n0: int, n1: int, dtype, what: str):
    n_out = 2 if what == "grad" else 1
    shape = jax.ShapeDtypeStruct((n0, n1), dtype)
    if n_out == 1:
        return band, shape
    return [band] * n_out, [shape] * n_out


# ---------------------------------------------------------------------------
# Lorenzo family: residual band -> prefix-sum planes -> stencil, all in VMEM
# ---------------------------------------------------------------------------

def _lorenzo_core(p, h, out_refs, what: str):
    """Shared band body: D0 = cumsum(p, axis=1), D1 = base + cumsum(p,
    axis=0); emit the requested integer planes.  Halo tile ``h``: row 0 is
    ``base`` (the exclusive column prefix of earlier bands), row 1 the
    next band's first row of D0.

    Derivative planes are ``D[+1] + D[0]`` — identical integers at stages
    ②③④ (q[i+1]-q[i-1] telescopes to D[i+1]+D[i]); the laplacian plane is
    ``sum_a (D_a[+1] - D_a[0])``, Eq. V-B.3.
    """
    r = p.shape[0]
    outs = iter(out_refs)
    if what in ("deriv0", "grad", "lap"):
        da = _cumsum(p, 1)
        da_next = jnp.where(_iota(da, 0) == r - 1, h[1:2],
                            pltpu.roll(da, r - 1, 0))
    if what in ("deriv1", "grad", "lap"):
        db = h[0:1] + _cumsum(p, 0)
        db_next = _shift_cols(db)[1]
    if what in ("deriv0", "grad"):
        next(outs)[...] = da_next + da
    if what in ("deriv1", "grad"):
        next(outs)[...] = db_next + db
    if what == "lap":
        next(outs)[...] = (da_next - da) + (db_next - db)


def _lorenzo_kernel(p_ref, h_ref, *out_refs, what: str):
    _lorenzo_core(p_ref[...], h_ref[...], out_refs, what)


def _lorenzo_enc_kernel(w_ref, s_ref, h_ref, *out_refs, what: str, n1: int,
                        bits: int):
    """Payload-input variant: band word rows -> in-kernel bitplane unpack
    -> the same Lorenzo band body.  The residual plane exists only in
    VMEM."""
    _lorenzo_core(_unpack_band(w_ref, s_ref, n1, bits), h_ref[...],
                  out_refs, what)


def _colsum_enc_kernel(w_ref, s_ref, o_ref, *, n1: int, bits: int):
    """Payload-input band column sums (the cross-band ``base`` prefix
    input) — int32 modular, so any summation order is exact."""
    p = _unpack_band(w_ref, s_ref, n1, bits)
    o_ref[...] = jnp.broadcast_to(jnp.sum(p, axis=0, keepdims=True),
                                  o_ref.shape)


def _lorenzo_halo(base: jax.Array, nxt: jax.Array) -> jax.Array:
    return _halo_tiles(base, jnp.cumsum(nxt, axis=1))


@functools.partial(jax.jit, static_argnames=("what", "interpret"))
def lorenzo2d(p: jax.Array, *, what: str, interpret: bool = False):
    """Fused Lorenzo recorrelation + integer stencil over a 2-D residual
    plane.

    ``what``: ``deriv0`` / ``deriv1`` (one full-shape int32 plane), ``grad``
    (both planes from one pass), ``lap`` (V-B.3 int32 plane).  Boundary
    rows/columns of each output are don't-care; callers slice the same
    window the XLA lowering rules slice, then apply the float tail.
    """
    n0, n1 = p.shape
    r, nb = _bands(n0, n1)
    base = _exclusive_band_prefix(jnp.sum(p.reshape(nb, r, n1), axis=1))
    halo = _lorenzo_halo(base, _next_rows(p, r))
    band, hspec = _specs(r, n1)
    out_specs, out_shape = _outputs(band, n0, n1, p.dtype, what)
    return pl.pallas_call(
        functools.partial(_lorenzo_kernel, what=what),
        grid=(nb,),
        in_specs=[band, hspec],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        name=f"lorenzo2d_{what}",
    )(p, halo)


@functools.partial(jax.jit,
                   static_argnames=("shape", "bits", "what", "interpret"))
def lorenzo_enc2d(payload: jax.Array, shape: tuple, bits: int, *,
                  what: str, interpret: bool = False):
    """Single-pass decode + Lorenzo stencil from the packed payload.

    Two payload-input kernel passes, neither of which materializes the
    residual plane in HBM: a band column-sum pass (for the tiny cross-band
    ``base`` prefix), then the stencil pass — each unpacks its band's
    payload word rows in VMEM.  Halo rows are unpacked outside the kernel
    at row cost.  The recovered integers are bit-identical to
    ``decode_device`` + :func:`lorenzo2d` (same unpack arithmetic), so the
    output planes are too.
    """
    n0, n1 = shape
    r, nb = _bands(n0, n1)
    words, offs = row_words(payload, n0, n1, bits)
    band, hspec, wspec, sspec = _specs(r, n1, words.shape[1])
    colsums = pl.pallas_call(
        functools.partial(_colsum_enc_kernel, n1=n1, bits=bits),
        grid=(nb,),
        in_specs=[wspec, sspec],
        out_specs=hspec,
        out_shape=jax.ShapeDtypeStruct((nb * SUBLANES, n1), jnp.int32),
        interpret=interpret,
        name="lorenzo_enc2d_colsum",
    )(words, offs)
    nxt = jnp.concatenate(
        [unpack_rows(payload, jnp.arange(1, nb, dtype=jnp.int32) * r,
                     n1, bits),
         jnp.zeros((1, n1), jnp.int32)], axis=0)
    halo = _lorenzo_halo(_exclusive_band_prefix(colsums[::SUBLANES]), nxt)
    out_specs, out_shape = _outputs(band, n0, n1, jnp.int32, what)
    return pl.pallas_call(
        functools.partial(_lorenzo_enc_kernel, what=what, n1=n1, bits=bits),
        grid=(nb,),
        in_specs=[wspec, sspec, hspec],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        name=f"lorenzo_enc2d_{what}",
    )(words, offs, halo)


# ---------------------------------------------------------------------------
# block-mean family: residual band + metadata band -> stencil
# ---------------------------------------------------------------------------

def _blockmean_core(p, mg, h, out_refs, what: str, b0: int):
    """Shared band body: upsample the column-upsampled metadata band along
    rows in VMEM (the full-resolution metadata plane never exists in HBM)
    and emit the requested stencil planes.  Halo tile ``h``: rows 0-3 are
    the residual rows above and below the band, then the metadata rows
    above and below it.

    Derivative planes serve stages ②③④ alike: with q = p + m elementwise,
    q[+1]-q[-1] and (p[+1]-p[-1]) + (m[+1]-m[-1]) are the same int32 value.
    The two laplacian variants replicate the XLA rules' distinct f32
    accumulation orders (②: stencil(p) + stencil(m); ③④: stencil(p + m)),
    minus the trailing eps multiply, which the lowering rule applies.
    """
    r, n1 = p.shape
    rb = r // b0
    m = jnp.broadcast_to(mg[:rb].reshape(rb, 1, n1), (rb, b0, n1))
    m = m.reshape(r, n1)
    p_up, p_dn = _shift_rows(p, h[0:1], h[1:2])
    m_up, m_dn = _shift_rows(m, h[2:3], h[3:4])
    outs = iter(out_refs)

    def lap5(c, dn, up, right, left):
        # exact oplib._laplacian_stencil order: -2*nd*c, +hi, +lo per axis
        acc = c.astype(jnp.float32) * -4.0
        acc = acc + dn.astype(jnp.float32)
        acc = acc + up.astype(jnp.float32)
        acc = acc + right.astype(jnp.float32)
        acc = acc + left.astype(jnp.float32)
        return acc

    if what in ("deriv0", "grad"):
        next(outs)[...] = (p_dn - p_up) + (m_dn - m_up)
    if what in ("deriv1", "grad"):
        p_l, p_r = _shift_cols(p)
        m_l, m_r = _shift_cols(m)
        next(outs)[...] = (p_r - p_l) + (m_r - m_l)
    if what == "lap_p":
        p_l, p_r = _shift_cols(p)
        m_l, m_r = _shift_cols(m)
        lp = lap5(p, p_dn, p_up, p_r, p_l)
        lm = lap5(m, m_dn, m_up, m_r, m_l)
        next(outs)[...] = lp + lm
    if what == "lap_q":
        p_l, p_r = _shift_cols(p)
        m_l, m_r = _shift_cols(m)
        next(outs)[...] = lap5(p + m, p_dn + m_dn, p_up + m_up,
                               p_r + m_r, p_l + m_l)


def _blockmean_kernel(p_ref, m_ref, h_ref, *out_refs, what: str, b0: int):
    _blockmean_core(p_ref[...], m_ref[...], h_ref[...], out_refs, what, b0)


def _blockmean_enc_kernel(w_ref, s_ref, m_ref, h_ref, *out_refs, what: str,
                          b0: int, n1: int, bits: int):
    """Payload-input variant: band word rows -> in-kernel bitplane unpack
    -> the same block-mean band body.  Only the ±1 halo rows of the
    residual plane are unpacked outside the kernel; the band itself exists
    only in VMEM."""
    _blockmean_core(_unpack_band(w_ref, s_ref, n1, bits), m_ref[...],
                    h_ref[...], out_refs, what, b0)


def _meta_bands(meta: jax.Array, b1: int, nb: int, r: int, b0: int):
    """Column-upsampled metadata in per-band tiles (``rb = r/b0`` rows,
    padded to a multiple of 8), its BlockSpec, and the metadata rows
    above and below each band."""
    mcol = jnp.repeat(meta, b1, axis=1)
    g0, n1 = mcol.shape
    rb = r // b0
    rb8 = -(-rb // SUBLANES) * SUBLANES
    tiles = jnp.pad(mcol.reshape(nb, rb, n1),
                    ((0, 0), (0, rb8 - rb), (0, 0))).reshape(nb * rb8, n1)
    spec = pl.BlockSpec((rb8, n1), lambda b: (b, 0))
    return tiles, spec, _prev_rows(mcol, rb), _next_rows(mcol, rb)


@functools.partial(jax.jit, static_argnames=("block", "what", "interpret"))
def blockmean2d(p: jax.Array, meta: jax.Array, block: tuple, *,
                what: str, interpret: bool = False):
    """Fused block-mean upsample + stencil over a 2-D residual plane.

    ``meta`` is the block-grid metadata (``n0//b0 x n1//b1``); ``what``:
    ``deriv0`` / ``deriv1`` / ``grad`` (int32 planes) / ``lap_p`` (stage-②
    f32 accumulation) / ``lap_q`` (stage-③④ f32 accumulation).  Boundary
    rows/columns of each output are don't-care, as in :func:`lorenzo2d`.
    """
    n0, n1 = p.shape
    b0, b1 = block
    r, nb = _bands(n0, n1, b0)
    mtiles, mspec, m_prev, m_next = _meta_bands(meta, b1, nb, r, b0)
    halo = _halo_tiles(_prev_rows(p, r), _next_rows(p, r), m_prev, m_next)
    band, hspec = _specs(r, n1)
    dtype = jnp.float32 if what in ("lap_p", "lap_q") else p.dtype
    out_specs, out_shape = _outputs(band, n0, n1, dtype, what)
    return pl.pallas_call(
        functools.partial(_blockmean_kernel, what=what, b0=b0),
        grid=(nb,),
        in_specs=[band, mspec, hspec],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        name=f"blockmean2d_{what}",
    )(p, mtiles, halo)


@functools.partial(jax.jit, static_argnames=("shape", "block", "bits",
                                             "what", "interpret"))
def blockmean_enc2d(payload: jax.Array, meta: jax.Array, shape: tuple,
                    block: tuple, bits: int, *, what: str,
                    interpret: bool = False):
    """Single-pass decode + block-mean stencil from the packed payload.

    One payload-input kernel pass: each grid cell unpacks its band's
    payload word rows in VMEM, upsamples the metadata band, and writes only
    the stencil plane — the residual plane never exists in HBM.  Halo rows
    (±1 row per band) are unpacked outside the kernel at row cost.
    Bit-identical to ``decode_device`` + :func:`blockmean2d`.
    """
    n0, n1 = shape
    b0, b1 = block
    r, nb = _bands(n0, n1, b0)
    words, offs = row_words(payload, n0, n1, bits)
    mtiles, mspec, m_prev, m_next = _meta_bands(meta, b1, nb, r, b0)
    zero = jnp.zeros((1, n1), jnp.int32)
    starts = jnp.arange(1, nb, dtype=jnp.int32) * r
    p_prev = jnp.concatenate([zero, unpack_rows(payload, starts - 1, n1,
                                                bits)], axis=0)
    p_next = jnp.concatenate([unpack_rows(payload, starts, n1, bits), zero],
                             axis=0)
    halo = _halo_tiles(p_prev, p_next, m_prev, m_next)
    band, hspec, wspec, sspec = _specs(r, n1, words.shape[1])
    dtype = jnp.float32 if what in ("lap_p", "lap_q") else jnp.int32
    out_specs, out_shape = _outputs(band, n0, n1, dtype, what)
    return pl.pallas_call(
        functools.partial(_blockmean_enc_kernel, what=what, b0=b0, n1=n1,
                          bits=bits),
        grid=(nb,),
        in_specs=[wspec, sspec, mspec, hspec],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        name=f"blockmean_enc2d_{what}",
    )(words, offs, mtiles, halo)


# ---------------------------------------------------------------------------
# 3-D Lorenzo: payload words -> stage-③ plane (the store's materialization)
# ---------------------------------------------------------------------------

PLANE_BYTES = 1 << 20  # largest int32 plane (or its word rows) one step holds
SLAB_BYTES = 2 << 20   # target bytes of one grid step's output (or word) slab
MXU_BITS = 17          # widest payload whose lane prefix runs on the MXU


def _word_layout(n2: int, bits: int) -> tuple[int, int, int]:
    """``(P, SW, Wn)`` of rows of ``n2`` values at ``bits``: rows repeat
    their in-word bit offset every ``P`` rows, ``P`` rows fill ``SW``
    whole words, and ``Wn`` words hold any one row."""
    a = n2 * bits
    period = 32 // math.gcd(a, 32)
    offs = [(ph * a) & 31 for ph in range(period)]
    return period, period * a // 32, max(((s + a - 1) >> 5) + 1 for s in offs)


def _slab_planes(shape: tuple, bits: int) -> int | None:
    """Planes per grid step: as many as keep the output slab and its word
    slab within :data:`SLAB_BYTES`, or ``None`` when a plane
    exceeds :data:`PLANE_BYTES` or its rows are not whole sublane tiles
    — the kernel does not cover it."""
    n0, n1, n2 = shape
    width = n2 if bits == 32 else max(_word_layout(n2, bits)[2], LANES)
    plane = 4 * n1 * max(n2, width)
    if n1 % SUBLANES or plane > PLANE_BYTES:
        return None
    return min(n0, SLAB_BYTES // plane)


def lorenzo3d_covers(shape: tuple, bits: int) -> bool:
    """Does :func:`lorenzo3d_q` cover a padded ``shape`` at ``bits``?"""
    return len(shape) == 3 and (bits == 0 or _slab_planes(
        tuple(shape), bits) is not None)


def plane_words(payload: jax.Array, rows: int, n2: int,
                bits: int) -> jax.Array:
    """``(rows, W)`` payload word rows, row ``g`` starting at the word that
    holds its first value (in-word offset ``((g % P) * n2 * bits) & 31``,
    :func:`_word_layout`).  Rows of whole words (``P == 1``) are a reshape
    of the payload; otherwise each phase of the ``P``-row period is a
    strided view of it.  Narrow rows are padded to one lane tile."""
    period, sw, wn = _word_layout(n2, bits)
    w = payload
    m = -(-rows // period)
    need = ((period - 1) * n2 * bits >> 5) + m * sw
    if w.shape[0] < need:
        w = jnp.pad(w, (0, need - w.shape[0]))
    # a row's words never outrun its period's (wn <= sw), so each phase is
    # a reshape of the payload from the phase's first word
    phases = [w[base:base + m * sw].reshape(m, sw)[:, :wn]
              for base in ((ph * n2 * bits) >> 5 for ph in range(period))]
    words = (phases[0] if period == 1
             else jnp.stack(phases, axis=1).reshape(m * period, wn))[:rows]
    if bits < 32 and wn < LANES:
        words = jnp.pad(words, ((0, 0), (0, LANES - wn)))
    return words


def _lane_prefix_mxu(p: jax.Array, uj: jax.Array) -> jax.Array:
    """Inclusive prefix along lanes on the MXU.  Each 128-lane block of
    ``p``, split into its low byte and the rest (both exact in bf16 while
    ``|p| <= 2**16``), times ``[U | J]`` (the upper triangle of ones, all
    ones) gives the block's prefix and, in every lane, its total: sums of
    at most 128 such values, exact in f32.  The int32 recombination adds
    the totals of the blocks before."""
    r, n = p.shape
    nb = n // LANES
    lo = (p & 255).astype(jnp.float32).astype(jnp.bfloat16)
    hi = (p >> 8).astype(jnp.float32).astype(jnp.bfloat16)
    x = jnp.concatenate([v[:, LANES * c:LANES * (c + 1)]
                         for v in (lo, hi) for c in range(nb)], axis=0)
    y = jnp.dot(x, uj, preferred_element_type=jnp.float32).astype(jnp.int32)
    y = y[:nb * r] + (y[nb * r:] << 8)
    out, carry = [], None
    for c in range(nb):
        pre, tot = y[c * r:(c + 1) * r, :LANES], y[c * r:(c + 1) * r, LANES:]
        out.append(pre if carry is None else pre + carry)
        carry = tot if carry is None else carry + tot
    return out[0] if nb == 1 else jnp.concatenate(out, axis=1)


def _lorenzo3d_q_kernel(w_ref, *refs, n2: int, bits: int, period: int,
                        mxu: bool):
    """One slab of planes, plane by plane: unpack the plane's word rows,
    unzigzag, take the prefix along lanes (on the MXU where ``mxu``) and
    along rows, and add the carry plane ``acc_ref`` — the previous
    plane's stage-③ integers, kept in VMEM from one grid step to the next
    (so the grid runs in order, never under ``vmap``).  Whole planes keep
    every vector operation long enough to hide its latency."""
    uj_ref, o_ref, acc_ref = refs if mxu else (None,) + refs
    slab, n1 = o_ref.shape[:2]

    @pl.when(pl.program_id(0) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    first = pl.program_id(0) * slab * n1

    def plane(t, _):
        w = jax.lax.bitcast_convert_type(w_ref[t], jnp.int32)
        if bits == 32:
            u = w
        else:
            s = 0
            if period > 1:
                g = first + t * n1 + jax.lax.broadcasted_iota(
                    jnp.int32, (n1, 1), 0)
                s = ((g & (period - 1)) * ((n2 * bits) & 31)) & 31
            u = unpack_lanes(w, s, n2, bits)
        p = _unzigzag(u)
        p = _lane_prefix_mxu(p, uj_ref[...]) if mxu else _cumsum(p, 1)
        q = acc_ref[...] + _cumsum(p, 0)
        acc_ref[...] = q
        o_ref[t] = q
        return 0

    jax.lax.fori_loop(0, slab, plane, 0)


@functools.partial(jax.jit, static_argnames=("shape", "bits", "interpret"))
def lorenzo3d_q(payload: jax.Array, shape: tuple, bits: int, *,
                interpret: bool = False):
    """Stage-③ integers of a 3-D Lorenzo field (padded ``shape``) straight
    from its packed payload: ``unlorenzo(unzigzag(unpack(payload)))``, bit
    for bit (int32 prefix sums are modular, so any order is exact).

    One sequential pass over slabs of planes: each grid step reads its
    planes' payload words and writes their int32 planes, and the unpack,
    unzigzag and all three prefix sums run in VMEM.  The axis-0 prefix is
    a carry plane held in VMEM across grid steps; the slab count need not
    divide the plane count (the words gain zero planes, the output is cut
    back).  Rows of whole 128-lane blocks at up to :data:`MXU_BITS` bits
    take their lane prefix on the MXU, others by log-step shifted adds.
    """
    n0, n1, n2 = shape
    if bits == 0:
        return jnp.zeros(shape, jnp.int32)
    slab = _slab_planes(shape, bits)
    if slab is None:
        raise ValueError(f"lorenzo3d_q does not cover {shape} at {bits} bits")
    nb = -(-n0 // slab)
    words = plane_words(payload, n0 * n1, n2, bits).reshape(n0, n1, -1)
    if nb * slab > n0:
        words = jnp.pad(words, ((0, nb * slab - n0), (0, 0), (0, 0)))
    width = words.shape[2]
    mxu = bits <= MXU_BITS and n2 % LANES == 0
    args = [words]
    in_specs = [pl.BlockSpec((slab, n1, width), lambda b: (b, 0, 0))]
    if mxu:
        i = jnp.arange(LANES)
        tri = i[:, None] <= i[None, :]
        args.append(jnp.concatenate([tri, jnp.ones_like(tri)],
                                    axis=1).astype(jnp.bfloat16))
        in_specs.append(pl.BlockSpec((LANES, 2 * LANES), lambda b: (0, 0)))
    out = pl.pallas_call(
        functools.partial(_lorenzo3d_q_kernel, n2=n2, bits=bits,
                          period=_word_layout(n2, bits)[0], mxu=mxu),
        grid=(nb,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((slab, n1, n2), lambda b: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nb * slab, n1, n2), jnp.int32),
        scratch_shapes=[pltpu.VMEM((n1, n2), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="lorenzo3d_q",
    )(*args)
    return out[:n0]


# ---------------------------------------------------------------------------
# 3-D vector operators: three stage-③ planes -> divergence and curl
# ---------------------------------------------------------------------------

VECTOR_ROWS = 32         # output rows of one grid step (a multiple of 8)
VECTOR_BYTES = 12 << 20  # VMEM of one grid step's blocks, double-buffered


def _vector3d_step_bytes(k: int, r: int, n2: int, n_out: int) -> int:
    """VMEM of one grid step: three ``(k+2, r+8, n2)`` int32 input
    windows, ``n_out`` ``(k, r, n2-2)`` f32 output blocks (lanes padded to
    whole 128-lane tiles) and the ``(24, n2)`` eps tile, each
    double-buffered."""
    lanes_out = -(-(n2 - 2) // LANES) * LANES
    return 2 * 4 * (3 * (k + 2) * (r + SUBLANES) * n2 + n_out * k * r
                    * lanes_out + 3 * SUBLANES * n2)


def vector3d_tiles(shape: tuple, n_out: int) -> tuple[int, int] | None:
    """``(k, r)`` of :func:`vector3d_q` over component planes of ``shape``:
    ``k`` output planes (the largest divisor of ``n0 - 2`` whose step fits
    :data:`VECTOR_BYTES`) by ``r`` output rows a grid step, or ``None``
    when the kernel does not cover the shape: rank 3, rows in whole
    sublane tiles, at least 16 of them, and lanes in whole 128-lane
    tiles."""
    if len(shape) != 3:
        return None
    n0, n1, n2 = shape
    if n0 < 3 or n1 < 2 * SUBLANES or n1 % SUBLANES or n2 % LANES:
        return None
    r = min(VECTOR_ROWS, n1 - SUBLANES)
    fits = [k for k in range(1, n0 - 1) if (n0 - 2) % k == 0
            and _vector3d_step_bytes(k, r, n2, n_out) <= VECTOR_BYTES]
    return (max(fits), r) if fits else None


def _vector3d_kernel(e_ref, u_ref, v_ref, w_ref, *o_refs, k: int, r: int,
                     n2: int, div: bool, curl: bool):
    """``k`` output planes of ``r`` rows each, plane by plane and 8 rows
    at a time: each central difference the ops need as an exact int32
    difference, moved onto the output's lanes, then one cast and one
    multiply by its component's eps, and the rules' adds and subtracts in
    their order, written as the ``n2 - 2`` interior lanes.  Window row
    ``i`` holds output row ``i``'s ``-1`` neighbour, and lane ``j`` output
    lane ``j``'s, so rows are read one or two down and lanes rotated.  All
    data movement is on integers: the float ops form the rules' own
    expressions, which XLA's CPU backend then contracts alike."""
    eps = [e_ref[SUBLANES * c:SUBLANES * (c + 1), :] for c in range(3)]
    refs = (u_ref, v_ref, w_ref)
    terms = set()
    if div:
        terms |= {(0, 0), (1, 1), (2, 2)}
    if curl:
        terms |= {(2, 1), (1, 2), (0, 2), (2, 0), (1, 0), (0, 1)}

    def plane(t, carry):
        for o in range(0, r, SUBLANES):
            rows = {}

            def at(c, dt, dr):
                if (c, dt, dr) not in rows:
                    rows[c, dt, dr] = refs[c][t + dt,
                                              o + dr:o + dr + SUBLANES, :]
                return rows[c, dt, dr]

            f = {}
            for c, a in sorted(terms):
                if a == 0:
                    d = pltpu.roll(at(c, 2, 1) - at(c, 0, 1), n2 - 1, 1)
                elif a == 1:
                    d = pltpu.roll(at(c, 1, 2) - at(c, 1, 0), n2 - 1, 1)
                else:
                    x = at(c, 1, 1)
                    d = pltpu.roll(x, n2 - 2, 1) - x
                f[c, a] = (d.astype(jnp.float32) * eps[c])[:, :n2 - 2]
            outs = iter(o_refs)
            if div:
                next(outs)[t, o:o + SUBLANES, :] = f[0, 0] + f[1, 1] + f[2, 2]
            if curl:
                next(outs)[t, o:o + SUBLANES, :] = f[2, 1] - f[1, 2]
                next(outs)[t, o:o + SUBLANES, :] = f[0, 2] - f[2, 0]
                next(outs)[t, o:o + SUBLANES, :] = f[1, 0] - f[0, 1]
        return carry

    jax.lax.fori_loop(0, k, plane, 0)


@functools.partial(jax.jit, static_argnames=("div", "curl", "interpret"))
def vector3d_q(u: jax.Array, v: jax.Array, w: jax.Array, eps: jax.Array, *,
               div: bool, curl: bool, interpret: bool = False):
    """Divergence and/or curl of a 3-component vector from its stage-③
    planes (int32, one shape) and the components' ``eps`` (f32, ``(3,)``),
    in one pass: ``(div,)``, ``(cx, cy, cz)`` or ``(div, cx, cy, cz)``,
    each the f32 ``(n0-2, n1-2, n2-2)`` interior.  Bitwise the XLA rules'
    ``(q[i+1] - q[i-1]).astype(f32) * eps`` terms, summed in their order.

    Unlike the 2-D kernels, the float tail runs here: the outputs are the
    interior, so integer outputs with the multiply and the crop in XLA
    would add a pass over the whole output.  Every grid step reads the
    ``(k+2, r+8, n2)`` window of each plane it needs (the planes and rows
    after its ``k`` by ``r`` output block are its halos) and nothing
    carries from one step to the next, so ``vmap`` may add a grid axis.
    """
    shape = u.shape
    n0, n1, n2 = shape
    n_out = (1 if div else 0) + (3 if curl else 0)
    tiles = vector3d_tiles(shape, n_out)
    if tiles is None or not n_out:
        raise ValueError(f"vector3d_q does not cover {shape}")
    k, r = tiles
    nb = -(-(n1 - 2) // r)
    pad = max(0, nb * r + SUBLANES - n1)
    window = pl.BlockSpec(
        (pl.Element(k + 2), pl.Element(r + SUBLANES, (0, pad)),
         pl.Element(n2)),
        lambda b, c: (b * k, c * r, 0))
    block = pl.BlockSpec((k, r, n2 - 2), lambda b, c: (b, c, 0))
    e = jnp.repeat(eps.astype(jnp.float32), SUBLANES)[:, None]
    e = jnp.broadcast_to(e, (3 * SUBLANES, n2))
    out = jax.ShapeDtypeStruct((n0 - 2, n1 - 2, n2 - 2), jnp.float32)
    return tuple(pl.pallas_call(
        functools.partial(_vector3d_kernel, k=k, r=r, n2=n2, div=div,
                          curl=curl),
        grid=((n0 - 2) // k, nb),
        in_specs=[pl.BlockSpec((3 * SUBLANES, n2), lambda b, c: (0, 0)),
                  window, window, window],
        out_specs=[block] * n_out,
        out_shape=[out] * n_out,
        interpret=interpret,
        name="vector3d_q",
    )(e, u, v, w))
