"""Block-sparse region queries over compressed/encoded fields (DESIGN.md §5).

An analytical operation over a spatial sub-region should touch only the
blocks that cover it, not decode the whole field.  Because the device
container packs residuals at a *uniform* static width (``Encoded.bits``),
the payload words holding any block are statically computable host-side:
a region query gathers exactly those words (plus the per-block metadata /
bitwidths / valid counts of the covering blocks) and unpacks nothing else.

The gathered blocks always form an *honest sub-field* — a smaller
:class:`~repro.core.stages.Compressed` whose every invariant holds — so the
homomorphic operators reuse their existing stage arithmetic on it:

* **block-mean family** (HSZx/HSZx-nd): every block is self-contained, so
  the closure of a region is its geometric covering block set;
* **Lorenzo family** (HSZp/HSZp-nd): recorrelation is a prefix sum, so the
  closure is the origin-anchored *prefix hull* ``[0, stop)`` per axis — a
  prefix-rectangle restriction of a Lorenzo field is itself a valid Lorenzo
  field (the zero boundary at the origin is preserved).  Stage-② derivatives
  only prefix-sum over the non-derivative axes, so their closure narrows to
  a *band*: covering range on the derivative axis, hull on the others.

All plan geometry (block ranges, flat indices, payload word indices, window
index maps, statistic weights) is computed host-side with numpy from static
shapes, memoized, and enters traced code only as constants — region ops stay
``jit``/``vmap``-composable exactly like their full-field counterparts.  The
one exception is the per-value payload addressing of nd plans, which is
affine in the box coordinates and built on device from iotas
(:meth:`RegionPlan.value_words`): as host constants it made a 1/8 window of
a 512^3 field a 1 GB program.
"""
from __future__ import annotations
from collections.abc import Sequence

from collections import OrderedDict

import numpy as np
import jax
import jax.numpy as jnp

from . import encode
from .stages import Compressed, Encoded, Scheme, Stage

#: one axis of a region: ``None`` (full axis), a ``slice``, or ``(start, stop)``.
AxisSpec = None | slice | tuple[int, int] | Sequence[int]
RegionSpec = Sequence[AxisSpec]

#: closure kinds: ``"cover"`` (geometric covering blocks), ``"hull"``
#: (origin-anchored prefix rectangle), ``("band", axis)`` (cover on ``axis``,
#: hull on the others — Lorenzo stage-② derivatives).
Closure = str | tuple[str, int]


def normalize_region(region: RegionSpec, shape: Sequence[int]) -> tuple[tuple[int, int], ...]:
    """Canonicalize a region to per-axis ``(start, stop)`` over ``shape``.

    Accepts ``None`` / ``slice(start, stop)`` / ``(start, stop)`` per axis;
    negative indices count from the axis end, python-style.
    """
    if len(region) != len(shape):
        raise ValueError(f"region rank {len(region)} != field rank {len(shape)}")
    out = []
    for spec, s in zip(region, shape):
        if spec is None:
            start, stop = 0, s
        elif isinstance(spec, slice):
            if spec.step not in (None, 1):
                raise ValueError("region slices must have step 1")
            start, stop, _ = spec.indices(s)
        else:
            start, stop = spec
            start = int(start) + (s if start < 0 else 0)
            stop = int(stop) + (s if stop < 0 else 0)
        if not (0 <= start < stop <= s):
            raise ValueError(f"region axis ({start}, {stop}) out of bounds for size {s}")
        out.append((int(start), int(stop)))
    return tuple(out)


class GatherIndex:
    """Static payload-gather arrays for one ``(plan, bits)`` pair.

    ``word_idx`` are the only payload words touched; ``pos0``/``pos1``/
    ``shift`` address each gathered value's (<= 2) word contributions within
    that gathered word set (``pos1`` may point one past it: a zero word).
    nd plans leave them ``None`` and build them on device instead.
    """

    def __init__(self, word_idx: np.ndarray, pos0: np.ndarray, pos1: np.ndarray,
                 shift: np.ndarray, n_values: int):
        self.word_idx = word_idx
        self.pos0 = pos0
        self.pos1 = pos1
        self.shift = shift
        self.n_values = n_values

    @property
    def n_words(self) -> int:
        """Number of payload words a region decode gathers."""
        return int(self.word_idx.shape[0])


class RegionPlan:
    """Host-side static plan of one region query over one field layout.

    Built once per ``(layout, region, closure)`` and memoized; holds the
    gathered block set, the sub-field geometry, the window index map, and the
    lazily-built payload word-gather / statistic-weight arrays.
    """

    def __init__(self, scheme: Scheme, shape: tuple[int, ...],
                 padded_shape: tuple[int, ...], block: tuple[int, ...],
                 region: tuple[tuple[int, int], ...], closure: Closure):
        self.scheme = scheme
        self.shape = shape              # original (logical) data shape
        self.padded_shape = padded_shape
        self.block = block
        self.region = region            # normalized, original-shape coords
        self.closure = closure
        self._gather_cache: dict[int, GatherIndex] = {}
        self._weights: tuple[np.ndarray, ...] | None = None

        grid = tuple(p // b for p, b in zip(padded_shape, block))
        self.grid = grid
        if scheme.is_nd:
            self._build_nd(grid)
        else:
            self._build_flat(grid)
        self.win_shape = tuple(e - s for s, e in region)
        self.n_window = int(np.prod(self.win_shape))
        self.n_sub_blocks = int(self.block_ids.shape[0])
        self.gathered_elems = int(np.prod(self.sub_padded_shape))

    # -- construction -------------------------------------------------------
    def _axis_block_range(self, axis: int, s: int, e: int) -> tuple[int, int]:
        b = self.block[axis]
        if self.closure == "hull" or (
                isinstance(self.closure, tuple) and self.closure[1] != axis):
            return 0, -(-e // b)
        return s // b, -(-e // b)

    def _build_nd(self, grid: tuple[int, ...]) -> None:
        block = self.block
        ranges = tuple(self._axis_block_range(a, s, e)
                       for a, (s, e) in enumerate(self.region))
        self.grid_ranges = ranges
        self.sub_padded_shape = tuple((hi - lo) * b for (lo, hi), b in zip(ranges, block))
        self.sub_shape = tuple(min(hi * b, s) - lo * b
                               for (lo, hi), b, s in zip(ranges, block, self.shape))
        self.window = tuple(slice(s - lo * b, e - lo * b)
                            for (s, e), (lo, _), b in zip(self.region, ranges, block))
        self.spatial_slices = tuple(slice(lo * b, hi * b)
                                    for (lo, hi), b in zip(ranges, block))
        self.grid_slices = tuple(slice(lo, hi) for lo, hi in ranges)
        axes = [np.arange(lo, hi) for lo, hi in ranges]
        mesh = np.meshgrid(*axes, indexing="ij")
        self.block_ids = np.ravel_multi_index(tuple(mesh), grid).reshape(-1)
        self.win_pos = None
        # per-gathered-block window-overlap element counts (outer product)
        per_axis = []
        for (s, e), (lo, hi), b in zip(self.region, ranges, block):
            i = np.arange(lo, hi)
            per_axis.append(np.clip(np.minimum(e, (i + 1) * b)
                                    - np.maximum(s, i * b), 0, None))
        ov = per_axis[0]
        for a in per_axis[1:]:
            ov = np.multiply.outer(ov, a)
        self.overlap = ov.reshape(-1).astype(np.int32)
        self.aligned = all(s % b == 0 and (e % b == 0 or e == dim)
                           for (s, e), b, dim in zip(self.region, block, self.shape))

    def _build_flat(self, grid: tuple[int, ...]) -> None:
        """1-D schemes flatten the data; a spatial region becomes a union of
        row-major flat runs whose covering block *set* (not range) is gathered."""
        b = self.block[0]
        n = int(np.prod(self.shape))
        lead = [np.arange(s, e) for s, e in self.region[:-1]]
        s_last, e_last = self.region[-1]
        if lead:
            mesh = np.meshgrid(*lead, indexing="ij")
            starts = np.ravel_multi_index(
                tuple(mesh) + (np.full(mesh[0].shape, s_last),), self.shape).reshape(-1)
        else:
            starts = np.asarray([s_last], dtype=np.int64)
        win_flat = (starts[:, None] + np.arange(e_last - s_last)).reshape(-1)
        self.win_flat = win_flat  # ascending (row-major region order)
        cover_ids = np.unique(win_flat // b)
        if self.scheme.is_lorenzo:
            # prefix hull: every block up to the last one the window touches
            self.block_ids = np.arange(int(cover_ids[-1]) + 1, dtype=np.int64)
        else:
            self.block_ids = cover_ids
        nb = int(self.block_ids.shape[0])
        self.sub_padded_shape = (nb * b,)
        # only the field's final block is partial, and it sorts last — so the
        # gathered valid elements are a prefix of the gathered layout
        per_block_valid = np.minimum(b, n - self.block_ids * b)
        self.sub_shape = (int(per_block_valid.sum()),)
        self.window = None
        rank = np.searchsorted(self.block_ids, win_flat // b)
        self.win_pos = (rank * b + win_flat % b).astype(np.int32)
        self.overlap = np.bincount(rank, minlength=nb).astype(np.int32)
        cover_rank = np.searchsorted(self.block_ids, cover_ids)
        self.aligned = bool(
            np.array_equal(self.overlap[cover_rank],
                           np.minimum(b, n - cover_ids * b)))
        self.grid_ranges = None
        self.grid_slices = None
        self.spatial_slices = None

    # -- payload word gather (Encoded fast path) ----------------------------
    def payload_gather(self, bits: int) -> GatherIndex:
        """Static word-gather arrays for a uniform-width payload at ``bits``.

        nd plans (``bits > 0``) carry only ``word_idx``: their per-value
        positions are built on device (:meth:`value_words`,
        :meth:`gathered_positions`), and the gathered words are the union of
        each run's contiguous word range, found without a per-value sort."""
        gi = self._gather_cache.get(bits)
        if gi is not None:
            return gi
        if self.scheme.is_nd and bits > 0:
            a, b = self._run_words(bits)
            prev_end = np.concatenate([[-1], np.maximum.accumulate(b)[:-1]])
            start = np.maximum(a, prev_end + 1)
            length = np.maximum(b - start + 1, 0)
            offset = np.cumsum(length) - length
            word_idx = (np.repeat(start - offset, length)
                        + np.arange(int(length.sum()), dtype=np.int64))
            gi = GatherIndex(word_idx.astype(np.int32), None, None, None,
                             self.gathered_elems)
            self._gather_cache[bits] = gi
            return gi
        if self.scheme.is_nd:
            axes = [np.arange(lo * b, hi * b)
                    for (lo, hi), b in zip(self.grid_ranges, self.block)]
            mesh = np.meshgrid(*axes, indexing="ij")
            gflat = np.ravel_multi_index(tuple(mesh), self.padded_shape).reshape(-1)
        else:
            b = self.block[0]
            gflat = (self.block_ids[:, None] * b + np.arange(b)).reshape(-1)
        m = int(gflat.shape[0])
        if bits == 0:
            gi = GatherIndex(np.zeros((0,), np.int32), np.zeros((m,), np.int32),
                             np.zeros((m,), np.int32), np.zeros((m,), np.uint32), m)
        else:
            total_words = encode.words_for(int(np.prod(self.padded_shape)), bits)
            offs = gflat.astype(np.int64) * bits
            w0 = offs >> 5
            uniq = np.unique(np.concatenate([w0, w0 + 1]))
            uniq = uniq[uniq < total_words]
            pos0 = np.searchsorted(uniq, w0).astype(np.int32)
            w1 = w0 + 1
            pos1 = np.where(w1 < total_words, np.searchsorted(uniq, w1),
                            uniq.shape[0]).astype(np.int32)
            gi = GatherIndex(uniq.astype(np.int32), pos0, pos1,
                             (offs & 31).astype(np.uint32), m)
        self._gather_cache[bits] = gi
        return gi

    def _run_words(self, bits: int) -> tuple[np.ndarray, np.ndarray]:
        """First and last payload word each run of an nd plan touches (a run
        is the gathered values along the last axis, in sub-field order):
        ``w0`` of its first value, and ``w0 + 1`` of its last, clipped to
        the payload."""
        total = encode.words_for(int(np.prod(self.padded_shape)), bits)
        first = np.zeros(self.sub_padded_shape[:-1], np.int64)
        stride = int(np.prod(self.padded_shape[1:], dtype=np.int64))
        for a, ((lo, hi), b) in enumerate(zip(self.grid_ranges[:-1],
                                              self.block[:-1])):
            ax = (np.arange((hi - lo) * b, dtype=np.int64) + lo * b) * stride
            first = first + ax.reshape([-1 if i == a else 1
                                        for i in range(first.ndim)])
            stride //= self.padded_shape[a + 1]
        first = first.reshape(-1) + self.grid_ranges[-1][0] * self.block[-1]
        last = first + self.sub_padded_shape[-1] - 1
        return (first * bits) >> 5, np.minimum(((last * bits) >> 5) + 1,
                                               total - 1)

    def value_words(self, bits: int) -> tuple[jax.Array, jax.Array]:
        """(low word, in-word bit shift) of every gathered value of an nd
        plan, in sub-field order, built on device from iotas.

        The per-value arrays of :meth:`payload_gather` enter a program as
        constants; for a large window (1/8 of a 512^3 field) that is
        hundreds of MB of program.  An nd plan gathers a box, so each
        value's flat index is affine in its box coordinates and needs no
        host array.  ``idx*bits`` is split as ``(idx >> 5)*bits +
        ((idx & 31)*bits >> 5)`` so no int32 product overflows.
        """
        nd = len(self.block)
        idx, stride = None, 1
        for a in reversed(range(nd)):
            (lo, hi), b = self.grid_ranges[a], self.block[a]
            ax = (jnp.arange((hi - lo) * b, dtype=jnp.int32) + lo * b) * stride
            term = ax.reshape([-1 if i == a else 1 for i in range(nd)])
            idx = term if idx is None else idx + term
            stride *= self.padded_shape[a]
        # the barrier keeps XLA from folding these data-independent arrays
        # into program constants at compile time (slow, and large)
        idx = jax.lax.optimization_barrier(idx.reshape(-1))
        frac = (idx & 31) * bits
        return (idx >> 5) * bits + (frac >> 5), (frac & 31).astype(jnp.uint32)

    def gathered_positions(self, bits: int):
        """``(pos0, pos1, shift)`` of :meth:`payload_gather`, for an nd
        plan, computed on device.

        The gathered words are ``payload_gather(bits).word_idx`` (sorted,
        unique).  A run of values along the last axis touches one
        contiguous word range, so within a run ``pos0 = w0 + delta_r`` with
        one host offset per run (the word's rank in the gathered set minus
        the word itself), and ``pos1 = pos0 + 1`` unless ``w0 + 1`` is past
        the payload (then the appended zero word)."""
        total = encode.words_for(int(np.prod(self.padded_shape)), bits)
        gi = self.payload_gather(bits)
        w0, shift = self.value_words(bits)
        run_len = self.sub_padded_shape[-1]
        a_r = self._run_words(bits)[0]
        delta = (np.searchsorted(gi.word_idx, a_r) - a_r).astype(np.int32)
        pos0 = (w0.reshape(-1, run_len)
                + jnp.asarray(delta)[:, None]).reshape(-1)
        pos1 = jnp.where(w0 + 1 < total, pos0 + 1, gi.n_words)
        return pos0, pos1, shift

    # -- sub-field assembly --------------------------------------------------
    def gather_metadata(self, c: Compressed | Encoded) -> jax.Array:
        """Metadata restricted to the gathered blocks (no payload decode)."""
        if not c.scheme.is_blockmean:
            return c.metadata  # Lorenzo: global anchor lives in the residuals
        if self.grid_slices is not None:
            return c.metadata[self.grid_slices]
        return c.metadata.reshape(-1)[jnp.asarray(self.block_ids.astype(np.int32))]

    def assemble(self, residuals: jax.Array, src: Compressed | Encoded) -> Compressed:
        """Build the honest sub-field around gathered residuals."""
        ids = jnp.asarray(self.block_ids.astype(np.int32))
        return Compressed(
            residuals=residuals, metadata=self.gather_metadata(src),
            bitwidths=src.bitwidths[ids], eps=src.eps,
            valid_counts=src.valid_counts[ids], scheme=src.scheme,
            shape=self.sub_shape, padded_shape=self.sub_padded_shape,
            block=src.block, orig_dtype=src.orig_dtype)

    # -- window access -------------------------------------------------------
    def window_of(self, arr: jax.Array) -> jax.Array:
        """Crop a sub-field spatial array to the requested window.

        nd schemes slice the gathered rectangle; 1-D schemes gather the
        window's flat positions (static index map) and restore the n-D shape.
        """
        if self.window is not None:
            return arr[self.window]
        return arr.reshape(-1)[jnp.asarray(self.win_pos)].reshape(self.win_shape)

    def lorenzo_mean_weights(self) -> tuple[np.ndarray, ...]:
        """Window-sum weights: ``sum_{i in window} q_i = <weights, residuals>``.

        Generalizes the full-field rank-1 Lorenzo mean: per-axis weights
        ``w_a[i] = #{j in window_a : j >= i}`` (separable, nd) or one flat
        weight vector counting window positions at-or-after each index (1-D).
        """
        if self._weights is not None:
            return self._weights
        if self.scheme.is_nd:
            ws = []
            for (s, e), length in zip(self.region, self.sub_padded_shape):
                i = np.arange(length)
                ws.append(np.clip(e - np.maximum(i, s), 0, None).astype(np.float32))
            self._weights = tuple(ws)
        else:
            i = np.arange(self.sub_padded_shape[0])
            w = self.n_window - np.searchsorted(self.win_flat, i, side="left")
            self._weights = (w.astype(np.float32),)
        return self._weights


# ---------------------------------------------------------------------------
# plan construction / memoization
# ---------------------------------------------------------------------------

_PLAN_CACHE: "OrderedDict[Tuple, RegionPlan]" = OrderedDict()
_PLAN_CACHE_LIMIT = 256


def canonical_closure(scheme: Scheme, closure: Closure,
                      region: object | None = None) -> Closure:
    """Canonical cache/plan-key form of a closure.

    1-D layouts have no per-axis bands (``("band", a)`` degrades to the
    prefix hull — exactly what :func:`plan_region` executes), and with no
    region the closure never enters any computation, so every full-field
    materialization shares one key (``"cover"``).
    """
    if region is None:
        return "cover"
    if not Scheme(scheme).is_nd and isinstance(closure, tuple):
        return "hull"
    return closure


def plan_region(c: Compressed | Encoded, region: RegionSpec,
                closure: Closure = "cover") -> RegionPlan:
    """Plan (and memoize) a region query over ``c``'s layout."""
    norm = normalize_region(region, c.shape)
    closure = canonical_closure(c.scheme, closure, norm)
    key = (c.scheme, c.shape, c.padded_shape, c.block, norm, closure)
    plan = _PLAN_CACHE.get(key)
    if plan is not None:
        _PLAN_CACHE.move_to_end(key)
        return plan
    plan = RegionPlan(c.scheme, c.shape, c.padded_shape, c.block, norm, closure)
    _PLAN_CACHE[key] = plan
    while len(_PLAN_CACHE) > _PLAN_CACHE_LIMIT:
        _PLAN_CACHE.popitem(last=False)
    return plan


def op_closure(scheme: Scheme, op: str, stage: Stage, axis: int = 0) -> Closure:
    """Dependency closure an op needs at a stage (see module docstring)."""
    if not Scheme(scheme).is_lorenzo:
        return "cover"
    if Scheme(scheme).is_nd and Stage(stage) == Stage.P and op == "derivative":
        return ("band", axis)
    return "hull"


def extract(c: Compressed | Encoded, plan: RegionPlan) -> Compressed:
    """The gathered sub-field; from :class:`Encoded` this unpacks only the
    payload words covering the plan's blocks (:func:`repro.core.encode.decode_region`)."""
    if isinstance(c, Encoded):
        return encode.decode_region(c, plan)
    if plan.spatial_slices is not None:
        residuals = c.residuals[plan.spatial_slices]
    else:
        b = c.block[0]
        blocked = c.residuals.reshape(-1, b)
        residuals = blocked[jnp.asarray(plan.block_ids.astype(np.int32))].reshape(-1)
    return plan.assemble(residuals, c)


def region_aligned(c: Compressed | Encoded, region: RegionSpec) -> bool:
    """Is the window block-aligned (so stage-① statistics stay eps-exact)?"""
    return plan_region(c, region, "cover").aligned


def closure_fraction(c: Compressed | Encoded, op: str, stage: Stage,
                     region: RegionSpec, axis: int = 0) -> float:
    """Fraction of the field a region query must touch at ``stage``.

    Cost-model input: measured full-field microseconds scale by this factor.
    Stage ① touches metadata only, so its fraction is in blocks; other stages
    are in elements of the gathered closure.  Multivariate ops average their
    per-axis derivative closures.
    """
    stage = Stage(stage)
    if op in ("divergence", "curl"):
        nd = len(c.shape)
        fr = [closure_fraction(c, "derivative", stage, region, axis=a)
              for a in range(nd)]
        return float(np.mean(fr))
    if stage == Stage.M:
        plan = plan_region(c, region, "cover")
        n_blocks = int(np.prod(plan.grid))
        return plan.n_sub_blocks / max(n_blocks, 1)
    plan = plan_region(c, region, op_closure(c.scheme, op, stage, axis))
    return plan.gathered_elems / max(int(np.prod(c.padded_shape)), 1)
