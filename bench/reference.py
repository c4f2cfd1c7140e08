"""Plain reference and the comparison that decides ``correct``.

The reference imports nothing of the program.  It applies each operation
to the original field (made by ``fields.py`` from the seed) and to the
field's error-bounded reconstruction, which it computes itself from the
format's definition: ``eps = rel_eb * (max - min)`` and
``q = round(x * (1 / (2 eps)))`` in float32, reconstruction ``2 eps q``.

On the chip the program evaluates ``x * (1 / (2 eps))`` a few ulps away
from IEEE float32, so a value whose ``x / (2 eps)`` lies within a hair of
a half-integer may land in either neighbouring bin, and both lie within
``eps`` of the original.  The reference therefore admits either bin for a
value within ``AMBIGUOUS`` of a half-integer, and compares an answer with
the interval of the operation's results over those choices.

Stencils follow the repository's definitions: central differences and the
``2 * ndim + 1``-point laplacian over the interior (one cell trimmed on
every side of every axis).  Statistics are the mean and the sample
standard deviation (``ddof=1``).  Everything is computed with ``jnp`` on
the default device, in float32 (sums of at most one row, combined in
float64 on the host).

The vector operations take their components in order: ``divergence`` is
the sum of each component's central difference along its own axis;
``curl`` of two components ``(u, v)`` is ``dv/d0 - du/d1``, of three
``(u, v, w)`` the vector ``(dw/d1 - dv/d2, du/d2 - dw/d0, dv/d0 - du/d1)``.

Each answer yields these numbers, reduced over a run's answers by their
maximum (``kind`` is ``stats`` for mean and std, ``stencil`` otherwise):

* ``eb_share.<kind>``: the largest ``|answer - op(x)|`` over the error
  the configuration guarantees for the operation, ``W * eps`` (``W``: the
  sum of the operation's absolute weights, 1 for the statistics; for a
  vector operation the sum of each term's weight times its component's
  ``eps``).
* ``qgap.<kind>``: how far the answer lies outside the interval of
  ``op(reconstruction)``, in units of ``eps`` (0 inside it): the largest
  over a stencil's values (for a vector operation, in units of the
  smallest ``eps`` of its components).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

STATS = ("mean", "std")
VECTOR = ("divergence", "curl")
AMBIGUOUS = 0.01  # bins: either rounding is admitted this close to a tie


def kind(op: str) -> str:
    return "stats" if op in STATS else "stencil"


def _shifted(nd: int, axis: int | None = None, off: int = 0):
    """Interior slice of an ``nd``-array, moved ``off`` along ``axis``."""
    sl = [slice(1, -1)] * nd
    if axis is not None:
        sl[axis] = slice(1 + off, (-1 + off) or None)
    return tuple(sl)


def terms(op: str, nd: int) -> list[list[tuple[float, tuple]]]:
    """Each output of a stencil ``op`` as ``[(weight, interior slice)]``."""
    def derivative(a):
        return [(0.5, _shifted(nd, a, 1)), (-0.5, _shifted(nd, a, -1))]
    if op.startswith("derivative"):
        return [derivative(int(op[len("derivative"):]))]
    if op == "gradient":
        return [derivative(a) for a in range(nd)]
    if op == "laplacian":
        out = [(-2.0 * nd, _shifted(nd))]
        for a in range(nd):
            out += [(1.0, _shifted(nd, a, 1)), (1.0, _shifted(nd, a, -1))]
        return [out]
    raise ValueError(f"no reference for op {op!r}")


def vector_terms(op: str, n: int, nd: int
                 ) -> list[list[tuple[float, int, tuple]]]:
    """Each output of a vector ``op`` over ``n`` components as
    ``[(weight, component, interior slice)]``."""
    def d(c, a, sign=1.0):
        return [(0.5 * sign, c, _shifted(nd, a, 1)),
                (-0.5 * sign, c, _shifted(nd, a, -1))]
    if op == "divergence":
        return [[t for a in range(n) for t in d(a, a)]]
    if op == "curl" and n == 2:
        return [d(1, 0) + d(0, 1, -1.0)]
    if op == "curl" and n == 3:
        return [d(2, 1) + d(1, 2, -1.0), d(0, 2) + d(2, 0, -1.0),
                d(1, 0) + d(0, 1, -1.0)]
    raise ValueError(f"no reference for {op!r} over {n} components")


def weight(op: str, nd: int) -> float:
    """Sum of the absolute weights of ``op`` (1 for the statistics)."""
    if op in STATS:
        return 1.0
    return sum(abs(w) for w, _ in terms(op, nd)[0])


def apply_stencil(op: str, f):
    """``op`` on an array of any float dtype, in that dtype (the control
    passes bfloat16)."""
    return [sum(f[sl] * jnp.asarray(w, f.dtype) for w, sl in out)
            for out in terms(op, f.ndim)]


def apply_stats(op: str, f: np.ndarray):
    """``op`` on a numpy array, in its dtype."""
    return f.mean() if op == "mean" else f.std(ddof=1)


def error_bound(x_min, x_max, rel_eb: float) -> np.float32:
    """``eps = rel_eb * (max - min)`` in float32, as the format defines it."""
    return np.float32((np.float32(x_max) - np.float32(x_min))
                      * np.float32(rel_eb))


def bins(t):
    """Nearest bin of ``t = x / (2 eps)`` and the step (-1, 0 or +1) to the
    other admitted bin."""
    xp = jnp if isinstance(t, jax.Array) else np
    q = xp.rint(t)
    amb = xp.abs(xp.abs(t - q) - 0.5) < AMBIGUOUS
    step = xp.where(amb, xp.where(t < q, -1, 1), 0)
    return q, step


@functools.partial(jax.jit, static_argnames=("op", "region"))
def _stencil_readings(got, x, inv, eps, op: str, region):
    if region is not None:
        x = x[tuple(slice(a, b) for a, b in region)]
    q, step = bins(x * inv)
    shares, gaps = [], []
    for g, out in zip(got, terms(op, x.ndim)):
        g = g.astype(jnp.float32)
        truth = sum(w * x[sl] for w, sl in out)
        val = sum(w * q[sl] for w, sl in out)
        lo = val + sum(jnp.minimum(0.0, w * step[sl]) for w, sl in out)
        hi = val + sum(jnp.maximum(0.0, w * step[sl]) for w, sl in out)
        u = g * inv  # the answer in bins
        gap = 2.0 * jnp.maximum(jnp.maximum(lo - u, u - hi), 0.0)
        bad = ~jnp.isfinite(g)
        shares.append(jnp.max(jnp.where(bad, jnp.inf, jnp.abs(g - truth))))
        gaps.append(jnp.max(jnp.where(bad, jnp.inf, gap)))
    return jnp.max(jnp.stack(shares)) / eps, jnp.max(jnp.stack(gaps))


@functools.partial(jax.jit, static_argnames=("op", "region"))
def _vector_readings(got, xs, invs, epss, op: str, region):
    if region is not None:
        xs = [x[tuple(slice(a, b) for a, b in region)] for x in xs]
    binned = [bins(x * inv) for x, inv in zip(xs, invs)]
    steps = [2.0 * e for e in epss]  # value of one bin, per component
    unit = jnp.min(jnp.stack(epss))
    shares, gaps = [], []
    for g, out in zip(got, vector_terms(op, len(xs), xs[0].ndim)):
        g = g.astype(jnp.float32)
        truth = sum(w * xs[c][sl] for w, c, sl in out)
        val = sum(w * steps[c] * binned[c][0][sl] for w, c, sl in out)
        lo = val + sum(jnp.minimum(0.0, w * steps[c] * binned[c][1][sl])
                       for w, c, sl in out)
        hi = val + sum(jnp.maximum(0.0, w * steps[c] * binned[c][1][sl])
                       for w, c, sl in out)
        bound = sum(abs(w) * epss[c] for w, c, _ in out)
        gap = jnp.maximum(jnp.maximum(lo - g, g - hi), 0.0) / unit
        bad = ~jnp.isfinite(g)
        shares.append(jnp.max(jnp.where(bad, jnp.inf, jnp.abs(g - truth)))
                      / bound)
        gaps.append(jnp.max(jnp.where(bad, jnp.inf, gap)))
    return jnp.max(jnp.stack(shares)), jnp.max(jnp.stack(gaps))


@functools.partial(jax.jit, static_argnames=("region",))
def _stats_sums(x, inv, region):
    """Sums over the last axis (float32 sums of at most a row, exact for
    the integer bins), combined in float64 on the host: the sums of ``x``,
    of the bins, of the steps down and up, of the ambiguous values, and
    then the centred squares of ``x`` and of the bins."""
    if region is not None:
        x = x[tuple(slice(a, b) for a, b in region)]
    q, step = bins(x * inv)
    ax = x.ndim - 1
    n = x.size
    sx, sq = jnp.sum(x, axis=ax), jnp.sum(q, axis=ax)
    mx, mq = jnp.sum(sx) / n, jnp.sum(sq) / n
    return (sx, sq, jnp.sum(jnp.minimum(step, 0), axis=ax),
            jnp.sum(jnp.maximum(step, 0), axis=ax),
            jnp.sum(step != 0, axis=ax),
            jnp.sum((x - mx) ** 2, axis=ax), jnp.sum((q - mq) ** 2, axis=ax))


class FieldTruth:
    """One original field and its quantization, for the comparison."""

    def __init__(self, x: jax.Array, rel_eb: float):
        self.x = x
        self.eps = error_bound(jnp.min(x), jnp.max(x), rel_eb)
        self.inv = np.float32(1.0) / (np.float32(2.0) * self.eps)
        self._stats: dict = {}

    def stats(self, op: str, region):
        """``(op(x), lo, hi)``: the truth and the interval of
        ``op(reconstruction)``, in float64."""
        if (op, region) not in self._stats:
            sx, sq, down, up, amb, cx, cq = (
                np.asarray(a, np.float64).sum() for a in
                _stats_sums(self.x, self.inv, region=region))
            n = (self.x.size if region is None
                 else int(np.prod([b - a for a, b in region])))
            scale = 2.0 * float(self.eps)
            self._stats["mean", region] = (
                sx / n, (sq + down) * scale / n, (sq + up) * scale / n)
            # |std(a) - std(b)| <= |a - b|_2 / sqrt(n - 1)
            mid = np.sqrt(cq / (n - 1)) * scale
            slack = scale * np.sqrt(amb / (n - 1))
            self._stats["std", region] = (
                np.sqrt(cx / (n - 1)), mid - slack, mid + slack)
        return self._stats[op, region]

    def host(self) -> np.ndarray:
        return np.asarray(self.x)


def compare(readings: dict, op: str, got: list, truths: list[FieldTruth],
            region) -> None:
    """Fold one answer's outputs into ``readings``, a dict of the numbers
    above (the largest reading wins; a number appears once an answer of
    its kind was compared).  ``truths`` holds the one field of a per-field
    op, or the components of a vector op in order.  A misshapen or
    non-finite output reads infinity."""
    truth = truths[0]
    eps = float(truth.eps)
    k = kind(op)
    nd = truth.x.ndim
    region = None if region is None else tuple(map(tuple, region))
    if k == "stats":
        g = float(np.asarray(got[0], np.float64))
        t, lo, hi = truth.stats(op, region)
        if np.isfinite(g):
            share = float(abs(g - t) / eps)
            gap = float(max(lo - g, g - hi, 0.0) / eps)
        else:
            share = gap = float("inf")
    else:
        shape = tuple((b - a) for a, b in region) if region else truth.x.shape
        want = tuple(d - 2 for d in shape)
        outs = (vector_terms(op, len(truths), nd) if op in VECTOR
                else terms(op, nd))
        if len(got) != len(outs) or any(tuple(g.shape) != want for g in got):
            share = gap = float("inf")
        elif op in VECTOR:
            s, gp = _vector_readings(
                list(got), [t.x for t in truths], [t.inv for t in truths],
                [t.eps for t in truths], op=op, region=region)
            share, gap = float(s), float(gp)
        else:
            s, gp = _stencil_readings(list(got), truth.x, truth.inv,
                                      truth.eps, op=op, region=region)
            share = float(s) / weight(op, nd)
            gap = float(gp)
    for name, v in ((f"eb_share.{k}", share), (f"qgap.{k}", gap)):
        readings[name] = max(readings.get(name, 0.0), v)
