"""Stencil operators vs closed-form fields (absolute-correctness oracles).

The stage-comparison tests in ``test_homomorphic.py`` check that stages ②③
agree with stage ④ — which lets absolute errors (sign flips, scale factors,
axis swaps) hide if they affect every stage equally.  These tests pin the
operators to fields with *known exact answers* on the unit index grid:

* quadratics — central differences and the 5/7-point Laplacian are exact;
* rigid rotation ``(u, v) = (-y, x)`` — curl is exactly +2 everywhere (this
  is the oracle that catches the historical ``du/dy - dv/dx`` sign flip);
* trigonometric — the central difference of ``sin(a·i)`` is exactly
  ``sin(a) · cos(a·i)``.

Fields are integer-valued and compressed with ``abs_eb=0.25``, so
quantization is exact (``q = 2·d``) and stages ②③④ must agree to round-off.
"""
import numpy as np
import pytest
import jax.numpy as jnp

from repro import obs
from repro.core import Stage, homomorphic as H, hszp, hszp_nd, hszx, hszx_nd

ALL = [hszp, hszx, hszp_nd, hszx_nd]
ND = [hszp_nd, hszx_nd]

N0, N1 = 48, 64


def _grid_2d():
    i = np.arange(N0, dtype=np.float32)[:, None]
    j = np.arange(N1, dtype=np.float32)[None, :]
    return i, j


def _compress(comp, data):
    # abs_eb=0.25 => q = round(d / 0.5) = 2*d exactly for integer-valued d
    return comp.compress(jnp.asarray(data, jnp.float32), abs_eb=0.25)


def _stages(comp):
    return [Stage.Q, Stage.F] + ([Stage.P] if comp.scheme.is_nd else [])


@pytest.mark.parametrize("comp", ALL, ids=lambda c: c.scheme.value)
@pytest.mark.parametrize("axis", [0, 1])
def test_derivative_quadratic_exact(comp, axis):
    """d(x^2)/dx by central difference is exactly 2x on the interior."""
    i, j = _grid_2d()
    f = ((i * i) if axis == 0 else (j * j)) + np.zeros((N0, N1), np.float32)
    c = _compress(comp, f)
    coord = (i if axis == 0 else j) + np.zeros((N0, N1), np.float32)
    expect = 2.0 * coord[1:-1, 1:-1]
    for stage in _stages(comp):
        got = np.asarray(H.derivative(c, stage, axis))
        np.testing.assert_allclose(got, expect, rtol=1e-5, atol=1e-3)


@pytest.mark.parametrize("comp", ALL, ids=lambda c: c.scheme.value)
def test_derivative_axis_order(comp):
    """f = i*N1 + j separates the axes: df/d0 == N1, df/d1 == 1 (an axis swap
    cannot produce either)."""
    i, j = _grid_2d()
    c = _compress(comp, i * N1 + j)
    for stage in _stages(comp):
        np.testing.assert_allclose(np.asarray(H.derivative(c, stage, 0)),
                                   np.full((N0 - 2, N1 - 2), N1, np.float32),
                                   rtol=1e-5, atol=1e-3)
        np.testing.assert_allclose(np.asarray(H.derivative(c, stage, 1)),
                                   np.ones((N0 - 2, N1 - 2), np.float32),
                                   rtol=1e-5, atol=1e-3)


@pytest.mark.parametrize("comp", ALL, ids=lambda c: c.scheme.value)
def test_laplacian_quadratic_exact(comp):
    """Laplacian of x^2 + y^2 is exactly 4 under the 5-point stencil (h=1)."""
    i, j = _grid_2d()
    c = _compress(comp, i * i + j * j)
    for stage in _stages(comp):
        got = np.asarray(H.laplacian(c, stage))
        np.testing.assert_allclose(got, np.full((N0 - 2, N1 - 2), 4.0, np.float32),
                                   rtol=1e-5, atol=1e-3)


@pytest.mark.parametrize("comp", ALL, ids=lambda c: c.scheme.value)
def test_divergence_radial_exact(comp):
    """div (x, y) = 2 exactly."""
    i, j = _grid_2d()
    cu = _compress(comp, i + np.zeros((N0, N1), np.float32))
    cv = _compress(comp, j + np.zeros((N0, N1), np.float32))
    for stage in _stages(comp):
        got = np.asarray(H.divergence([cu, cv], stage))
        np.testing.assert_allclose(got, np.full((N0 - 2, N1 - 2), 2.0, np.float32),
                                   rtol=1e-5, atol=1e-3)


@pytest.mark.parametrize("comp", ALL, ids=lambda c: c.scheme.value)
def test_curl_rigid_rotation_is_plus_two(comp):
    """The sign oracle: (u, v) = (-y, x) has curl dv/dx - du/dy == +2.

    The historical implementation computed du/dy - dv/dx (== -2 here); only
    stage-vs-stage comparisons could not see it.
    """
    i, j = _grid_2d()
    cu = _compress(comp, -(j + np.zeros((N0, N1), np.float32)))
    cv = _compress(comp, i + np.zeros((N0, N1), np.float32))
    for stage in _stages(comp):
        got = np.asarray(H.curl([cu, cv], stage))
        np.testing.assert_allclose(got, np.full((N0 - 2, N1 - 2), 2.0, np.float32),
                                   rtol=1e-5, atol=1e-3)


@pytest.mark.parametrize("comp", ND, ids=lambda c: c.scheme.value)
def test_curl_3d_rigid_rotation(comp):
    """3-D rotation about z: F = (-y, x, 0) has curl exactly (0, 0, 2)."""
    n = 24
    i, j, k = np.meshgrid(np.arange(n), np.arange(n), np.arange(n),
                          indexing="ij")
    z = np.zeros((n, n, n), np.float32)
    cu = _compress(comp, -(j.astype(np.float32)))
    cv = _compress(comp, i.astype(np.float32))
    cw = _compress(comp, z)
    for stage in (Stage.P, Stage.Q, Stage.F):
        cx, cy, cz = H.curl([cu, cv, cw], stage)
        interior = (n - 2, n - 2, n - 2)
        np.testing.assert_allclose(np.asarray(cx), np.zeros(interior), atol=1e-3)
        np.testing.assert_allclose(np.asarray(cy), np.zeros(interior), atol=1e-3)
        np.testing.assert_allclose(np.asarray(cz), np.full(interior, 2.0),
                                   rtol=1e-5, atol=1e-3)


@pytest.mark.parametrize("comp", ALL, ids=lambda c: c.scheme.value)
def test_derivative_trigonometric(comp):
    """Central difference of sin(a·i) is exactly sin(a)·cos(a·i); the
    compressed result must match within O(eps) at every supported stage."""
    a = 2 * np.pi * 3 / N0
    i, j = _grid_2d()
    f = np.sin(a * i) + 0.0 * j
    comp_field = comp.compress(jnp.asarray(f, jnp.float32), abs_eb=1e-4)
    eps = float(comp_field.eps)
    expect = (np.sin(a) * np.cos(a * i) + 0.0 * j)[1:-1, 1:-1]
    for stage in _stages(comp):
        got = np.asarray(H.derivative(comp_field, stage, 0))
        # central difference of d' where |d - d'| <= eps -> error <= eps
        np.testing.assert_allclose(got, expect, atol=2 * eps + 1e-6)


@pytest.mark.parametrize("comp", ALL, ids=lambda c: c.scheme.value)
def test_stats_linear_field_exact(comp):
    """mean/std of f = i*N1 + j (a permutation of 0..N-1) in closed form."""
    i, j = _grid_2d()
    n = N0 * N1
    c = _compress(comp, i * N1 + j)
    expect_mean = (n - 1) / 2.0
    expect_std = float(np.sqrt(n * (n + 1) / 12.0))  # sample std of 0..n-1
    stages = [Stage.P, Stage.Q, Stage.F] + \
        ([Stage.M] if comp.scheme.is_blockmean else [])
    for stage in stages:
        got = float(H.mean(c, stage))
        tol = 0.5 if stage == Stage.M else max(1e-4 * expect_mean, 1e-3)
        assert abs(got - expect_mean) <= tol, (stage, got)
    for stage in (Stage.P, Stage.Q, Stage.F):
        got = float(H.std(c, stage))
        assert abs(got - expect_std) <= max(1e-4 * expect_std, 1e-2), (stage, got)


def _grid_3d(shape):
    return [g.astype(np.float32)
            for g in np.meshgrid(*map(np.arange, shape), indexing="ij")]


#: a shape the 3-D vector kernel covers (rows in 8s, lanes in 128s)
KERNEL_SHAPE = (6, 16, 128)


@pytest.mark.parametrize("oracle", ["rigid_rotation", "radial"])
@pytest.mark.parametrize("comp", ALL, ids=lambda c: c.scheme.value)
def test_vector_3d_oracles_on_the_kernel_path(comp, oracle):
    """The same oracles where ``kernels.fused.vector3d_q`` answers (stages
    ③ ④): rotation about z, F = (-y, x, 0), has curl (0, 0, 2); the
    radial F = (x, y, z) has divergence 3."""
    i, j, k = _grid_3d(KERNEL_SHAPE)
    if oracle == "rigid_rotation":
        fields, op = (-j, i, np.zeros_like(i)), H.curl
        want = (0.0, 0.0, 2.0)
    else:
        fields, op = (i, j, k), H.divergence
        want = (3.0,)
    comps = [_compress(comp, f) for f in fields]
    interior = tuple(d - 2 for d in KERNEL_SHAPE)
    for stage in (Stage.Q, Stage.F):
        fused0 = obs.counters["vector_fused"]
        got = op(comps, stage)
        assert obs.counters["vector_fused"] == fused0 + 1
        got = got if isinstance(got, tuple) else (got,)
        for g, w in zip(got, want):
            np.testing.assert_allclose(np.asarray(g), np.full(interior, w),
                                       rtol=1e-5, atol=1e-3)
