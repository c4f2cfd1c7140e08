"""The NYX archive deployment at a small size: six 3-D fields held encoded
in a store whose cache keeps four of their six stage-③ planes.

Two templates served in turn through ``AnalyticsFrontend``, as in the
benchmark's ``nyx.archive`` cell: (A) divergence and curl of the velocity
fields 3-5, (B) mean and std of the fields 0-2, both ``stage="auto"``.  In
a four-plane LRU each request evicts the three planes the next one needs,
so from the second request on every request materializes three planes.
The answers must not depend on that: they equal, bit for bit, the same
requests against a store that keeps all six planes and storeless
``query``, and lie within the error bound of a plain numpy reference on
the original fields.
"""
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.analytics import query
from repro.core import Stage, expr, hszp_nd
from repro.kernels import ops as kernel_ops
from repro.serve import AnalyticsFrontend, AnalyticsRequest
from repro.store import FieldStore

SHAPE = (24, 20, 28)          # 3-D blocks pad two of the axes
REL_EB = 1e-3
PLANE = 4 * int(np.prod(SHAPE))   # bytes of one stage-③ int32 plane
N_REQUESTS = 8


def _field(i: int) -> np.ndarray:
    """Seeded smooth field: three octaves of one sine per axis, plus noise."""
    rng = np.random.default_rng([15, i])
    x = np.zeros(SHAPE, np.float32)
    for k in range(3):
        for a, d in enumerate(SHAPE):
            g = np.linspace(0, 1, d, dtype=np.float32)
            line = np.sin(2 * np.pi * rng.uniform(1.5, 4.0) * 2 ** k * g
                          + rng.uniform(0, 2 * np.pi))
            x = x + (line / 2 ** (k + 1)).reshape(
                [-1 if j == a else 1 for j in range(3)])
    return (x + rng.normal(0, 0.02, SHAPE)).astype(np.float32)


@pytest.fixture(scope="module")
def archive():
    xs = [_field(i) for i in range(6)]
    encs = [hszp_nd.encode(hszp_nd.compress(jnp.asarray(x), rel_eb=REL_EB))
            for x in xs]
    return xs, encs


def _templates(fields):
    """Roots of templates A and B over six leaves (ids or fields)."""
    v = tuple(fields[3:])
    a = [expr.divergence(v), expr.curl(v)]
    b = [r for f in fields[:3] for r in (expr.mean(f), expr.std(f))]
    return a, b


def _flat(values) -> list[np.ndarray]:
    out = []
    for v in values:
        out += list(v) if isinstance(v, tuple) else [v]
    return [np.asarray(u) for u in out]


def _serve(encs, cache_planes: int):
    """Templates A and B in turn through a frontend over a store of
    ``cache_planes`` stage-③ planes: per request, its flat answers and
    what it added to the materialization and eviction counters, to the
    materialization spans and to the fused materializations."""
    store = FieldStore(cache_bytes=cache_planes * PLANE)
    ids = [store.put(f"nyx/{i}", e) for i, e in enumerate(encs)]
    fe = AnalyticsFrontend(store=store)
    tpls = _templates(ids)
    out = []
    for uid in range(N_REQUESTS):
        made0 = obs.counters["store_materializations"]
        fused0 = obs.counters["store_materializations_fused"]
        evicted0 = obs.counters["store_evictions"]
        t0 = time.perf_counter_ns()
        fe.add_request(AnalyticsRequest(uid=uid, exprs=tpls[uid % 2],
                                        stage="auto"))
        (r,) = fe.step()
        assert r.error is None, r.error
        spans = sum(s[0] == obs.STORE_MATERIALIZE for s in obs.spans(t0))
        out.append((_flat(r.result),
                    obs.counters["store_materializations"] - made0,
                    obs.counters["store_evictions"] - evicted0, spans,
                    obs.counters["store_materializations_fused"] - fused0))
    return out, store


@pytest.fixture(scope="module")
def thrashing(archive):
    return _serve(archive[1], cache_planes=4)


def test_thrashing_answers_match_resident_store(archive, thrashing):
    served, _ = thrashing
    resident, store = _serve(archive[1], cache_planes=6)
    for (got, *_), (want, *_) in zip(served, resident):
        assert len(got) == len(want)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g, w)
    # all six planes stay: each is built once, none is evicted
    assert [r[1] for r in resident] == [3, 3] + [0] * (N_REQUESTS - 2)
    assert store.stats.evictions == 0 and store.cache_entries == 6


@pytest.mark.parametrize("stage", ["auto", Stage.Q], ids=["auto", "Q"])
def test_thrashing_answers_match_storeless_query(archive, thrashing, stage):
    served, _ = thrashing
    for i, tpl in enumerate(_templates(archive[1])):
        want = _flat(query(exprs=tpl, stage=stage).values)
        for uid in range(i, N_REQUESTS, 2):
            for g, w in zip(served[uid][0], want, strict=True):
                np.testing.assert_array_equal(g, w)


def test_each_request_materializes_and_evicts_three(thrashing):
    served, store = thrashing
    for uid, (_, made, evicted, spans, _) in enumerate(served):
        if uid >= 2:   # after the first cycle
            assert (made, evicted, spans) == (3, 3, 3), uid
    assert served[0][1:4] == (3, 0, 3)
    assert store.stats.misses == 3 * N_REQUESTS
    assert store.cache_entries == 4


def test_every_miss_is_fused(thrashing):
    """Each store miss of these encoded 3-D Lorenzo fields is built by the
    one-dispatch kernel branch: ``store_materializations_fused`` moves with
    ``store_materializations``."""
    served, _ = thrashing
    assert [r[4] for r in served] == [r[1] for r in served]


@pytest.mark.parametrize("op", ["divergence", "curl", "mean", "std"])
def test_kernel_seeded_answers_match_storeless_query(archive, op):
    """A stage-③ answer seeded from planes the kernel branch built equals
    the storeless query, bit for bit, with the kernels on and off."""
    encs = archive[1]
    store = FieldStore(cache_bytes=6 * PLANE)
    ids = [store.put(f"nyx/{i}", e) for i, e in enumerate(encs)]
    vec = op in ("divergence", "curl")

    def roots(fields):
        if vec:
            return [getattr(expr, op)(tuple(fields[3:]))]
        return [getattr(expr, op)(f) for f in fields[:3]]

    fused0 = obs.counters["store_materializations_fused"]
    got = _flat(query(exprs=roots(ids), stage=Stage.Q, store=store).values)
    assert obs.counters["store_materializations_fused"] - fused0 == 3
    for mode in ("interpret", "off"):
        with kernel_ops.override_mode(mode):
            want = _flat(query(exprs=roots(encs), stage=Stage.Q).values)
        assert len(got) == len(want)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g, w)


# -- the plain reference ------------------------------------------------------

def _eps(x: np.ndarray) -> float:
    return float(np.float32(REL_EB) * (x.max() - x.min()))


def _d(x: np.ndarray, axis: int) -> np.ndarray:
    """Central difference along ``axis`` over the interior, float32."""
    hi = [slice(1, -1)] * x.ndim
    lo = list(hi)
    hi[axis], lo[axis] = slice(2, None), slice(None, -2)
    return (x[tuple(hi)] - x[tuple(lo)]) * np.float32(0.5)


def _reference(xs):
    """``(op, outputs, bounds)`` in the order of templates A and B.  A
    central difference moves by at most the eps of its field; the mean of a
    reconstruction by eps, its sample std by eps * sqrt(n / (n - 1))."""
    u, v, w = xs[3:]
    e = [_eps(x) for x in xs]
    eu, ev, ew = e[3:]
    div = _d(u, 0) + _d(v, 1) + _d(w, 2)
    curl = [_d(w, 1) - _d(v, 2), _d(u, 2) - _d(w, 0), _d(v, 0) - _d(u, 1)]
    out = [("divergence", div, eu + ev + ew),
           ("curl", curl[0], ew + ev), ("curl", curl[1], eu + ew),
           ("curl", curl[2], ev + eu)]
    n = xs[0].size
    for x, ex in zip(xs[:3], e[:3]):
        out += [("mean", x.mean(dtype=np.float32), ex),
                ("std", x.std(ddof=1, dtype=np.float32),
                 ex * np.sqrt(n / (n - 1)))]
    return out


@pytest.mark.parametrize("op", ["divergence", "curl", "mean", "std"])
def test_answers_within_bound_of_numpy_reference(archive, thrashing, op):
    xs, _ = archive
    served, _ = thrashing
    got = served[N_REQUESTS - 2][0] + served[N_REQUESTS - 1][0]
    ref = _reference(xs)
    assert len(got) == len(ref)
    checked = 0
    for g, (name, want, bound) in zip(got, ref):
        if name != op:
            continue
        assert g.shape == np.shape(want)
        err = float(np.max(np.abs(g.astype(np.float64) - want)))
        # float32 rounding of the answer and the reference: far below 1e-3
        # of eps at these magnitudes
        assert err <= bound * (1 + 1e-3), (name, err, bound)
        checked += 1
    assert checked == {"divergence": 1, "curl": 3}.get(op, 3)
