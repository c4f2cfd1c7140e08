"""Block-sparse region queries: sparsity, correctness, planning, serving.

The contract under test (ISSUE 2 acceptance):

* a region query over a small window decodes only the covering blocks'
  payload words (asserted via the plan's gathered word count);
* for every (scheme, op, stage) cell, the region result equals the same op
  applied to the cropped full decompression, within stage tolerance;
* region geometry feeds stage planning (stage-① alignment, closure-scaled
  cost model) and batching (region is part of the jit-cache key).
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro import analytics
from repro.core import (Stage, UnsupportedStageError, encode,
                        homomorphic as H, hszp, hszp_nd, hszx, hszx_nd)
from repro.core import region as R
from repro.serve import AnalyticsFrontend, AnalyticsRequest

ALL = [hszp, hszx, hszp_nd, hszx_nd]
ND = [hszp_nd, hszx_nd]

REGION = ((30, 75), (10, 52))  # unaligned window of the 181x97 field_2d
WIN = tuple(slice(s, e) for s, e in REGION)


def _c(comp, data, rel_eb=1e-3):
    return comp.compress(jnp.asarray(data), rel_eb=rel_eb)


def _window_ref(comp, c):
    """The acceptance reference: crop the full decompression to the region."""
    return np.asarray(comp.decompress(c, Stage.F))[WIN]


# -- the sparsity contract ----------------------------------------------------

@pytest.mark.parametrize("bits", [1, 7, 12, 31, 32])
@pytest.mark.parametrize("case", [
    ((40, 48), (8, 8), ((5, 30), (9, 40)), "cover"),
    ((40, 48), (8, 8), ((0, 40), (0, 48)), "cover"),
    ((40, 48), (8, 8), ((16, 24), (3, 5)), (0, 1)),
    ((24, 40, 33), (8, 8, 8), ((3, 17), (10, 30), (0, 33)), "cover"),
    ((24, 40, 33), (8, 8, 8), ((3, 17), (10, 30), (5, 9)), "hull"),
], ids=["2d", "2d-full", "2d-band", "3d", "3d-hull"])
def test_device_gather_indices_match_host_plan(case, bits):
    """nd region plans build their per-value gather indices on device; they
    must equal the host plan's arrays exactly (global words for the
    single-device decode, gathered-set positions for the sharded merge)."""
    shape, block, region, closure = case
    padded = tuple(-(-n // b) * b for n, b in zip(shape, block))
    plan = R.RegionPlan(hszp_nd.scheme, shape, padded, block, region, closure)
    # host oracle: every gathered value's words, sorted and deduplicated
    axes = [np.arange(lo * b, hi * b)
            for (lo, hi), b in zip(plan.grid_ranges, block)]
    gflat = np.ravel_multi_index(np.meshgrid(*axes, indexing="ij"),
                                 padded).reshape(-1).astype(np.int64)
    total = encode.words_for(int(np.prod(padded)), bits)
    w0 = (gflat * bits) >> 5
    uniq = np.unique(np.concatenate([w0, w0 + 1]))
    uniq = uniq[uniq < total]
    np.testing.assert_array_equal(plan.payload_gather(bits).word_idx, uniq)
    got_w0, shift = plan.value_words(bits)
    np.testing.assert_array_equal(np.asarray(got_w0), w0)
    np.testing.assert_array_equal(np.asarray(shift), (gflat * bits) & 31)
    pos0, pos1, shift2 = plan.gathered_positions(bits)
    np.testing.assert_array_equal(np.asarray(pos0), np.searchsorted(uniq, w0))
    np.testing.assert_array_equal(
        np.asarray(pos1), np.where(w0 + 1 < total,
                                   np.searchsorted(uniq, w0 + 1), len(uniq)))
    np.testing.assert_array_equal(np.asarray(shift2), np.asarray(shift))


def test_region_decodes_only_covering_blocks():
    """A <=10% window gathers exactly its covering blocks and a proportional
    share of the payload words — never the whole field."""
    rng = np.random.default_rng(7)
    d = rng.normal(0, 1, (160, 160)).astype(np.float32)
    c = hszx_nd.compress(jnp.asarray(d), rel_eb=1e-3)   # block (16, 16)
    e = hszx_nd.encode(c)
    region = ((32, 80), (48, 96))                       # 48x48 = 9% of field
    plan = R.plan_region(e, region, "cover")
    assert plan.n_sub_blocks == 9                       # 3x3 covering blocks
    gi = plan.payload_gather(e.bits)
    assert gi.n_words < 0.15 * e.payload.size           # ~9% + block-row slack
    # the gathered decode is bit-exact vs the corresponding full-decode slice
    sub = encode.decode_region(e, plan)
    np.testing.assert_array_equal(np.asarray(sub.residuals),
                                  np.asarray(c.residuals)[32:80, 48:96])


def test_region_word_count_scales_with_window():
    rng = np.random.default_rng(8)
    e = hszx_nd.encode(hszx_nd.compress(
        jnp.asarray(rng.normal(0, 1, (160, 160)).astype(np.float32)),
        rel_eb=1e-3))
    small = R.plan_region(e, ((0, 16), (0, 16)), "cover").payload_gather(e.bits)
    large = R.plan_region(e, ((0, 96), (0, 96)), "cover").payload_gather(e.bits)
    assert small.n_words < large.n_words < e.payload.size


def test_lorenzo_closure_is_prefix_hull():
    """Lorenzo recorrelation is a prefix sum: the closure anchors at origin."""
    rng = np.random.default_rng(9)
    c = hszp_nd.compress(jnp.asarray(
        rng.normal(0, 1, (160, 160)).astype(np.float32)), rel_eb=1e-3)
    hull = R.plan_region(c, ((128, 160), (128, 160)), "hull")
    assert hull.grid_ranges == ((0, 10), (0, 10))
    band0 = R.plan_region(c, ((128, 160), (128, 160)), ("band", 0))
    assert band0.grid_ranges == ((8, 10), (0, 10))  # cover on the deriv axis
    assert band0.gathered_elems < hull.gathered_elems


# -- correctness: every (scheme, op, stage) cell ------------------------------

@pytest.mark.parametrize("comp", ALL, ids=lambda c: c.scheme.value)
def test_region_statistics_match_cropped_decompression(comp, field_2d):
    c = _c(comp, field_2d)
    e = comp.encode(c)
    win = _window_ref(comp, c)
    for field in (c, e):
        for stage in (Stage.P, Stage.Q, Stage.F):
            mu = float(H.mean(field, stage, region=REGION))
            assert abs(mu - win.mean()) <= 2e-4, (stage, mu, win.mean())
            sd = float(H.std(field, stage, region=REGION))
            assert abs(sd - win.std(ddof=1)) <= float(c.eps) + 1e-4, (stage, sd)


@pytest.mark.parametrize("comp", ALL, ids=lambda c: c.scheme.value)
@pytest.mark.parametrize("op", ["derivative", "laplacian"])
def test_region_stencils_match_cropped_decompression(comp, op, field_2d):
    c = _c(comp, field_2d)
    e = comp.encode(c)
    win = _window_ref(comp, c)
    stages = [Stage.Q, Stage.F] + ([Stage.P] if comp.scheme.is_nd else [])
    for field in (c, e):
        for stage in stages:
            if op == "derivative":
                for axis in (0, 1):
                    got = np.asarray(H.derivative(field, stage, axis,
                                                  region=REGION))
                    hi = [slice(1, -1)] * 2
                    lo = [slice(1, -1)] * 2
                    hi[axis], lo[axis] = slice(2, None), slice(None, -2)
                    ref = (win[tuple(hi)] - win[tuple(lo)]) * 0.5
                    np.testing.assert_allclose(got, ref, rtol=1e-4,
                                               atol=float(c.eps) * 1e-2)
            else:
                got = np.asarray(H.laplacian(field, stage, region=REGION))
                ref = (-4 * win[1:-1, 1:-1] + win[2:, 1:-1] + win[:-2, 1:-1]
                       + win[1:-1, 2:] + win[1:-1, :-2])
                np.testing.assert_allclose(got, ref, rtol=1e-4,
                                           atol=float(c.eps) * 1e-1)


@pytest.mark.parametrize("comp", ALL, ids=lambda c: c.scheme.value)
@pytest.mark.parametrize("op", ["divergence", "curl"])
def test_region_multivariate_match_cropped_decompression(comp, op, vector_field_2d):
    u, v = vector_field_2d
    cu, cv = _c(comp, u), _c(comp, v)
    region = ((20, 60), (40, 90))
    fn = H.divergence if op == "divergence" else H.curl
    du = np.asarray(comp.decompress(cu, Stage.F))[20:60, 40:90]
    dv = np.asarray(comp.decompress(cv, Stage.F))[20:60, 40:90]
    if op == "divergence":
        ref = ((du[2:, 1:-1] - du[:-2, 1:-1]) * 0.5
               + (dv[1:-1, 2:] - dv[1:-1, :-2]) * 0.5)
    else:  # curl = dv/dx - du/dy
        ref = ((dv[2:, 1:-1] - dv[:-2, 1:-1]) * 0.5
               - (du[1:-1, 2:] - du[1:-1, :-2]) * 0.5)
    stages = [Stage.Q, Stage.F] + ([Stage.P] if comp.scheme.is_nd else [])
    for stage in stages:
        got = np.asarray(fn([cu, cv], stage, region=region))
        np.testing.assert_allclose(got, ref, rtol=1e-4,
                                   atol=float(cu.eps) * 1e-1)


@pytest.mark.parametrize("comp", ND, ids=lambda c: c.scheme.value)
def test_region_3d(comp, field_3d):
    c = _c(comp, field_3d)
    region = ((4, 20), (10, 36), (5, 29))
    win = np.asarray(comp.decompress(c, Stage.F))[4:20, 10:36, 5:29]
    for stage in (Stage.P, Stage.Q):
        assert abs(float(H.mean(c, stage, region=region)) - win.mean()) <= 2e-4
        got = np.asarray(H.derivative(c, stage, 1, region=region))
        ref = (win[1:-1, 2:, 1:-1] - win[1:-1, :-2, 1:-1]) * 0.5
        np.testing.assert_allclose(got, ref, rtol=1e-4, atol=float(c.eps) * 1e-2)


def test_region_full_window_equals_full_field(field_2d):
    """region=(full extent) must reproduce the full-field op exactly."""
    for comp in ND:
        c = _c(comp, field_2d)
        full = tuple((0, s) for s in c.shape)
        for stage in (Stage.P, Stage.Q):
            np.testing.assert_allclose(
                float(H.mean(c, stage, region=full)),
                float(H.mean(c, stage)), rtol=1e-6, atol=1e-6)
            np.testing.assert_array_equal(
                np.asarray(H.derivative(c, stage, 0, region=full)),
                np.asarray(H.derivative(c, stage, 0)))


def test_region_slice_specs(field_2d):
    """slice / (start, stop) / None axis specs are equivalent."""
    c = _c(hszx_nd, field_2d)
    a = H.mean(c, Stage.P, region=(slice(30, 75), slice(10, 52)))
    b = H.mean(c, Stage.P, region=REGION)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    full_rows = H.mean(c, Stage.P, region=(None, (10, 52)))
    expect = H.mean(c, Stage.P, region=((0, 181), (10, 52)))
    np.testing.assert_array_equal(np.asarray(full_rows), np.asarray(expect))
    with pytest.raises(ValueError):
        H.mean(c, Stage.P, region=((0, 300), (0, 10)))
    with pytest.raises(ValueError):
        H.mean(c, Stage.P, region=((0, 10),))  # rank mismatch


# -- stage-1 alignment rule ---------------------------------------------------

def test_region_stage1_mean_requires_alignment():
    rng = np.random.default_rng(3)
    d = rng.normal(3.0, 1.0, (160, 160)).astype(np.float32)
    c = hszx_nd.compress(jnp.asarray(d), rel_eb=1e-3)  # block (16, 16)
    aligned = ((32, 80), (48, 96))
    mu = float(H.mean(c, Stage.M, region=aligned))
    assert abs(mu - d[32:80, 48:96].mean()) <= 2 * float(c.eps)
    with pytest.raises(UnsupportedStageError):
        H.mean(c, Stage.M, region=((33, 80), (48, 96)))
    # planner mirrors the op: auto drops stage 1 for unaligned windows
    assert analytics.plan_stage(c.scheme, "mean", "auto",
                                region=aligned, field=c) == Stage.M
    assert analytics.plan_stage(c.scheme, "mean", "auto",
                                region=((33, 80), (48, 96)), field=c) == Stage.P
    with pytest.raises(UnsupportedStageError):
        analytics.plan_stage(c.scheme, "mean", Stage.M,
                             region=((33, 80), (48, 96)), field=c)


# -- region-aware cost model --------------------------------------------------

def test_region_closure_fractions_flip_auto_stage():
    """Lorenzo stage-② derivative bands shrink with the window while stage-③
    prefix hulls do not: a far-corner window flips the auto plan to ②."""
    rng = np.random.default_rng(4)
    c = hszp_nd.compress(jnp.asarray(
        rng.normal(0, 1, (160, 160)).astype(np.float32)), rel_eb=1e-3)
    cm = analytics.CostModel()
    for stage, us in ((Stage.P, 100.0), (Stage.Q, 50.0), (Stage.F, 200.0)):
        cm.record(c.scheme, "derivative", stage, us)
    # full field: stage Q measured cheapest
    assert analytics.plan_stage(c.scheme, "derivative", "auto", cm) == Stage.Q
    # far-corner window: the stage-P band touches 0.2 of the field while the
    # stage-Q hull touches all of it -> 100*0.2 < 50*1.0 picks P
    region = ((128, 160), (128, 160))
    assert analytics.plan_stage(c.scheme, "derivative", "auto", cm,
                                region=region, field=c, axis=0) == Stage.P
    fr_p = R.closure_fraction(c, "derivative", Stage.P, region, axis=0)
    fr_q = R.closure_fraction(c, "derivative", Stage.Q, region, axis=0)
    assert fr_p == pytest.approx(0.2) and fr_q == pytest.approx(1.0)


def test_closure_fraction_blockmean_scales_with_window():
    rng = np.random.default_rng(5)
    c = hszx_nd.compress(jnp.asarray(
        rng.normal(0, 1, (160, 160)).astype(np.float32)), rel_eb=1e-3)
    region = ((128, 160), (128, 160))
    for stage in (Stage.P, Stage.Q, Stage.F):
        fr = R.closure_fraction(c, "mean", stage, region)
        assert fr == pytest.approx((32 * 32) / (160 * 160))
    assert R.closure_fraction(c, "mean", Stage.M, region) == pytest.approx(4 / 100)


# -- engine / query / serving -------------------------------------------------

def _compress_many(comp, n, shape=(96, 80), rel_eb=1e-3, seed=0):
    rng = np.random.default_rng(seed)
    return [comp.compress(jnp.asarray(rng.normal(0, 1, shape).astype(np.float32)),
                          rel_eb=rel_eb) for _ in range(n)]


@pytest.mark.parametrize("comp", ALL, ids=lambda c: c.scheme.value)
def test_query_region_batched_matches_per_field(comp):
    cs = _compress_many(comp, 4)
    region = ((10, 40), (20, 60))
    for op in ("mean", "std", "derivative"):
        for stage in analytics.feasible_stages(comp.scheme, op):
            if stage == Stage.M:
                continue  # unaligned window: stage 1 infeasible by design
            res = analytics.query(cs, op, stage=stage, region=region)
            if op == "mean":
                fn = jax.jit(lambda c, s=stage: H.mean(c, s, region=region))
            elif op == "std":
                fn = jax.jit(lambda c, s=stage: H.std(c, s, region=region))
            else:
                fn = jax.jit(lambda c, s=stage: H.derivative(c, s, 0,
                                                             region=region))
            for got, c in zip(res.values, cs):
                np.testing.assert_array_equal(np.asarray(got),
                                              np.asarray(fn(c)))


def test_region_part_of_jit_cache_key():
    eng = analytics.BatchedAnalytics()
    cs = _compress_many(hszx_nd, 2)
    r1, r2 = ((0, 32), (0, 32)), ((32, 64), (16, 48))
    out1 = eng.run(cs, "mean", Stage.P, region=r1)
    assert eng.cache_size == 1
    eng.run(cs, "mean", Stage.P, region=r1)
    assert eng.cache_size == 1      # same region -> cache hit
    out2 = eng.run(cs, "mean", Stage.P, region=r2)
    assert eng.cache_size == 2      # different region -> new program
    assert not np.allclose(np.asarray(out1), np.asarray(out2))


def test_serve_equivalent_region_specs_group_together(field_2d):
    """slice vs (start, stop) vs numpy-int specs of the same window must land
    in one batch group (the signature normalizes, not repr-compares)."""
    from repro.serve.analytics import _region_signature
    f = _c(hszx_nd, field_2d)
    reqs = [AnalyticsRequest(uid=0, fields=f, region=REGION),
            AnalyticsRequest(uid=1, fields=f,
                             region=(slice(30, 75), slice(10, 52))),
            AnalyticsRequest(uid=2, fields=f,
                             region=((np.int64(30), np.int64(75)), (10, 52)))]
    sigs = {_region_signature(r) for r in reqs}
    assert len(sigs) == 1
    assert _region_signature(AnalyticsRequest(uid=3, fields=f)) is None


def test_serve_region_requests(field_2d):
    fields = [_c(hszx_nd, field_2d), _c(hszx_nd, field_2d * 0.5)]
    fe = AnalyticsFrontend()
    fe.add_request(AnalyticsRequest(uid=0, fields=fields[0], op="mean",
                                    region=REGION))
    fe.add_request(AnalyticsRequest(uid=1, fields=fields[1], op="mean",
                                    region=REGION))
    fe.add_request(AnalyticsRequest(uid=2, fields=fields[0], op="mean"))
    fe.add_request(AnalyticsRequest(uid=3, fields=fields[0], op="laplacian",
                                    region=REGION))
    done = {r.uid: r for r in fe.run_until_drained()}
    assert all(r.error is None for r in done.values())
    win = _window_ref(hszx_nd, fields[0])
    assert abs(float(done[0].result) - win.mean()) <= 2e-4
    assert done[2].result_stage == Stage.M          # full field: metadata mean
    assert done[0].result_stage == Stage.P          # unaligned region: stage 2
    h, w = REGION[0][1] - REGION[0][0], REGION[1][1] - REGION[1][0]
    assert done[3].result.shape == (h - 2, w - 2)
    # region vs full-field requests compile separate programs, same-region
    # mean requests batch together: mean-region + mean-full + laplacian = 3
    assert fe.engine.cache_size == 3
