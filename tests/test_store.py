"""Materialized-stage field store (ISSUE 4 acceptance).

The contract under test:

* store-backed query results are **bit-identical** to storeless queries for
  every (scheme, op-set, stage, ±region) cell — field-arity and vector-arity
  sets, Compressed and Encoded containers;
* ``stage="auto"`` provably flips to a cached stage when the cache-aware
  cost model says so — both uncalibrated (residency beats reconstruction)
  and calibrated (measured cost minus fig34 reconstruction term) — and,
  store-backed and uncalibrated, plans the retainable stage from whose
  materialization the selected rules run straight (``oplib.reads_seed``,
  pinned per XLA and fused lowering rule against its jaxpr);
* the ``FieldStore`` is a byte-budgeted LRU with exact hit / miss /
  eviction accounting and id-invalidation rules;
* serve resolves string field ids end to end with one dispatch per group;
* ``CostModel.save``/``load`` JSON round-trips the full calibration state.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.extend.core import Var

from repro import analytics, obs
from repro.analytics import BatchedAnalytics, CostModel, query
from repro.analytics.query import _slot_stages
from repro.core import (Scheme, Stage, homomorphic as H, hszp, hszp_nd, hszx,
                        hszx_nd, oplib)
from repro.serve import AnalyticsFrontend, AnalyticsRequest
from repro.core import expr
from repro.kernels import ops as kops
from repro.store import (FieldStore, MaterializedStage, materialize,
                         materialized_nbytes)

ALL = [hszp, hszx, hszp_nd, hszx_nd]
REGION = ((30, 75), (10, 52))  # unaligned window of the 181x97 field_2d

FUSED_SETS = [("mean",), ("mean", "std"), ("mean", "std", "laplacian"),
              ("std", "derivative"), ("mean", "gradient")]


def _c(comp, data, rel_eb=1e-3):
    return comp.compress(jnp.asarray(data), rel_eb=rel_eb)

def _compress_many(comp, n, shape=(64, 48), rel_eb=1e-3, seed=0):
    rng = np.random.default_rng(seed)
    return [comp.compress(jnp.asarray(rng.normal(0, 1, shape).astype(np.float32)),
                          rel_eb=rel_eb) for _ in range(n)]


def _shared_stages(scheme, ops):
    return [s for s in Stage if s != Stage.M
            if all(s in analytics.feasible_stages(scheme, op) for op in ops)]


def _assert_same(got, ref):
    if isinstance(ref, tuple):
        assert isinstance(got, tuple) and len(got) == len(ref)
        for g, r in zip(got, ref):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(r))
    else:
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


# -- materialized stages ------------------------------------------------------

@pytest.mark.parametrize("comp", ALL, ids=lambda c: c.scheme.value)
def test_materialized_stage_is_pytree(comp, field_2d):
    import jax
    e = comp.encode(_c(comp, field_2d))
    m = materialize(e, Stage.Q)
    leaves = jax.tree_util.tree_leaves(m)
    assert leaves and m.nbytes == sum(x.size * x.dtype.itemsize for x in leaves)
    m2 = jax.tree.map(lambda x: x, m)
    assert isinstance(m2, MaterializedStage) and m2.sig() == m.sig()
    # stacking (what the engine's seeded programs do) keeps the treedef
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), m, m2)
    assert stacked.q_spatial.shape == (2,) + m.q_spatial.shape


# the one-dispatch 3-D Lorenzo branch: (compressor, field, stage, region,
# kernel mode, built by the kernel?)
_FUSED_CASES = [
    ("hszp_nd", "3d", Stage.Q, None, "interpret", True),
    ("hszp_nd", "3d", Stage.F, None, "interpret", True),
    ("hszp_nd", "3d", Stage.Q, ((2, 20), (5, 31), (0, 33)), "interpret",
     False),
    ("hszp_nd", "3d", Stage.P, None, "interpret", False),
    ("hszp_nd", "3d", Stage.Q, None, "off", False),
    ("hszx_nd", "3d", Stage.Q, None, "interpret", False),
    ("hszp", "3d", Stage.Q, None, "interpret", False),
    ("hszp_nd", "2d", Stage.Q, None, "interpret", False),
]


@pytest.mark.parametrize(
    "comp,dim,stage,region,mode,fused", _FUSED_CASES,
    ids=["q", "f", "region", "p", "off", "blockmean", "1d", "2d"])
def test_materialize_fused_branch_engages_by_shape(comp, dim, stage, region,
                                                   mode, fused, field_2d,
                                                   field_3d):
    """Only a full-field stage-③ (or ④) materialization of an encoded 3-D
    Lorenzo field takes the kernel branch, counted in
    ``store_materializations_fused``; its plane is the XLA prelude's."""
    comp = {"hszp_nd": hszp_nd, "hszx_nd": hszx_nd, "hszp": hszp}[comp]
    e = comp.encode(_c(comp, field_3d if dim == "3d" else field_2d))
    fused0 = obs.counters["store_materializations_fused"]
    with kops.override_mode(mode):
        m = materialize(e, stage, region=region)
    assert obs.counters["store_materializations_fused"] - fused0 == fused
    if m.q_spatial is not None:
        with kops.override_mode("off"):
            want = oplib.StageContext(e, Stage.Q, region,
                                      m.closure).q_spatial
        np.testing.assert_array_equal(np.asarray(m.q_spatial),
                                      np.asarray(want))


def test_materialize_rejects_stage_m(field_2d):
    e = hszx_nd.encode(_c(hszx_nd, field_2d))
    with pytest.raises(ValueError, match="already resident"):
        materialize(e, Stage.M)


def test_mismatched_seed_rejected(field_2d):
    e = hszp_nd.encode(_c(hszp_nd, field_2d))
    m_q = materialize(e, Stage.Q)
    with pytest.raises(ValueError, match="does not match"):
        H.compute(e, "mean", Stage.P, seed=m_q)
    m_reg = materialize(e, Stage.Q, region=REGION, closure="hull")
    with pytest.raises(ValueError, match="does not match"):
        H.compute(e, "mean", Stage.Q, seed=m_reg)  # region key mismatch


# -- bit-identical store-backed queries: every (scheme, op-set, stage, ±region)

@pytest.mark.parametrize("comp", ALL, ids=lambda c: c.scheme.value)
@pytest.mark.parametrize("ops", FUSED_SETS, ids="+".join)
def test_store_backed_bit_identical(comp, ops, field_2d):
    c = _c(comp, field_2d)
    e = comp.encode(c)
    for field in (c, e):
        store = FieldStore()
        store.put("f", field)
        eng = BatchedAnalytics()
        for stage in _shared_stages(comp.scheme, ops):
            for region in (None, REGION):
                ref = query([field], list(ops), stage=stage, engine=eng,
                            region=region)
                got = query(["f"], list(ops), stage=stage, engine=eng,
                            region=region, store=store)
                hot = query(["f"], list(ops), stage=stage, engine=eng,
                            region=region, store=store)
                # first call misses (unless stage ④ reuses the ③ entry),
                # second is always served resident
                assert got.store_misses + got.store_hits >= 1
                assert hot.store_misses == 0 and hot.store_hits >= 1
                for op in ops:
                    _assert_same(got.values[0][op], ref.values[0][op])
                    _assert_same(hot.values[0][op], ref.values[0][op])


@pytest.mark.parametrize("comp", [hszp_nd, hszx_nd], ids=lambda c: c.scheme.value)
@pytest.mark.parametrize("ops", [("divergence",), ("divergence", "curl")],
                         ids="+".join)
def test_store_backed_vector_bit_identical(comp, ops, vector_field_2d):
    u, v = vector_field_2d
    cu, cv = _c(comp, u), _c(comp, v)
    store = FieldStore()
    store.put("u", cu)
    store.put("v", cv)
    eng = BatchedAnalytics()
    region = ((20, 60), (40, 90))
    for stage in _shared_stages(comp.scheme, ops):
        for r in (None, region):
            ref = query([(cu, cv)], list(ops), stage=stage, engine=eng,
                        region=r)
            for _ in range(2):  # miss pass, then hit pass
                got = query([("u", "v")], list(ops), stage=stage, engine=eng,
                            region=r, store=store)
                for op in ops:
                    _assert_same(got.values[0][op], ref.values[0][op])
            assert got.store_hits >= 2  # both components served hot


def test_store_backed_batch_and_mixed_inputs(field_2d):
    """Ids and raw containers mix in one query; ids group separately (only
    they can seed) but every value matches the storeless reference."""
    cs = _compress_many(hszp_nd, 4)
    store = FieldStore()
    for i, c in enumerate(cs[:2]):
        store.put(f"f{i}", c)
    eng = BatchedAnalytics()
    ref = query(cs, ["mean", "std"], stage=Stage.P, engine=eng)
    got = query(["f0", "f1", cs[2], cs[3]], ["mean", "std"], stage=Stage.P,
                engine=eng, store=store)
    assert got.n_batches == 2  # store-backed vs raw split
    for i in range(4):
        for op in ("mean", "std"):
            _assert_same(got.values[i][op], ref.values[i][op])


def test_seeded_engine_program_is_separate_and_equal(field_2d):
    eng = BatchedAnalytics()
    cs = _compress_many(hszp_nd, 3)
    store = FieldStore()
    ids = [store.put(f"f{i}", c) for i, c in enumerate(cs)]
    cold = query(cs, "std", stage=Stage.Q, engine=eng)
    assert eng.cache_size == 1
    hot = query(ids, "std", stage=Stage.Q, engine=eng, store=store)
    assert eng.cache_size == 2  # seeded program compiles separately
    for a, b in zip(cold.values, hot.values):
        _assert_same(b, a)
    query(ids, "std", stage=Stage.Q, engine=eng, store=store)
    assert eng.cache_size == 2  # and is reused on the hit path


def test_stage_f_std_accurate_for_mean_dominated_fields():
    """The stage-④ std (the accuracy reference) must be mean-subtracted: a
    single-pass moments form catastrophically cancels in f32 when the mean
    dominates the spread (1000 ± 0.1 -> garbage)."""
    rng = np.random.default_rng(3)
    d = (1000.0 + rng.normal(0, 0.1, (128, 128))).astype(np.float32)
    c = hszx_nd.compress(jnp.asarray(d), rel_eb=1e-4)
    got = float(H.std(c, Stage.F))
    assert abs(got - d.std(ddof=1)) < 1e-3, (got, d.std(ddof=1))


def test_vector_op_bare_string_id_rejected(field_2d):
    store = FieldStore()
    store.put("uv", _c(hszp_nd, field_2d))
    with pytest.raises(TypeError, match="per component"):
        query(["uv"], "curl", stage=Stage.Q, store=store)


# -- cache-aware auto planning ------------------------------------------------

def test_auto_flips_to_cached_stage_uncalibrated(field_2d):
    """Residency alone flips the plan: Lorenzo {mean, std} auto-plans ② cold,
    but a resident stage-③ materialization beats any reconstruction."""
    c = _c(hszp_nd, field_2d)
    store = FieldStore()
    store.put("f", c)
    eng = BatchedAnalytics()
    cold = query([c], ["mean", "std"], engine=eng)
    assert cold.stages[0] == {"mean": Stage.P, "std": Stage.P}
    store.ensure("f", Stage.Q)
    hot = query(["f"], ["mean", "std"], engine=eng, store=store)
    assert hot.stages[0] == {"mean": Stage.Q, "std": Stage.Q}
    assert hot.store_hits >= 1
    ref = query([c], ["mean", "std"], stage=Stage.Q, engine=eng)
    for op in ("mean", "std"):
        _assert_same(hot.values[0][op], ref.values[0][op])


def test_auto_flip_calibrated_reconstruction_term():
    """With measured costs, a cached stage is priced at cost minus the fig34
    reconstruction term — which flips the choice exactly when that term is
    what made the higher stage lose."""
    scheme = Scheme.HSZP_ND
    cm = CostModel()
    for op in ("mean", "std"):
        cm.record(scheme, op, Stage.P, 100.0)
        cm.record(scheme, op, Stage.Q, 120.0)
        cm.record(scheme, op, Stage.F, 500.0)
    cm.record_reconstruction(scheme, Stage.Q, 110.0)
    # cold: P wins (200 < 240)
    assert analytics.plan_stages(scheme, ["mean", "std"],
                                 cost_model=cm).fused == Stage.P
    # Q resident: 2 * (120 - 110) = 20 < 200 -> flips to Q
    plan = analytics.plan_stages(scheme, ["mean", "std"], cost_model=cm,
                                 cached=frozenset({Stage.Q}))
    assert plan.fused == Stage.Q
    # a cached stage never goes below zero cost, and stage order breaks ties
    cm.record_reconstruction(scheme, Stage.P, 500.0)
    assert cm.cost(scheme, "mean", Stage.P, cached=True) == 0.0


def test_unmeasured_reconstruction_discount_is_conservative():
    """A cached stage with no measured reconstruction must not undercut a
    measured rival on made-up numbers: the fallback discount is the largest
    reconstruction measured at a *lower* stage (monotone in stage), so a
    stage-③ entry that also serves stage ④ still routes the plan to ③."""
    scheme = Scheme.HSZP_ND
    cm = CostModel()
    for op, p, q, f in (("mean", 90.0, 130.0, 700.0),
                        ("std", 95.0, 140.0, 700.0)):
        cm.record(scheme, op, Stage.P, p)
        cm.record(scheme, op, Stage.Q, q)
        cm.record(scheme, op, Stage.F, f)
    cm.record_reconstruction(scheme, Stage.Q, 80.0)
    # Q entry resident => both Q and F count as cached; F's reconstruction
    # is unmeasured and discounts by recon(Q)=80, keeping F at 1240 vs 110
    plan = analytics.plan_stages(scheme, ["mean", "std"], cost_model=cm,
                                 cached=frozenset({Stage.Q, Stage.F}))
    assert plan.fused == Stage.Q
    assert cm.cost(scheme, "mean", Stage.F, cached=True) == 620.0
    # with nothing measured at a lower stage there is no discount at all
    cm2 = CostModel()
    cm2.record(scheme, "mean", Stage.P, 90.0)
    cm2.record(scheme, "mean", Stage.Q, 130.0)
    assert cm2.cost(scheme, "mean", Stage.Q, cached=True) == 130.0


def test_plan_stage_cached_preference_keeps_metadata_fast_path():
    """Stage ① needs no reconstruction (metadata is resident in the
    container), so a cached higher stage must not displace it."""
    assert analytics.plan_stage(Scheme.HSZX_ND, "mean",
                                cached=frozenset({Stage.Q})) == Stage.M
    assert analytics.plan_stage(Scheme.HSZP_ND, "mean",
                                cached=frozenset({Stage.Q})) == Stage.Q


def _cached_stages(store, fid, ops, *, region=None):
    """The planner's residency probe (``query._slot_stages``) for one
    store-backed field under a flat op set."""
    names = oplib.canonical_ops(ops)
    c = store.get(fid)
    resident, _ = _slot_stages(
        store, (fid,), (c,), names,
        lambda s: (oplib.set_closure(names, c.scheme, s),), region)
    return resident


def test_cached_stages_requires_matching_region_and_closure(field_2d):
    c = _c(hszp_nd, field_2d)
    store = FieldStore()
    store.put("f", c)
    store.ensure("f", Stage.Q)
    # the stage-③ integers serve stage ④ too (dequantize is postlude)
    assert _cached_stages(store, "f", ["mean", "std"]) == {Stage.Q, Stage.F}
    # the full-field entry does not serve a region query (different key) ...
    assert _cached_stages(store, "f", ["mean", "std"],
                          region=REGION) == frozenset()
    cl = oplib.set_closure(["mean", "std"], c.scheme, Stage.Q)
    store.ensure("f", Stage.Q, region=REGION, closure=cl)
    assert _cached_stages(store, "f", ["mean", "std"],
                          region=REGION) == {Stage.Q, Stage.F}
    # ... and closures are part of the key: a stage-② derivative band entry
    # is not the hull the {mean, std} set needs
    band = oplib.set_closure("derivative", c.scheme, Stage.P, axis=0)
    hull = oplib.set_closure(["mean", "std"], c.scheme, Stage.P)
    assert band != hull
    store.ensure("f", Stage.P, region=REGION, closure=band)
    assert Stage.P not in _cached_stages(store, "f", ["mean", "std"],
                                         region=REGION)
    assert Stage.P in _cached_stages(store, "f", "derivative", region=REGION)


# -- FieldStore semantics -----------------------------------------------------

def test_field_registry_semantics(field_2d):
    c = _c(hszx_nd, field_2d)
    store = FieldStore()
    store.put("a", c)
    assert "a" in store and store.get("a") is c and store.ids() == ("a",)
    with pytest.raises(ValueError, match="already registered"):
        store.put("a", c)
    with pytest.raises(KeyError, match="unknown field id"):
        store.get("missing")
    with pytest.raises(TypeError):
        store.put("b", np.zeros(4))
    with pytest.raises(ValueError):
        store.put("", c)


def test_replace_and_remove_invalidate_materializations(field_2d):
    c1 = _c(hszx_nd, field_2d)
    c2 = _c(hszx_nd, field_2d * 2.0)
    store = FieldStore()
    store.put("a", c1)
    store.ensure("a", Stage.Q)
    assert store.cache_entries == 1
    store.put("a", c2, replace=True)
    assert store.cache_entries == 0  # stale intermediate dropped
    assert store.stats.evictions == 1  # invalidation counts as churn
    m = store.ensure("a", Stage.Q)
    _assert_same(m.q_spatial, materialize(c2, Stage.Q).q_spatial)
    store.remove("a")
    assert "a" not in store and store.cache_entries == 0
    assert store.cache_bytes_in_use == 0


def test_lru_eviction_under_byte_budget(field_2d):
    c = _c(hszx_nd, field_2d)
    one = materialize(c, Stage.Q).nbytes
    store = FieldStore(cache_bytes=int(2.5 * one))
    for i in range(3):
        store.put(f"f{i}", c)
    store.ensure("f0", Stage.Q)
    store.ensure("f1", Stage.Q)
    assert store.cache_entries == 2
    store.lookup("f0", Stage.Q)          # refresh f0 -> f1 becomes LRU
    store.ensure("f2", Stage.Q)          # budget forces one eviction
    assert store.cache_entries == 2
    assert store.stats.evictions == 1
    assert store.cache_bytes_in_use <= store.cache_bytes
    assert store.lookup("f0", Stage.Q) is not None   # survivor
    assert store.lookup("f1", Stage.Q) is None       # evicted (miss)
    assert (store.stats.hits, store.stats.misses) == (2, 4)


def test_oversized_entry_not_retained(field_2d):
    c = _c(hszx_nd, field_2d)
    store = FieldStore(cache_bytes=16)   # smaller than any materialization
    store.put("a", c)
    m = store.ensure("a", Stage.Q)       # still computed and returned ...
    assert m.q_spatial is not None
    assert store.cache_entries == 0      # ... but never resident
    # counted as a rejection, not an eviction (it was never resident)
    assert store.stats.rejected == 1 and store.stats.evictions == 0
    # seed() declines outright (no wasted reconstruction), so queries fall
    # back to unseeded execution instead of re-materializing every call
    assert store.seed("a", Stage.Q) is None
    assert store.stats.rejected == 2
    misses0 = store.stats.misses
    res = query(["a"], ["mean", "std"], stage=Stage.Q, store=store)
    ref = query([c], ["mean", "std"], stage=Stage.Q)
    for op in ("mean", "std"):
        _assert_same(res.values[0][op], ref.values[0][op])
    assert store.stats.misses == misses0  # never touched the cache again


@pytest.mark.parametrize("comp", ALL, ids=lambda c: c.scheme.value)
def test_materialized_nbytes_predicts_exactly(comp, field_2d):
    """The static size predictor must equal the realized nbytes — it is the
    retention decision, so drift would retain unboundedly or decline hot
    cells."""
    from repro.store import materialized_nbytes
    e = comp.encode(_c(comp, field_2d))
    for stage in (Stage.P, Stage.Q, Stage.F):
        for region, cl in ((None, "cover"),
                           (REGION, oplib.set_closure(["mean", "std"],
                                                      e.scheme, stage))):
            predicted = materialized_nbytes(e, stage, region=region,
                                            closure=cl)
            actual = materialize(e, stage, region=region, closure=cl).nbytes
            assert predicted == actual, (stage, region)


# -- serving by field id ------------------------------------------------------

def test_serve_resolves_field_ids_one_dispatch_per_group(field_2d):
    cs = _compress_many(hszx_nd, 3)
    store = FieldStore()
    for i, c in enumerate(cs):
        store.put(f"fields/{i}", c)
    fe = AnalyticsFrontend(store=store)
    for i in range(3):
        fe.add_request(AnalyticsRequest(uid=i, fields=f"fields/{i}",
                                        op=["mean", "std"]))
    done = {r.uid: r for r in fe.run_until_drained()}
    assert all(r.error is None for r in done.values())
    assert fe.engine.cache_size == 1     # the whole id group: one program
    stage = done[0].result_stage["mean"]
    import jax
    refs = {op: jax.jit(lambda f, o=op: getattr(H, o)(f, stage))
            for op in ("mean", "std")}
    for i in range(3):
        _assert_same(done[i].result["mean"], refs["mean"](cs[i]))
        _assert_same(done[i].result["std"], refs["std"](cs[i]))
    # second round is served from resident materializations
    h0 = store.stats.hits
    fe.add_request(AnalyticsRequest(uid=9, fields="fields/0", op=["mean", "std"]))
    done = fe.run_until_drained()
    assert done[0].error is None and store.stats.hits > h0


def test_serve_vector_ids_and_rejections(field_2d, vector_field_2d):
    u, v = vector_field_2d
    store = FieldStore()
    store.put("u", _c(hszp_nd, u))
    store.put("v", _c(hszp_nd, v))
    fe = AnalyticsFrontend(store=store)
    fe.add_request(AnalyticsRequest(uid=0, fields=("u", "v"), op="curl"))
    fe.add_request(AnalyticsRequest(uid=1, fields="ghost", op="mean"))
    done = {r.uid: r for r in fe.run_until_drained()}
    assert done[0].error is None
    import jax
    ref = jax.jit(lambda a, b: H.curl([a, b], done[0].result_stage))
    _assert_same(done[0].result, ref(store.get("u"), store.get("v")))
    assert done[1].error is not None and "ghost" in done[1].error


def test_serve_ids_without_store_rejected(field_2d):
    fe = AnalyticsFrontend()             # no store attached
    fe.add_request(AnalyticsRequest(uid=0, fields="some/id", op="mean"))
    (r,) = fe.run_until_drained()
    assert r.error is not None and "store" in r.error


# -- CostModel persistence ----------------------------------------------------

def test_cost_model_save_load_roundtrip(tmp_path):
    cm = CostModel()
    cm.record(Scheme.HSZP_ND, "mean", Stage.P, 100.0)
    cm.record(Scheme.HSZP_ND, "mean", Stage.P, 200.0)   # running mean: 150
    cm.record(Scheme.HSZX, "std", Stage.Q, 42.0)
    cm.record_reconstruction(Scheme.HSZP_ND, Stage.Q, 80.0)
    path = tmp_path / "cost.json"
    cm.save(path)
    loaded = CostModel.load(path)
    assert loaded.table == cm.table
    assert loaded.recon == cm.recon
    assert loaded._counts == cm._counts
    # counts round-trip => post-load observations continue the same mean
    loaded.record(Scheme.HSZP_ND, "mean", Stage.P, 300.0)
    cm.record(Scheme.HSZP_ND, "mean", Stage.P, 300.0)
    assert loaded.table == cm.table
    # and the loaded model plans identically
    assert analytics.plan_stages(
        Scheme.HSZP_ND, ["mean"], cost_model=loaded).fused == Stage.P


def test_cost_model_load_rejects_foreign_json(tmp_path):
    path = tmp_path / "bogus.json"
    path.write_text('{"format": "something-else", "cells": []}')
    with pytest.raises(ValueError, match="not a hsz-cost-model"):
        CostModel.load(path)


def test_cost_model_calibrates_reconstruction_from_fig34_rows():
    csv = "\n".join([
        "name,us_per_call,derived",
        "fig34/Ocean/hszp_nd-q,80.0,GBps=1.0",
        "fig34/NYX/hszp_nd-q,120.0,GBps=1.0",
        "fig34/Ocean/hszp_nd-f,500.0,GBps=1.0",
        "fig58/Ocean/mean/hszp_nd-q,130.0,GBps=1.0",
        "fig58/Ocean/mean/hszp_nd-p,90.0,GBps=1.0",
        "fig58/Ocean/mean/hszp_nd-f,700.0,GBps=1.0",
    ])
    cm = CostModel.from_benchmark_csv(csv)
    assert cm.reconstruction(Scheme.HSZP_ND, Stage.Q) == 100.0  # mean of 2
    assert cm.reconstruction(Scheme.HSZP_ND, Stage.M) == 0.0
    assert cm.cost(Scheme.HSZP_ND, "mean", Stage.Q) == 130.0
    assert cm.cost(Scheme.HSZP_ND, "mean", Stage.Q, cached=True) == 30.0
    # cold: P (90 < 130); Q resident: 30 < 90 -> flip
    stages = (Stage.P, Stage.Q, Stage.F)
    assert cm.cheapest(Scheme.HSZP_ND, "mean", stages) == Stage.P
    assert cm.cheapest(Scheme.HSZP_ND, "mean", stages,
                       cached={Stage.Q}) == Stage.Q


# -- byte-accounting audit (ISSUE 5 satellite) --------------------------------

def _bytes_consistent(store):
    assert store.cache_bytes_in_use == sum(
        m.nbytes for m in store._cache.values())
    assert store.cache_bytes_in_use <= max(store.cache_bytes, 0) or (
        store.cache_entries == 1)


def test_byte_accounting_replay_put_replace_evict(field_2d):
    """Replay put/replace/evict sequences; after every step the byte counter
    must equal the sum of resident nbytes (no double-subtraction on the
    replace-with-eviction path, no self-eviction of the fresh entry)."""
    c1 = _c(hszx_nd, field_2d)
    c2 = _c(hszx_nd, field_2d * 2.0)
    one = materialize(c1, Stage.Q).nbytes
    store = FieldStore(cache_bytes=int(2.2 * one))
    for i in range(3):
        store.put(f"f{i}", c1)
    store.ensure("f0", Stage.Q); _bytes_consistent(store)
    store.ensure("f1", Stage.Q); _bytes_consistent(store)
    store.ensure("f2", Stage.Q); _bytes_consistent(store)   # evicts f0
    assert store.stats.evictions == 1
    # replace under pressure: invalidate + re-materialize
    store.put("f1", c2, replace=True); _bytes_consistent(store)
    store.ensure("f1", Stage.Q); _bytes_consistent(store)
    # same-key replace (the streaming summary refresh path)
    m = materialize(c2, Stage.Q)
    key = next(iter(store._cache))
    store._insert(key, m); _bytes_consistent(store)
    assert store._cache[key] is m                     # replaced in place
    # the just-inserted entry is never its own victim even at a tight budget
    small = FieldStore(cache_bytes=one)
    small._insert(("a",), materialize(c1, Stage.Q)); _bytes_consistent(small)
    small._insert(("b",), materialize(c2, Stage.Q)); _bytes_consistent(small)
    assert list(k[0] for k in small._cache) == ["b"]  # a evicted, b resident


def test_oversized_replacement_drops_stale_entry(field_2d):
    """Replacing a resident cell with a value too large to retain must not
    leave the *stale* old value serving hits (fatal for streaming summaries,
    which are replaced on every append)."""
    c = _c(hszx_nd, field_2d)
    m_small = materialize(c, Stage.Q)
    store = FieldStore(cache_bytes=2 * m_small.nbytes)
    key = ("x", Stage.Q, None, "cover")
    store._insert(key, m_small)
    assert key in store._cache

    class Oversized:
        nbytes = 10 * m_small.nbytes

    store._insert(key, Oversized())
    assert key not in store._cache        # stale entry gone, nothing resident
    assert store.cache_bytes_in_use == 0
    assert store.stats.rejected == 1 and store.stats.evictions == 1
    _bytes_consistent(store)


# -- cached-stage planning: infeasible intersections (ISSUE 5 satellite) ------

@pytest.mark.parametrize("comp", ALL, ids=lambda c: c.scheme.value)
def test_plan_stages_cached_outside_feasible_intersection(comp, field_2d):
    """A resident stage-② materialization under a gradient-bearing op set:
    for 1-D schemes the cached stage is outside the set's feasible
    intersection — planning must fall back to the cold choice (not raise,
    not price the infeasible stage), and store-backed queries must still
    answer bit-identically."""
    scheme = comp.scheme
    cold = analytics.plan_stages(scheme, ["mean", "gradient"])
    plan = analytics.plan_stages(scheme, ["mean", "gradient"],
                                 cached=frozenset({Stage.P}))
    if Stage.P in analytics.feasible_stages(scheme, "gradient"):
        assert plan.fused == Stage.P      # nd: resident stage serves the set
    else:
        assert plan.fused == cold.fused   # 1-D: clean cold fallback
    # calibrated: the discount must only ever apply inside the intersection
    cm = CostModel()
    for op in ("mean", "gradient"):
        for s in analytics.feasible_stages(scheme, op):
            cm.record(scheme, op, s, 100.0 * int(s))
        cm.record_reconstruction(scheme, Stage.Q, 50.0)
    plan_cal = analytics.plan_stages(scheme, ["mean", "gradient"],
                                     cost_model=cm,
                                     cached=frozenset({Stage.P}))
    for op, s in plan_cal.stages:
        assert s in analytics.feasible_stages(scheme, op)
    # end to end through the store
    c = _c(comp, field_2d)
    store = FieldStore()
    store.put("f", c)
    store.ensure("f", Stage.P)
    eng = BatchedAnalytics()
    got = query(["f"], ["mean", "gradient"], store=store, engine=eng)
    ref = query([c], ["mean", "gradient"],
                stage={op: s for op, s in
                       zip(("mean", "gradient"),
                           (got.stages[0]["mean"], got.stages[0]["gradient"]))}
                if got.stages[0]["mean"] != got.stages[0]["gradient"]
                else got.stages[0]["mean"], engine=eng)
    _assert_same(got.values[0]["mean"], ref.values[0]["mean"])
    _assert_same(got.values[0]["gradient"], ref.values[0]["gradient"])


# -- CostModel.load: older / hand-stripped payloads (ISSUE 5 satellite) -------

def test_cost_model_load_tolerates_stripped_payload(tmp_path):
    import json
    cm = CostModel()
    cm.record(Scheme.HSZP_ND, "mean", Stage.P, 100.0)
    cm.record(Scheme.HSZX, "std", Stage.Q, 42.0)
    cm.record_reconstruction(Scheme.HSZP_ND, Stage.Q, 80.0)
    path = tmp_path / "cost.json"
    cm.save(path)
    data = json.loads(path.read_text())
    del data["recon"]                       # older version: no recon table
    for cell in data["cells"]:
        cell.pop("count", None)             # no observation counts
    data["cells"].append({"scheme": "hszp_nd", "op": "std"})  # stripped cell
    path.write_text(json.dumps(data))
    with pytest.warns(UserWarning, match="skipped 1 malformed"):
        loaded = CostModel.load(path)
    # intact cells round-trip; the stripped cell and recon fall back to the
    # uncalibrated path instead of KeyError
    assert loaded.table[(Scheme.HSZP_ND, "mean", Stage.P)] == 100.0
    assert loaded.table[(Scheme.HSZX, "std", Stage.Q)] == 42.0
    assert loaded.recon == {}
    assert loaded.cost(Scheme.HSZP_ND, "std", Stage.Q) is None
    assert loaded.reconstruction(Scheme.HSZP_ND, Stage.Q) is None
    # counts default to 1, so post-load recording still averages sanely
    loaded.record(Scheme.HSZP_ND, "mean", Stage.P, 300.0)
    assert loaded.table[(Scheme.HSZP_ND, "mean", Stage.P)] == 200.0
    # and planning with the degraded model works (uncalibrated fallback)
    assert analytics.plan_stages(Scheme.HSZP_ND, ["mean", "std"],
                                 cost_model=loaded).fused == Stage.P


# -- serve-by-id per-request isolation (ISSUE 5 satellite) --------------------

def test_serve_per_request_isolation_and_cache_hygiene(field_2d, vector_field_2d):
    """Malformed requests — duplicate component ids, empty op list, region
    out of bounds — reject individually with a structured error; healthy
    requests in the same batch are served, and nothing poisons the engine's
    jit cache (subsequent identical queries still answer)."""
    u, v = vector_field_2d
    store = FieldStore()
    store.put("u", _c(hszp_nd, u))
    store.put("v", _c(hszp_nd, v))
    store.put("f", _c(hszp_nd, field_2d))
    fe = AnalyticsFrontend(store=store)
    fe.add_request(AnalyticsRequest(uid=0, fields=("u", "u"), op="curl"))
    fe.add_request(AnalyticsRequest(uid=1, fields="f", op=[]))
    fe.add_request(AnalyticsRequest(uid=2, fields="f", op="mean",
                                    region=((0, 5000), (0, 10))))
    fe.add_request(AnalyticsRequest(uid=3, fields=("u", "v"), op="curl"))
    fe.add_request(AnalyticsRequest(uid=4, fields="f", op=["mean", "std"]))
    done = {r.uid: r for r in fe.run_until_drained()}
    assert "duplicate field ids" in done[0].error
    assert "empty op set" in done[1].error
    assert "out of bounds" in done[2].error
    assert done[3].error is None and done[4].error is None
    n = fe.engine.cache_size
    # the rejected shapes left no poisoned programs: replaying the healthy
    # requests compiles nothing new and answers identically
    fe.add_request(AnalyticsRequest(uid=5, fields=("u", "v"), op="curl"))
    fe.add_request(AnalyticsRequest(uid=6, fields="f", op=["mean", "std"]))
    done2 = {r.uid: r for r in fe.run_until_drained()}
    assert fe.engine.cache_size == n
    assert done2[5].error is None and done2[6].error is None
    _assert_same(done2[5].result, done[3].result)
    for op in ("mean", "std"):
        _assert_same(done2[6].result[op], done[4].result[op])


def test_query_rejects_duplicate_vector_ids_but_allows_raw_duplicates(field_2d):
    store = FieldStore()
    store.put("u", _c(hszp_nd, field_2d))
    with pytest.raises(ValueError, match="duplicate field ids"):
        query([("u", "u")], "curl", stage=Stage.Q, store=store)
    # raw containers carry no identity: physical duplication stays legal
    c = _c(hszp_nd, field_2d)
    res = query([(c, c)], "curl", stage=Stage.Q)
    assert np.isfinite(np.asarray(res.values[0])).all()


# -- store-backed auto planning: no recorrelation over a resident stage -------

STATS_LAP = ("mean", "std", "laplacian")


def _auto_stages(path, store, ids, ops):
    """Planned stage of every (field, op) answer of one store-backed
    ``stage="auto"`` request, through the expression query, the flat
    (deprecated) query, or the serving frontend."""
    roots = [expr.op(op, f) for f in ids for op in ops]
    if path == "exprs":
        res = query(exprs=roots, store=store)
        return res.stages, res.values
    if path == "flat":
        with pytest.warns(DeprecationWarning):
            res = query(list(ids), list(ops), store=store)
        return ([st[op] for st in res.stages for op in ops],
                [v[op] for v in res.values for op in ops])
    fe = AnalyticsFrontend(store=store)
    req = AnalyticsRequest(uid=0, exprs=roots, stage="auto")
    fe.add_request(req)
    fe.run_until_drained()
    assert req.error is None, req.error
    return list(req.result_stage), list(req.result)


@pytest.mark.parametrize("path", ["exprs", "flat", "frontend"])
def test_auto_plans_stage_without_recorrelation(path, field_3d):
    """3-D Lorenzo {mean, std, laplacian}: cold, the store-backed plan is
    stage ③ (stage-② std and laplacian would run prefix sums over the
    resident residuals every query), and it stays ③ once ③ is resident.
    std and laplacian are bit-identical to an explicit stage-② query; the
    mean differs from ②'s weighted residual sum by f32 summation order."""
    c = _c(hszp_nd, field_3d)
    store = FieldStore()
    store.put("f", c)
    ref = query(exprs=[expr.op(op, c) for op in STATS_LAP], stage=Stage.P)
    for _ in range(3):
        stages, values = _auto_stages(path, store, ["f"], STATS_LAP)
        assert stages == [Stage.Q] * 3
        got = dict(zip(STATS_LAP, values))
        _assert_same(got["std"], ref.values[1])
        _assert_same(got["laplacian"], ref.values[2])
        np.testing.assert_allclose(np.asarray(got["mean"]),
                                   np.asarray(ref.values[0]), rtol=1e-6)
    assert store.stats.misses == 1 and store.stats.hits == 2
    assert store.is_resident("f", Stage.Q)
    assert not store.is_resident("f", Stage.P)


@pytest.mark.parametrize("comp,ops,want", [
    (hszx_nd, ("mean", "std"), Stage.P),   # blockmean ② needs no prefix sum
    (hszx_nd, ("mean",), Stage.M),         # metadata stays the fast path
    (hszp_nd, ("mean",), Stage.P),         # ② and ③ tie; stage order keeps ②
], ids=["hszx_nd-mean+std", "hszx_nd-mean", "hszp_nd-mean"])
@pytest.mark.parametrize("path", ["exprs", "flat"])
def test_auto_keeps_cold_stage_without_recorrelation(comp, ops, want, path,
                                                     field_3d):
    store = FieldStore()
    store.put("f", _c(comp, field_3d))
    p0 = obs.counters["plan_resident_promotions"]
    for _ in range(2):
        stages, _ = _auto_stages(path, store, ["f"], ops)
        assert stages == [want] * len(ops)
    assert obs.counters["plan_resident_promotions"] == p0


@pytest.mark.parametrize("path", ["exprs", "flat"])
def test_auto_keeps_cold_stage_when_budget_cannot_retain(path, field_3d):
    """A budget that cannot hold the stage-③ plane (so not the larger
    stage-② plane either) keeps today's plan: stage ②, run unseeded."""
    c = _c(hszp_nd, field_3d)
    assert materialized_nbytes(c, Stage.P) > materialized_nbytes(c, Stage.Q)
    store = FieldStore(cache_bytes=materialized_nbytes(c, Stage.Q) - 1)
    store.put("f", c)
    assert not store.can_retain("f", Stage.Q)
    p0 = obs.counters["plan_resident_promotions"]
    for _ in range(2):
        stages, _ = _auto_stages(path, store, ["f"], STATS_LAP)
        assert stages == [Stage.P] * 3
    assert obs.counters["plan_resident_promotions"] == p0
    assert store.cache_entries == 0 and store.stats.rejected == 2


@pytest.mark.parametrize("ops", [("derivative",), ("gradient",),
                                 ("laplacian",), ("derivative", "laplacian")])
@pytest.mark.parametrize("mode,want", [("interpret", Stage.P),
                                       ("off", Stage.Q)])
def test_auto_2d_stencil_follows_the_selected_rule(ops, mode, want,
                                                   field_2d):
    """2-D Lorenzo stencils: with the kernels on, the fused stage-② rules
    recorrelate in VMEM over the resident residuals while the fused
    stage-③ derivative and gradient rules would decode the payload past a
    resident ③ plane, so the plan stays at ② (the storeless stage) and
    nothing is promoted; with the kernels off the XLA ② rules run prefix
    sums, and the plan goes to ③."""
    store = FieldStore()
    store.put("f", _c(hszp_nd, field_2d))
    with kops.override_mode(mode):
        p0 = obs.counters["plan_resident_promotions"]
        for _ in range(2):
            stages, _ = _auto_stages("exprs", store, ["f"], ops)
            assert stages == [want] * len(ops)
        assert (obs.counters["plan_resident_promotions"] - p0
                == (2 if want == Stage.Q else 0))
    assert store.is_resident(
        "f", want, closure=oplib.set_closure(ops, hszp_nd.scheme, want))


def test_plan_resident_promotions_counts_promoted_components(field_3d):
    """One count per planned component above the storeless stage: two
    store-backed Lorenzo stat+stencil components are promoted (cold and
    warm); a blockmean component, a Lorenzo mean, a raw (storeless) leaf
    and an explicit-stage query are not."""
    rng = np.random.default_rng(5)
    store = FieldStore()
    for i in range(2):
        store.put(f"p{i}", _c(hszp_nd, field_3d + rng.normal(
            0, 0.1, field_3d.shape).astype(np.float32)))
    store.put("x", _c(hszx_nd, field_3d))
    store.put("m", _c(hszp_nd, field_3d))
    raw = _c(hszp_nd, field_3d)
    roots = ([expr.op(op, f"p{i}") for i in range(2) for op in STATS_LAP]
             + [expr.mean("x"), expr.std("x"), expr.mean("m"),
                expr.std(raw), expr.laplacian(raw)])
    for _ in range(2):
        p0 = obs.counters["plan_resident_promotions"]
        res = query(exprs=roots, store=store)
        assert obs.counters["plan_resident_promotions"] == p0 + 2
        assert res.stages == [Stage.Q] * 6 + [Stage.P] * 5
    p0 = obs.counters["plan_resident_promotions"]
    query(exprs=roots, store=store, stage=Stage.Q)
    assert obs.counters["plan_resident_promotions"] == p0


def _plane_cumsums(jaxpr, n: int):
    """Prefix sums over a whole plane (an operand of ``n`` elements or
    more) anywhere in a jaxpr, sub-jaxprs included; the fused kernels'
    band-boundary prefix sums are far smaller."""
    for eqn in jaxpr.eqns:
        if (eqn.primitive.name == "cumsum"
                and eqn.invars[0].aval.size >= n):
            yield eqn
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _plane_cumsums(sub, n)


def _check_reads_seed(name, fam, stage, x):
    """Trace the rule ``compute`` selects for each ``fam`` scheme over the
    stage's resident materialization and hold ``oplib.reads_seed`` to it:
    the rule reads the seed (its arrays are live in the jaxpr) and runs no
    prefix sum over a whole plane exactly where declared."""
    spec = oplib.OPS[name]
    for comp in _FAMILY_SCHEMES[fam]:
        if stage not in spec.feasible(comp.scheme):
            continue
        scheme = comp.scheme
        if spec.arity == "vector":
            comps = [_c(comp, x * (k + 1)) for k in range(x.ndim)]
            closures = oplib.component_closures(name, [scheme] * x.ndim,
                                                stage)

            def run(seeds, comps=comps, closures=closures):
                return spec.lower_vector(
                    [oplib.StageContext(c, stage, None, cl, seed=m)
                     for c, cl, m in zip(comps, closures, seeds)], 0)
            seeds = [materialize(c, stage) for c in comps]
            declared = {oplib.reads_seed(name, c, stage, closure=cl)
                        for c, cl in zip(comps, closures)}
            (declared,) = declared
        else:
            c = _c(comp, x)
            closure = oplib.set_closure(name, scheme, stage, axis=1)

            def run(seed, c=c, closure=closure):
                ctx = oplib.StageContext(c, stage, None, closure, seed=seed)
                return oplib.select_rule(spec, stage, oplib.family_of(scheme),
                                         ctx)(ctx, 1)
            # stage ① has nothing to materialize: metadata is resident
            seeds = None if stage == Stage.M else materialize(c, stage)
            declared = oplib.reads_seed(name, c, stage, closure=closure)
        jaxpr = jax.make_jaxpr(run)(seeds).jaxpr
        used = {v for v in (*(v for e in jaxpr.eqns for v in e.invars),
                            *jaxpr.outvars) if isinstance(v, Var)}
        reads = not jaxpr.invars or any(v in used for v in jaxpr.invars)
        plane = x.size
        assert declared == (reads and not any(_plane_cumsums(jaxpr, plane))
                            ), scheme


_FAMILY_SCHEMES = {"lorenzo": (hszp, hszp_nd), "blockmean": (hszx, hszx_nd)}
_RULE_CELLS = [(name, fam, stage) for name in oplib.OPS
               for fam in _FAMILY_SCHEMES for stage in Stage
               if any(stage in oplib.OPS[name].feasible(comp.scheme)
                      for comp in _FAMILY_SCHEMES[fam])]
_RULE_IDS = [f"{n}-{f}-{s.name}" for n, f, s in _RULE_CELLS]


@pytest.mark.parametrize("name,fam,stage", _RULE_CELLS, ids=_RULE_IDS)
def test_recorrelates_matches_rule_jaxpr(name, fam, stage, field_3d):
    """The registry's seed fact, which the store-backed planner ranks by,
    is what each XLA lowering rule does (a 3-D field: no fused kernel
    covers it, and every axis difference needs prefix sums): a rule in
    ``OpSpec.recorrelates`` runs prefix sums over the resident plane."""
    _check_reads_seed(name, fam, stage, field_3d)


@pytest.mark.parametrize("name,fam,stage", _RULE_CELLS, ids=_RULE_IDS)
def test_reads_seed_matches_fused_rule_jaxpr(name, fam, stage, field_2d):
    """The same fact on a 2-D field with the kernels on, where the fused
    Pallas rules run: stage-② kernels recorrelate in VMEM over the
    resident residuals (no plane prefix sum), and the stage-③④ derivative
    and gradient kernels ignore a stage-③ seed (``FusedRule.reads_seed``)."""
    with kops.override_mode("interpret"):
        _check_reads_seed(name, fam, stage, field_2d)
