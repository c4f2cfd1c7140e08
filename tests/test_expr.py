"""Expression-DAG query compiler (repro.core.expr + the query/serve surface).

Bit-identity contract: lowering an expression shares ONE stage
reconstruction per distinct leaf, and every root's value equals composing
the corresponding single-op results (``oplib.compute``) with the same
pointwise arithmetic at the same stage — exactly, not approximately
(IEEE adds/subs/scales of identical inputs are deterministic).  Oracles
with closed forms (rigid rotation, quadratic ensembles) additionally pin
the absolute values.
"""
import warnings

import numpy as np
import pytest
import jax.numpy as jnp

try:
    from hypothesis import given, strategies as st
except ImportError:  # optional dep: property-based tests self-skip
    from repro.testing import given, st

from repro.core import Stage, expr, hszp, hszp_nd, hszx, hszx_nd, oplib
from repro.analytics import ExprPlan, plan_expr, query
from repro.analytics.engine import BatchedAnalytics
from repro.analytics.query import _query_opset
from repro.store import FieldStore

ALL = [hszp, hszx, hszp_nd, hszx_nd]
ND = [hszp_nd, hszx_nd]

N0, N1 = 48, 64
REGION = (slice(8, 40), slice(16, 48))


def _grid_2d():
    i = np.arange(N0, dtype=np.float32)[:, None]
    j = np.arange(N1, dtype=np.float32)[None, :]
    return i, j


def _compress(comp, data):
    # abs_eb=0.25 => q = 2*d exactly for integer-valued fields
    return comp.compress(jnp.asarray(data, jnp.float32), abs_eb=0.25)


def _stages(comp):
    return [Stage.Q, Stage.F] + ([Stage.P] if comp.scheme.is_nd else [])


def _op(c, name, stage, *, axis=0, region=None):
    return np.asarray(oplib.compute(c, name, stage, axis=axis,
                                    region=region)[name])


# ===========================================================================
# closed-form oracles
# ===========================================================================

@pytest.mark.parametrize("comp", ALL, ids=lambda c: c.scheme.value)
def test_vorticity_rigid_rotation_exact(comp):
    """vorticity = dv/dx - du/dy of (u, v) = (-y, x) is exactly +2, and the
    expression is bit-identical to composing the single-op results."""
    i, j = _grid_2d()
    cu = _compress(comp, -(j + np.zeros((N0, N1), np.float32)))
    cv = _compress(comp, i + np.zeros((N0, N1), np.float32))
    vort = expr.sub(expr.derivative(cv, axis=0), expr.derivative(cu, axis=1))
    for stage in _stages(comp):
        got = np.asarray(oplib.compute_exprs(vort, stage))
        oracle = (_op(cv, "derivative", stage, axis=0)
                  - _op(cu, "derivative", stage, axis=1))
        np.testing.assert_array_equal(got, oracle)
        np.testing.assert_allclose(
            got, np.full((N0 - 2, N1 - 2), 2.0, np.float32),
            rtol=1e-5, atol=1e-3)


@pytest.mark.parametrize("comp", ALL, ids=lambda c: c.scheme.value)
def test_ensemble_delta_quadratics_exact(comp):
    """laplacian(2(i²+j²)) - laplacian(i²+j²) is exactly 8 - 4 = 4."""
    i, j = _grid_2d()
    f = i * i + j * j
    c1 = _compress(comp, 2.0 * f)
    c2 = _compress(comp, f)
    delta = expr.laplacian(c1) - expr.laplacian(c2)
    for stage in _stages(comp):
        got = np.asarray(oplib.compute_exprs(delta, stage))
        oracle = _op(c1, "laplacian", stage) - _op(c2, "laplacian", stage)
        np.testing.assert_array_equal(got, oracle)
        np.testing.assert_allclose(
            got, np.full((N0 - 2, N1 - 2), 4.0, np.float32),
            rtol=1e-5, atol=1e-3)


# ===========================================================================
# expression == op-compose, all schemes, ± region, ± store seeding
# ===========================================================================

@pytest.mark.parametrize("comp", ALL, ids=lambda c: c.scheme.value)
@pytest.mark.parametrize("region", [None, REGION],
                         ids=["full", "region"])
def test_expression_matches_compose(comp, region, field_2d):
    """A mixed DAG (stencil + scaled statistics, shared leaf) equals the
    composed single-op results bit-for-bit at every feasible stage."""
    c1 = comp.compress(jnp.asarray(field_2d[:N0, :N1]), rel_eb=1e-3)
    c2 = comp.compress(jnp.asarray(field_2d[50:50 + N0, 20:20 + N1]),
                       rel_eb=1e-3)
    e = (expr.laplacian(c1) + 0.5 * expr.mean(c2)) - expr.std(c1)
    for stage in _stages(comp):
        got = np.asarray(oplib.compute_exprs(e, stage, region=region))
        oracle = (_op(c1, "laplacian", stage, region=region)
                  + 0.5 * _op(c2, "mean", stage, region=region)
                  - _op(c1, "std", stage, region=region))
        np.testing.assert_array_equal(got, oracle)


@pytest.mark.parametrize("comp", ND, ids=lambda c: c.scheme.value)
@pytest.mark.parametrize("region", [None, REGION], ids=["full", "region"])
def test_store_seeded_expression_bit_identical(comp, region, field_2d):
    """Store-backed id leaves: the warm (seeded) run returns bit-identical
    values to the cold run, and planning sees the residency."""
    store = FieldStore(cache_bytes=1 << 30)
    store.put("u", comp.compress(jnp.asarray(field_2d[:N0, :N1]),
                                 rel_eb=1e-3))
    store.put("v", comp.compress(jnp.asarray(field_2d[40:40 + N0, 10:10 + N1]),
                                 rel_eb=1e-3))
    vort = expr.sub(expr.derivative("v", axis=0), expr.derivative("u", axis=1))
    engine = BatchedAnalytics()
    cold = query(exprs=[vort], store=store, region=region, engine=engine)
    warm = query(exprs=[vort], store=store, region=region, engine=engine)
    np.testing.assert_array_equal(np.asarray(cold.values[0]),
                                  np.asarray(warm.values[0]))
    assert warm.store_hits >= 2 and warm.store_misses == 0
    assert store.is_resident("u", cold.stages[0], region=region,
                             closure=expr.leaf_closure(
                                 expr.analyze([vort]), 1,
                                 comp.scheme, cold.stages[0]))
    # and both agree with the storeless (eager) lowering at the planned
    # stage — allclose, not equal: XLA fuses the jitted program differently
    # from the eager trace (the seeded/unseeded runs above ARE bit-equal)
    ref = oplib.compute_exprs(
        expr.sub(expr.derivative(store.get("v"), axis=0),
                 expr.derivative(store.get("u"), axis=1)),
        cold.stages[0], region=region)
    np.testing.assert_allclose(np.asarray(cold.values[0]), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)


# ===========================================================================
# shared prelude: exactly one StageContext (stage reconstruction) per leaf
# ===========================================================================

def test_shared_prelude_one_context_per_leaf(monkeypatch, field_2d):
    """Five consumers over two leaves build exactly two StageContexts, and
    the whole DAG is one compiled dispatch."""
    c1 = hszp_nd.compress(jnp.asarray(field_2d[:N0, :N1]), rel_eb=1e-3)
    c2 = hszp_nd.compress(jnp.asarray(field_2d[60:60 + N0, 5:5 + N1]),
                          rel_eb=1e-3)
    built = []
    real = oplib.StageContext

    class Counting(real):
        def __init__(self, *a, **k):
            built.append(1)
            super().__init__(*a, **k)

    monkeypatch.setattr(oplib, "StageContext", Counting)
    e1 = expr.laplacian(c1) - expr.scale(expr.mean(c1), 2.0)
    e2 = expr.std(c1) + expr.laplacian(c2)
    out = oplib.compute_exprs([e1, e2], Stage.Q)
    assert len(built) == 2  # two distinct leaves, five op applications
    oracle = oplib.compute(c1, ["laplacian", "mean", "std"], Stage.Q)
    oracle2 = oplib.compute(c2, "laplacian", Stage.Q)
    np.testing.assert_array_equal(
        np.asarray(out[0]),
        np.asarray(oracle["laplacian"]) - 2.0 * np.asarray(oracle["mean"]))
    np.testing.assert_array_equal(
        np.asarray(out[1]),
        np.asarray(oracle["std"]) + np.asarray(oracle2["laplacian"]))


def test_query_expression_single_dispatch(field_2d):
    """query(exprs=[...]) compiles and issues exactly one program for a
    multi-root spatial DAG, and reuses it on re-query."""
    c1 = hszp_nd.compress(jnp.asarray(field_2d[:N0, :N1]), rel_eb=1e-3)
    c2 = hszp_nd.compress(jnp.asarray(field_2d[30:30 + N0, 8:8 + N1]),
                          rel_eb=1e-3)
    engine = BatchedAnalytics()
    roots = [expr.laplacian(c1) - expr.laplacian(c2),
             expr.mean(c1) + expr.mean(c2)]
    res = query(exprs=roots, engine=engine)
    assert res.n_dispatches == 1 and res.n_batches == 1
    assert engine.cache_size == 1
    again = query(exprs=roots, engine=engine)
    assert engine.cache_size == 1  # same canonical program: cache hit
    for a, b in zip(res.values, again.values):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ===========================================================================
# canonicalization: CSE, commuted adds, structural keys
# ===========================================================================

def test_cse_one_postlude_per_distinct_application(field_2d):
    c = hszp_nd.compress(jnp.asarray(field_2d[:N0, :N1]), rel_eb=1e-3)
    e = expr.laplacian(c) + expr.laplacian(c)
    program = expr.analyze([e])
    assert len(program.leaves) == 1
    assert len(program.op_nodes) == 1  # identical applications deduplicate
    got = np.asarray(oplib.compute_exprs(e, Stage.Q))
    np.testing.assert_array_equal(got, 2.0 * _op(c, "laplacian", Stage.Q))


def test_add_commutes_into_one_program_key(field_2d):
    c1 = hszp_nd.compress(jnp.asarray(field_2d[:N0, :N1]), rel_eb=1e-3)
    c2 = hszp_nd.compress(jnp.asarray(field_2d[10:10 + N0, 4:4 + N1]),
                          rel_eb=1e-3)
    ab = expr.analyze([expr.add(expr.mean(c1), expr.std(c2))])
    ba = expr.analyze([expr.add(expr.std(c2), expr.mean(c1))])
    assert ab.key == ba.key  # IEEE add commutes bitwise: share the program
    s_ab = expr.analyze([expr.sub(expr.mean(c1), expr.std(c2))])
    s_ba = expr.analyze([expr.sub(expr.std(c2), expr.mean(c1))])
    assert s_ab.key != s_ba.key  # sub does not


# ===========================================================================
# joint DAG planning
# ===========================================================================

def test_plan_expr_joint_intersection(field_2d):
    """A component joining a stencil (②③④ on nd) with a mean picks one
    stage feasible for both; independent components plan independently."""
    nd = hszp_nd.compress(jnp.asarray(field_2d[:N0, :N1]), rel_eb=1e-3)
    flat = hszp.compress(jnp.asarray(field_2d[:N0, :N1]), rel_eb=1e-3)
    joined = expr.laplacian(nd) + expr.mean(nd)
    alone = expr.mean(flat)
    program = expr.analyze([joined, expr.add(alone, alone)])
    plan = plan_expr(program, [nd, flat])
    assert isinstance(plan, ExprPlan) and len(plan.stages) == 2
    s_joined = plan.stages[program.root_component[0]]
    assert s_joined in (Stage.P, Stage.Q, Stage.F)  # never ① (stencil)
    # the 1-D-partitioned scheme forbids stage ② stencils — but a lone mean
    # may run anywhere; explicit infeasible stages still raise end-to-end
    with pytest.raises(Exception, match="stencil|stage"):
        oplib.compute_exprs(expr.laplacian(flat), Stage.P)


def test_plan_expr_explicit_stage_validates(field_2d):
    c = hszp.compress(jnp.asarray(field_2d[:N0, :N1]), rel_eb=1e-3)
    program = expr.analyze([expr.laplacian(c) + expr.mean(c)])
    with pytest.raises(Exception):
        plan_expr(program, [c], stage=Stage.P)  # flat scheme: no ② stencils
    plan = plan_expr(program, [c], stage=Stage.Q)
    assert plan.stages == (Stage.Q,)


# ===========================================================================
# validation errors
# ===========================================================================

def test_bare_leaf_root_rejected(field_2d):
    c = hszp_nd.compress(jnp.asarray(field_2d[:N0, :N1]), rel_eb=1e-3)
    with pytest.raises(TypeError, match="bare leaf"):
        expr.analyze([expr.leaf(c)])


def test_op_on_op_rejected(field_2d):
    c = hszp_nd.compress(jnp.asarray(field_2d[:N0, :N1]), rel_eb=1e-3)
    with pytest.raises(TypeError, match="add/sub/scale"):
        expr.op("mean", expr.laplacian(c))


def test_cycle_detected(field_2d):
    c = hszp_nd.compress(jnp.asarray(field_2d[:N0, :N1]), rel_eb=1e-3)
    a = expr.mean(c) + expr.std(c)
    b = expr.scale(a, 2.0)
    object.__setattr__(a, "a", b)  # forge a cycle past immutability
    with pytest.raises(ValueError, match="cycle"):
        expr.analyze([b])


def test_duplicate_bundle_ids_rejected():
    with pytest.raises(ValueError, match="duplicate"):
        expr.divergence(("u", "u"))


def test_mixed_temporal_spatial_consumers_rejected():
    with pytest.raises(TypeError, match="temporal"):
        expr.analyze([expr.add(expr.tmean("s"), expr.mean("s"))])


def test_unknown_op_and_bad_scale(field_2d):
    c = hszp_nd.compress(jnp.asarray(field_2d[:N0, :N1]), rel_eb=1e-3)
    with pytest.raises(ValueError, match="unknown"):
        expr.op("median", c)
    with pytest.raises(TypeError):
        expr.scale(expr.mean(c), True)


def test_shape_mismatch_rejected(field_2d):
    c1 = hszp_nd.compress(jnp.asarray(field_2d[:N0, :N1]), rel_eb=1e-3)
    c2 = hszp_nd.compress(jnp.asarray(field_2d[:32, :32]), rel_eb=1e-3)
    e = expr.laplacian(c1) + expr.laplacian(c2)
    with pytest.raises(ValueError, match="shapes"):
        oplib.compute_exprs(e, Stage.Q)


# ===========================================================================
# registry hygiene (satellite): collision guard + arity-naming errors
# ===========================================================================

def test_register_op_collision_guard():
    spec = oplib.OpSpec("mean", "field", "statistic",
                        lambda s: (Stage.Q, Stage.F))
    with pytest.raises(ValueError, match="collision.*mean"):
        oplib.register_op(spec)


def test_mixed_arity_error_names_offenders():
    with pytest.raises(ValueError) as ei:
        oplib.canonical_ops(["mean", "tdelta"])
    msg = str(ei.value)
    assert "different arities" in msg
    assert "mean (field)" in msg and "tdelta (temporal)" in msg


# ===========================================================================
# deprecation shims (satellite): old spellings warn, stay bit-identical
# ===========================================================================

def test_query_op_spelling_deprecated_but_identical(field_2d):
    c = hszp_nd.compress(jnp.asarray(field_2d[:N0, :N1]), rel_eb=1e-3)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        old = query([c], "mean")
    assert any(issubclass(x.category, DeprecationWarning) for x in w)
    ref = _query_opset([c], "mean")
    np.testing.assert_array_equal(np.asarray(old.values[0]),
                                  np.asarray(ref.values[0]))
    assert (old.n_batches, old.n_dispatches) == (ref.n_batches,
                                                 ref.n_dispatches)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        alias = query([c], ops=["mean", "std"])
    assert any(issubclass(x.category, DeprecationWarning) for x in w)
    ref2 = _query_opset([c], ["mean", "std"])
    np.testing.assert_array_equal(np.asarray(alias.values[0]["std"]),
                                  np.asarray(ref2.values[0]["std"]))
    with pytest.raises(TypeError, match="op= or ops="):
        query([c], "mean", ops=["std"])
    with pytest.raises(TypeError, match="expression form"):
        query([c], exprs=[expr.mean(c)])


def test_serve_opset_form_deprecated(field_2d):
    from repro.serve.analytics import AnalyticsFrontend, AnalyticsRequest

    c = hszp_nd.compress(jnp.asarray(field_2d[:N0, :N1]), rel_eb=1e-3)
    fe = AnalyticsFrontend()
    fe.add_request(AnalyticsRequest(uid=0, fields=c, op=["mean", "std"]))
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        done = fe.run_until_drained()
    assert any(issubclass(x.category, DeprecationWarning) for x in w)
    assert done[0].error is None and set(done[0].result) == {"mean", "std"}


def test_serve_expression_requests(field_2d):
    from repro.serve.analytics import AnalyticsFrontend, AnalyticsRequest

    c1 = hszp_nd.compress(jnp.asarray(field_2d[:N0, :N1]), rel_eb=1e-3)
    c2 = hszp_nd.compress(jnp.asarray(field_2d[20:20 + N0, 6:6 + N1]),
                          rel_eb=1e-3)
    fe = AnalyticsFrontend()
    good = AnalyticsRequest(uid=0,
                            exprs=expr.laplacian(c1) - expr.laplacian(c2))
    multi = AnalyticsRequest(uid=1, exprs=[expr.mean(c1), expr.std(c2)])
    bad = AnalyticsRequest(uid=2, exprs=expr.leaf(c1))  # bare leaf
    for r in (good, multi, bad):
        fe.add_request(r)
    fe.run_until_drained()
    assert bad.error is not None and "leaf" in bad.error
    assert good.error is None and multi.error is None
    np.testing.assert_allclose(
        np.asarray(good.result),
        _op(c1, "laplacian", good.result_stage)
        - _op(c2, "laplacian", good.result_stage), rtol=1e-5, atol=1e-6)
    assert len(multi.result) == 2 and len(multi.result_stage) == 2


# ===========================================================================
# temporal expressions + counter parity (satellite)
# ===========================================================================

def _stream(comp, rng, slabs=3, k=4, n=24):
    from repro.stream import TemporalField

    tf = TemporalField(comp, abs_eb=0.01)
    for _ in range(slabs):
        tf.append(rng.random((k, n, n)).astype(np.float32))
    return tf


def test_temporal_expression_matches_flat():
    from repro.stream.query import query_temporal

    rng = np.random.default_rng(7)
    tf = _stream(hszp_nd, rng)
    e = expr.tmean(tf) - expr.tdelta(tf)
    res = query(exprs=[e])
    flat = query_temporal([tf], ["tmean", "tdelta"])
    np.testing.assert_array_equal(
        np.asarray(res.values[0]),
        np.asarray(flat.values[0]["tmean"])
        - np.asarray(flat.values[0]["tdelta"]))
    # one summary per stream slot even with two consumers
    assert res.n_dispatches >= 2


def test_temporal_counters_uniform_with_spatial():
    """query_temporal reports dispatch/batch accounting like the spatial
    path: n_dispatches counts compiled calls (summaries, merges,
    postludes), n_batches counts layout groups."""
    from repro.stream.query import query_temporal

    rng = np.random.default_rng(8)
    t1 = _stream(hszp_nd, rng)
    t2 = _stream(hszp_nd, rng)  # same layout: one batch group
    res = query_temporal([t1, t2], "tmean")
    assert res.n_batches == 1
    # per stream: 1 batched summarize + 2 merges + 1 postlude = 4
    assert res.n_dispatches == 8
    assert res.store_hits == 0 and res.store_misses == 0
    t3 = _stream(hszx_nd, rng)  # different scheme: second layout group
    res2 = query_temporal([t1, t3], "tmean")
    assert res2.n_batches == 2


def test_cross_stream_delta_store_backed():
    from repro.stream import StreamFieldStore, TemporalField
    from repro.stream.query import query_temporal

    rng = np.random.default_rng(9)
    store = StreamFieldStore(cache_bytes=1 << 30)
    for fid in ("a", "b"):
        store.put_temporal(fid, TemporalField(hszp_nd, abs_eb=0.01))
        for _ in range(3):
            store.append(fid, rng.random((4, 24, 24)).astype(np.float32))
    res = query(exprs=[expr.sub(expr.tmean("a"), expr.tmean("b"))],
                store=store)
    a = np.asarray(query_temporal(["a"], "tmean", store=store).values[0])
    b = np.asarray(query_temporal(["b"], "tmean", store=store).values[0])
    np.testing.assert_array_equal(np.asarray(res.values[0]), a - b)


# ===========================================================================
# property test: random small DAGs == composed single-op oracle
# ===========================================================================

_leaf_ops = st.sampled_from(["mean", "std", "laplacian"])


@st.composite
def _dags(draw):
    """A random expression tree over up to 3 leaves (by index) with up to
    depth-3 combinators; returns a spec the test folds into an Expr."""
    n_leaves = draw(st.integers(1, 3))

    def node(depth):
        if depth >= 3 or draw(st.booleans()):
            return ("op", draw(_leaf_ops), draw(st.integers(0, n_leaves - 1)))
        kind = draw(st.sampled_from(["add", "sub", "scale"]))
        if kind == "scale":
            alpha = draw(st.sampled_from([-2.0, 0.5, 1.0, 3.0]))
            return ("scale", alpha, node(depth + 1))
        return (kind, node(depth + 1), node(depth + 1))

    return n_leaves, node(0)


@given(spec=_dags())
def test_random_dag_matches_composed_oracle(spec, field_2d):
    n_leaves, tree = spec
    comps = [hszp_nd.compress(
        jnp.asarray(field_2d[o:o + 32, o:o + 32]), rel_eb=1e-3)
        for o in (0, 16, 48)][:n_leaves]

    def build(t):
        if t[0] == "op":
            return expr.op(t[1], comps[t[2]])
        if t[0] == "scale":
            return expr.scale(build(t[2]), t[1])
        return (expr.add if t[0] == "add" else expr.sub)(build(t[1]),
                                                         build(t[2]))

    def oracle(t):
        if t[0] == "op":
            return _op(comps[t[2]], t[1], Stage.Q)
        if t[0] == "scale":
            return oracle(t[2]) * np.float32(t[1])
        a, b = oracle(t[1]), oracle(t[2])
        return a + b if t[0] == "add" else a - b

    got = np.asarray(oplib.compute_exprs(build(tree), Stage.Q))
    np.testing.assert_allclose(got, oracle(tree), rtol=1e-5, atol=1e-5)
