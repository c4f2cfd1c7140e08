"""Program spans and counters of the served path (``repro.obs``).

One frontend step opens ``repro.frontend.step`` and, inside it, sibling
spans for planning, store seeding and the engine's dispatch (and build, on
a jit-cache miss); the siblings partition the step.  A store miss opens
``repro.store.materialize`` inside the seed span, with no step serial, so
the partition holds.  The spans reach both sinks: the in-memory ring and,
under ``jax.profiler``, the trace.
"""
import glob
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.analytics import BatchedAnalytics
from repro.core import Stage, UnsupportedStageError, expr, hszp_nd
from repro.serve import AnalyticsFrontend, AnalyticsRequest
from repro.store import FieldStore

ALL_SPANS = set(obs.NAMES)


def _field(seed: int):
    rng = np.random.default_rng(seed)
    data = rng.normal(0, 1, (32, 48)).astype(np.float32)
    return hszp_nd.compress(jnp.asarray(data), rel_eb=1e-3)


@pytest.fixture
def fe():
    """A frontend with a fresh engine (empty jit cache) over two ids."""
    store = FieldStore()
    for i in range(2):
        store.put(f"f/{i}", _field(i))
    return AnalyticsFrontend(store=store)


def _expr_request(uid, stage=Stage.Q):
    return AnalyticsRequest(uid=uid, exprs=[expr.mean("f/0"),
                                            expr.laplacian("f/1")],
                            stage=stage)


def _flat_request(uid):
    return AnalyticsRequest(uid=uid, fields="f/0", op="laplacian",
                            stage=Stage.Q)


def _step(fe, *reqs):
    """Serve ``reqs`` in one step: ``(finished, the step's spans)``."""
    for r in reqs:
        fe.add_request(r)
    t0 = time.perf_counter_ns()
    done = fe.step()
    return done, obs.spans(t0)


def _partition(spans):
    """Check that the step's children nest inside it, carry its serial and
    do not overlap, and that each materialization lies inside a seed span
    with no serial; returns ``(step span, children)``."""
    (step,) = [s for s in spans if s[0] == obs.FRONTEND_STEP]
    nested = [s for s in spans if s[0] == obs.STORE_MATERIALIZE]
    children = [s for s in spans
                if s[0] not in (obs.FRONTEND_STEP, obs.STORE_MATERIALIZE)]
    _, t0, t1, serial, _ = step
    assert serial is not None
    for name, c0, c1, c_step, count in children:
        assert t0 <= c0 <= c1 <= t1, name
        assert c_step == serial and count is None
    seeds = [s for s in children if s[0] == obs.STORE_SEED]
    for _, m0, m1, m_step, count in nested:
        assert m_step is None and count is None
        assert any(s0 <= m0 <= m1 <= s1 for _, s0, s1, _, _ in seeds)
    for a, b in zip(children, children[1:]):   # ordered by start
        assert a[2] <= b[1], (a[0], b[0])
    busy = sum(c1 - c0 for _, c0, c1, _, _ in children)
    self_ns = (t1 - t0) - busy
    assert self_ns >= 0 and self_ns + busy == t1 - t0
    return step, children


@pytest.mark.parametrize("make", [_expr_request, _flat_request],
                         ids=["expr", "flat"])
def test_one_step_gives_the_span_set(fe, make):
    done, spans = _step(fe, make(0))
    assert [r.error for r in done] == [None]
    assert {s[0] for s in spans} == ALL_SPANS
    step, children = _partition(spans)
    assert step[4] == 1                           # requests finished
    assert [s[0] for s in children].count(obs.ENGINE_BUILD) == 1
    assert fe.engine.stats.misses == 1 and fe.engine.stats.hits == 0


@pytest.mark.parametrize("make", [_expr_request, _flat_request],
                         ids=["expr", "flat"])
def test_second_step_hits_the_jit_cache(fe, make):
    _step(fe, make(0))
    hits0 = obs.counters["jit_hits"]
    misses0 = obs.counters["jit_misses"]
    done, spans = _step(fe, make(1))
    assert [r.error for r in done] == [None]
    assert {s[0] for s in spans} == ALL_SPANS - {obs.ENGINE_BUILD,
                                                 obs.STORE_MATERIALIZE}
    _partition(spans)
    assert fe.engine.stats.hits == 1 and fe.engine.stats.misses == 1
    assert obs.counters["jit_hits"] == hits0 + 1
    assert obs.counters["jit_misses"] == misses0


def test_step_with_a_store_miss_still_partitions():
    """A cache of one stage-③ plane and requests alternating between two
    fields: from the third step on, each step misses the store and hits the
    jit cache.  Its materialization nests in the seed span, and the step's
    self time is the step less plan, seed and dispatch."""
    plane = 4 * 32 * 48
    store = FieldStore(cache_bytes=plane)
    for i in range(2):
        store.put(f"f/{i}", _field(i))
    fe = AnalyticsFrontend(store=store)

    def request(uid):
        return AnalyticsRequest(uid=uid, exprs=[expr.laplacian(f"f/{uid % 2}")],
                                stage=Stage.Q)

    for uid in range(2):
        _step(fe, request(uid))
    made0 = obs.counters["store_materializations"]
    evicted0 = obs.counters["store_evictions"]
    done, spans = _step(fe, request(2))
    assert [r.error for r in done] == [None]
    assert {s[0] for s in spans} == ALL_SPANS - {obs.ENGINE_BUILD}
    step, children = _partition(spans)
    assert sorted(c[0] for c in children) == sorted(
        [obs.QUERY_PLAN, obs.STORE_SEED, obs.ENGINE_DISPATCH])
    (made,) = [s for s in spans if s[0] == obs.STORE_MATERIALIZE]
    assert made[2] - made[1] > 0
    self_ns = (step[2] - step[1]) - sum(c[2] - c[1] for c in children)
    assert self_ns >= 0
    assert obs.counters["store_materializations"] == made0 + 1
    assert obs.counters["store_evictions"] == evicted0 + 1
    assert store.stats.evictions == 2 and store.stats.misses == 3


def test_steps_carry_their_serials(fe):
    _, a = _step(fe, _expr_request(0))
    _, b = _step(fe, _expr_request(1), _expr_request(2))
    (sa,) = [s for s in a if s[0] == obs.FRONTEND_STEP]
    (sb,) = [s for s in b if s[0] == obs.FRONTEND_STEP]
    assert sb[3] == sa[3] + 1 and sb[4] == 2
    assert {s[3] for s in b} == {sb[3]}


def test_rejected_group_closes_its_spans(fe):
    # a laplacian cannot run on the stage-1 metadata: the planner raises
    done, spans = _step(fe, _expr_request(0, stage=Stage.M))
    assert done[0].error is not None
    assert {s[0] for s in spans} == {obs.FRONTEND_STEP, obs.QUERY_PLAN}
    step, _ = _partition(spans)
    assert step[4] == 1
    t0 = time.perf_counter_ns()
    with obs.span(obs.QUERY_PLAN):
        pass
    (after,) = obs.spans(t0)
    assert after[3] is None          # the step's serial did not leak


def test_failed_first_call_closes_build_and_evicts():
    eng = BatchedAnalytics()
    t0 = time.perf_counter_ns()
    with pytest.raises(UnsupportedStageError):
        eng.run([_field(0)], "laplacian", Stage.M)
    names = [s[0] for s in obs.spans(t0)]
    assert names == [obs.ENGINE_DISPATCH, obs.ENGINE_BUILD]
    assert eng.cache_size == 0
    assert (eng.stats.misses, eng.stats.evictions) == (1, 1)


def test_ring_keeps_its_bound_and_time_order():
    ring = obs.Ring(size=8)
    starts = [5, 3, 9, 1, 7, 2, 8, 6, 4, 0, 11, 10]   # closing order
    for k, t0 in enumerate(starts):
        ring.append(obs.QUERY_PLAN, t0, t0 + 1, k, -1)
    out = ring.read(None, None)
    assert len(out) == ring.size
    assert [s[1] for s in out] == sorted(starts[-8:])   # the newest 8
    assert all(s[4] is None for s in out)
    assert [s[1] for s in ring.read(6, 10)] == [6, 7, 8]
    assert obs.RING_SIZE >= 1 << 17


def test_enclosing_span_reads_first_on_a_tie():
    ring = obs.Ring(size=4)
    ring.append(obs.QUERY_PLAN, 10, 12, 1, -1)        # child closes first
    ring.append(obs.FRONTEND_STEP, 10, 20, 1, 1)
    assert [s[0] for s in ring.read(None, None)] == [obs.FRONTEND_STEP,
                                                     obs.QUERY_PLAN]


def test_disabled_records_nothing(fe):
    _step(fe, _expr_request(0))
    hits0 = obs.counters["jit_hits"]
    obs.set_enabled(False)
    try:
        done, spans = _step(fe, _expr_request(1))
    finally:
        obs.set_enabled(True)
    assert [r.error for r in done] == [None]
    assert spans == []
    assert obs.counters["jit_hits"] == hits0 + 1     # counters still count


def test_spans_reach_the_profiler_trace(fe, tmp_path):
    _step(fe, _expr_request(0))                     # compile outside
    jax.profiler.start_trace(str(tmp_path))
    try:
        _, spans = _step(fe, _expr_request(1))
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    found = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name in ALL_SPANS:
                    found[e.name] = dict(e.stats)
    assert set(found) == ALL_SPANS - {obs.ENGINE_BUILD,
                                      obs.STORE_MATERIALIZE}
    (step,) = [s for s in spans if s[0] == obs.FRONTEND_STEP]
    assert found[obs.FRONTEND_STEP] == {"step": step[3], "count": 1}
