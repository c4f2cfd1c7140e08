"""The readers of the store's miss path (``misses.py``,
``metrics/materialize_ms.closed.py`` and
``metrics/materializations_per_request.py``)."""
import importlib.util
import os
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

import misses  # noqa: E402
import spans  # noqa: E402

READERS = ("materialize_ms.closed", "materializations_per_request")
S = 1_000_000_000
MS = 1_000_000


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "test_metric_" + name.replace(".", "_"),
        os.path.join(BENCH, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def fake_ring(monkeypatch, ring):
    def read(since, until):
        return sorted((s for s in ring if s[1] >= since
                       and (until is None or s[1] < until)),
                      key=lambda s: (s[1], -s[2]))
    monkeypatch.setattr(spans, "read_ring", read)


def hand_built_ring():
    step, seed, mat = spans.STEP, spans.SEED, misses.MATERIALIZE
    return [
        # warm-up, before the window: left out
        (step, S - 10 * MS, S - 5 * MS, 1, 1),
        (mat, S - 9 * MS, S - 8 * MS, None, None),
        # step 2 answers 1 request with 2 materializations (1.5 ms)
        (step, S + 10 * MS, S + 20 * MS, 2, 1),
        (seed, S + 11 * MS, S + 15 * MS, 2, None),
        (mat, S + 11 * MS, S + 12 * MS, None, None),
        (mat, S + 13 * MS, S + 13 * MS + 500_000, None, None),
        # the traced part: left out
        (step, S + 300 * MS, S + 310 * MS, 3, 1),
        (mat, S + 301 * MS, S + 302 * MS, None, None),
        # step 4 answers 2 requests with 1 materialization (2 ms)
        (step, S + 900 * MS, S + 920 * MS, 4, 2),
        (mat, S + 901 * MS, S + 903 * MS, None, None),
        # outside any step, and in a step after the window: left out
        (mat, S + 950 * MS, S + 951 * MS, None, None),
        (step, 2 * S + MS, 2 * S + 5 * MS, 5, 1),
        (mat, 2 * S + 2 * MS, 2 * S + 3 * MS, None, None),
    ]


RECORD = {"window": (1.0, 2.0), "trace_window": (1.2, 1.4), "trace": None}


def test_readers_on_a_hand_built_record(monkeypatch):
    fake_ring(monkeypatch, hand_built_ring())
    assert reader("materializations_per_request")(RECORD) \
        == pytest.approx(3 / 3)
    assert reader("materialize_ms.closed")(RECORD) == pytest.approx(3.5 / 3)


def test_readers_skip_a_program_without_the_span(monkeypatch):
    fake_ring(monkeypatch, hand_built_ring())
    monkeypatch.setattr(misses, "_known", lambda: False)
    for m in READERS:
        assert reader(m)(RECORD) is None


def test_readers_skip_a_window_with_no_answer(monkeypatch):
    fake_ring(monkeypatch, [])
    for m in READERS:
        assert reader(m)(RECORD) is None


def test_readers_on_live_steps_with_misses():
    """A real frontend on the CPU whose cache holds one of two planes:
    every step materializes once."""
    import jax.numpy as jnp
    import numpy as np
    from repro.core import Stage, expr, hszp_nd
    from repro.serve import AnalyticsFrontend, AnalyticsRequest
    from repro.store import FieldStore

    store = FieldStore(cache_bytes=4 * 32 * 48)
    rng = np.random.default_rng(0)
    for i in range(2):
        store.put(f"f/{i}", hszp_nd.compress(
            jnp.asarray(rng.normal(0, 1, (32, 48)).astype(np.float32)),
            rel_eb=1e-3))
    fe = AnalyticsFrontend(store=store)
    t0 = time.perf_counter()
    for uid in range(10):
        fe.add_request(AnalyticsRequest(
            uid=uid, exprs=[expr.laplacian(f"f/{uid % 2}")], stage=Stage.Q))
        fe.step()
    record = {"window": (t0, time.perf_counter()), "trace_window": None}
    assert reader("materializations_per_request")(record) == 1.0
    assert reader("materialize_ms.closed")(record) > 0
