"""The readers of the program's own spans (``spans.py`` and the six
``metrics/*.py`` that use it).

``testdata/stencil_window.xplane.pb.gz`` is the recorded chip trace of
``test_reduce.py`` (0.1 s of an ``ocean.stencil`` window on one TPU v5e,
36 programs); read by hand, its device plane runs 426.6 us to 852.3 us
early against the host.  ``testdata/stencil_spans.xplane.pb.gz`` is the
same cell traced with the program's spans (``run.py --workload
ocean.stencil --seed 2213000001 --seconds 1 --trace 1 --trace-seconds
0.1`` on one TPU v5e); that run read ``idle_in_step_share.closed``
34.65402595833376 from it.
"""
import gzip
import importlib.util
import os
import shutil
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

import spans  # noqa: E402

TESTDATA = os.path.join(BENCH, "testdata")
RING_METRICS = ("frontend_self_ms.closed", "planner_ms.closed",
                "store_seed_ms.closed", "dispatch_ms.closed")
SIX = RING_METRICS + ("jit_misses_in_window", "idle_in_step_share.closed")


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "test_metric_" + name.replace(".", "_"),
        os.path.join(BENCH, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def unpacked(tmp_path_factory, name):
    path = tmp_path_factory.mktemp("trace") / name.replace(".gz", "")
    with gzip.open(os.path.join(TESTDATA, name)) as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return str(path)


@pytest.fixture(scope="module")
def stencil_window(tmp_path_factory):
    return spans.load_trace(unpacked(tmp_path_factory,
                                     "stencil_window.xplane.pb.gz"))


@pytest.fixture(scope="module")
def stencil_spans(tmp_path_factory):
    return unpacked(tmp_path_factory, "stencil_spans.xplane.pb.gz")


def fake_ring(monkeypatch, ring):
    """Serve ``ring`` (``(name, start_ns, end_ns, step, count)``) as
    ``repro.obs.spans`` would."""
    def read(since, until):
        return sorted((s for s in ring if s[1] >= since
                       and (until is None or s[1] < until)),
                      key=lambda s: (s[1], -s[2]))
    monkeypatch.setattr(spans, "read_ring", read)


# ---------------------------------------------------------------------------
# alignment
# ---------------------------------------------------------------------------

def test_alignment_bounds_on_the_recorded_trace(stencil_window):
    al = spans.alignment(stencil_window)
    assert al["programs"] == 36
    assert al["lower_ns"] == pytest.approx(426_600, abs=1_000)
    assert al["upper_ns"] == pytest.approx(852_300, abs=1_000)


def test_alignment_is_the_least_consistent_shift(stencil_window):
    # shifted by the lower bound, no program starts before its enqueue and
    # one starts exactly at it; none ends after its completion event
    tr = stencil_window
    lead = spans.alignment(tr)["lower_ns"]
    starts = [(s + lead, tr["enqueue"][(rid, dev)])
              for dev, ms in tr["modules"].items() for s, _, rid in ms]
    assert all(s >= h for s, h in starts)
    assert min(s - h for s, h in starts) == 0


def test_alignment_falls_back_to_dispatch_spans(stencil_window):
    # without enqueue events, each program pairs with its dispatch span
    tr = dict(stencil_window, enqueue={})
    mods = sorted(s for ms in tr["modules"].values() for s, _, _ in ms)
    tr["dispatch"] = [(s + 500_000, s + 600_000) for s in mods]
    assert spans.alignment(tr)["lower_ns"] == 500_000
    tr["dispatch"] = tr["dispatch"][1:]       # one unpaired: no alignment
    assert spans.alignment(tr) is None


def test_no_window_no_alignment(stencil_window):
    assert spans.alignment(dict(stencil_window, window=None)) is None


def test_idle_in_step_without_program_spans(stencil_window, monkeypatch):
    # the recorded trace predates the program's spans: nothing to read
    monkeypatch.setattr(spans, "load_trace", lambda path: stencil_window)
    monkeypatch.setattr(spans, "newest_trace", lambda: "recorded")
    assert stencil_window["steps"] == []
    assert reader("idle_in_step_share.closed")({"trace": {}}) is None


def test_idle_in_step_on_a_hand_built_trace(monkeypatch):
    # window 0..100; steps 10..30 and 60..90; the device (1 us early, as
    # the enqueue at 21 against the program's start at 20 shows) busy
    # 19..39 and 74..79 on its clock, 20..40 and 75..80 on the host's
    tr = {"window": (0, 100), "steps": [(10, 30), (60, 90)],
          "dispatch": [], "completion": [(42, 45), (81, 84)],
          "enqueue": {(1, 0): 21, (2, 0): 75},
          "modules": {0: [(20, 39, 1), (74, 79, 2)]},
          "ops": {0: [(19, 39), (74, 79)]}}
    assert spans.alignment(tr) == {"lower_ns": 1, "upper_ns": 5,
                                   "programs": 2}
    monkeypatch.setattr(spans, "load_trace", lambda path: tr)
    monkeypatch.setattr(spans, "newest_trace", lambda: "hand-built")
    # in steps, idle: 10..20 and 60..75 and 80..90
    assert reader("idle_in_step_share.closed")({"trace": {}}) \
        == pytest.approx(35.0)
    assert reader("idle_in_step_share.closed")({"trace": None}) is None


# ---------------------------------------------------------------------------
# the ring readers
# ---------------------------------------------------------------------------

S = 1_000_000_000            # one second in ns
MS = 1_000_000


def hand_built_ring():
    step, plan, seed, disp, build = (spans.STEP, spans.PLAN, spans.SEED,
                                     spans.DISPATCH, spans.BUILD)
    return [
        # before the window
        (step, 9 * S // 10, 9 * S // 10 + MS, 1, 1),
        (disp, 9 * S // 10 + 1, 9 * S // 10 + 2, 1, None),
        # in the window: step 2 answers 1 request in 1.0 ms
        (step, 1 * S + 50 * MS, 1 * S + 51 * MS, 2, 1),
        (plan, 1 * S + 50 * MS + 100_000, 1 * S + 50 * MS + 300_000, 2,
         None),
        (seed, 1 * S + 50 * MS + 300_000, 1 * S + 50 * MS + 350_000, 2,
         None),
        (disp, 1 * S + 50 * MS + 350_000, 1 * S + 50 * MS + 850_000, 2,
         None),
        # in the traced part: left out of the timings
        (step, 1 * S + 300 * MS, 1 * S + 310 * MS, 3, 1),
        (build, 1 * S + 301 * MS, 1 * S + 309 * MS, 3, None),
        # in the window: step 4 answers 2 requests in 2.0 ms
        (step, 1 * S + 900 * MS, 1 * S + 902 * MS, 4, 2),
        (plan, 1 * S + 900 * MS, 1 * S + 900 * MS + 400_000, 4, None),
        (disp, 1 * S + 900 * MS + 400_000, 1 * S + 901 * MS, 4, None),
        (build, 1 * S + 901 * MS, 1 * S + 901 * MS + 500_000, 4, None),
        # outside any step, and after the window
        (disp, 1 * S + 950 * MS, 1 * S + 951 * MS, None, None),
        (step, 2 * S + MS, 2 * S + 2 * MS, 5, 1),
    ]


RECORD = {"window": (1.0, 2.0), "trace_window": (1.2, 1.4), "trace": None}


def test_ring_readers_on_a_hand_built_record(monkeypatch):
    fake_ring(monkeypatch, hand_built_ring())
    got = {m: reader(m)(RECORD) for m in RING_METRICS}
    # three answered requests in steps 2 and 4 (3.0 ms of steps)
    assert got["planner_ms.closed"] == pytest.approx(0.6 / 3)
    assert got["store_seed_ms.closed"] == pytest.approx(0.05 / 3)
    assert got["dispatch_ms.closed"] == pytest.approx(1.1 / 3)
    # self: 3.0 ms less 0.6 + 0.05 + 1.1 + 0.5 (the build) of children
    assert got["frontend_self_ms.closed"] == pytest.approx(0.75 / 3)
    # the build in step 3 lies in the traced part; it still counts
    assert reader("jit_misses_in_window")(RECORD) == 2


def test_ring_readers_without_a_traced_part(monkeypatch):
    fake_ring(monkeypatch, hand_built_ring())
    record = dict(RECORD, trace_window=None)
    assert reader("planner_ms.closed")(record) == pytest.approx(0.6 / 4)
    assert reader("frontend_self_ms.closed")(record) \
        == pytest.approx((13.0 - 0.6 - 0.05 - 1.1 - 8.5) / 4)


def test_readers_skip_a_program_without_spans(monkeypatch):
    monkeypatch.setattr(spans, "read_ring", lambda since, until: None)
    monkeypatch.setattr(spans, "newest_trace", lambda: None)
    for m in SIX:
        assert reader(m)(dict(RECORD, trace={})) is None


def test_readers_skip_a_window_with_no_answer(monkeypatch):
    fake_ring(monkeypatch, [])
    for m in RING_METRICS:
        assert reader(m)(RECORD) is None
    assert reader("jit_misses_in_window")(RECORD) == 0


def test_ring_readers_partition_live_steps():
    """Steps of a real frontend on the CPU: the four timings add up to the
    steps' own time per answered request."""
    import jax.numpy as jnp
    import numpy as np
    from repro.core import Stage, expr, hszp_nd
    from repro.serve import AnalyticsFrontend, AnalyticsRequest
    from repro.store import FieldStore

    store = FieldStore()
    rng = np.random.default_rng(0)
    for i in range(2):
        store.put(f"f/{i}", hszp_nd.compress(
            jnp.asarray(rng.normal(0, 1, (32, 48)).astype(np.float32)),
            rel_eb=1e-3))
    fe = AnalyticsFrontend(store=store)
    t0 = time.perf_counter()
    for uid in range(12):
        fe.add_request(AnalyticsRequest(
            uid=uid, exprs=[expr.mean(f"f/{uid % 2}"),
                            expr.laplacian("f/1")], stage=Stage.Q))
        fe.step()
    record = {"window": (t0, time.perf_counter()), "trace_window": None}
    got = {m: reader(m)(record) for m in RING_METRICS}
    steps, children, answered = spans.ring_steps(record)
    assert answered == len(steps) == 12
    builds = sum(e - s for n, s, e, _, _ in children if n == spans.BUILD)
    total = sum(e - s for _, s, e, _, _ in steps)
    assert sum(got.values()) == pytest.approx((total - builds) * 1e-6 / 12)
    assert all(v > 0 for v in got.values())
    assert reader("jit_misses_in_window")(record) == 2


# ---------------------------------------------------------------------------
# the six readers on a chip trace with the program's spans
# ---------------------------------------------------------------------------

def trace_ring(path):
    """The program's spans of a trace as ring rows: the step's serial and
    count from its annotation's stats, a child's serial from the step that
    holds it; and the ``bench.window`` bounds (trace clock, ns)."""
    import jax

    rows, window = [], None
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                iv = (e.start_ns, e.start_ns + e.duration_ns)
                if e.name == "bench.window":
                    window = iv
                elif e.name.startswith("repro."):
                    st = dict(e.stats)
                    rows.append((e.name, *iv, st.get("step"),
                                 st.get("count")))
    steps = [r for r in rows if r[0] == spans.STEP]
    ring = []
    for name, s0, s1, step, count in rows:
        if name != spans.STEP:
            (step,) = [p[3] for p in steps if p[1] <= s0 and s1 <= p[2]]
        ring.append((name, int(s0), int(s1), step, count))
    return ring, window


def test_six_readers_on_the_chip_trace(stencil_spans, monkeypatch):
    ring, (lo, hi) = trace_ring(stencil_spans)
    fake_ring(monkeypatch, ring)
    monkeypatch.setattr(spans, "newest_trace", lambda: stencil_spans)
    record = {"window": (lo * 1e-9, hi * 1e-9), "trace_window": None,
              "trace": {}}
    got = {m: reader(m)(record) for m in SIX}
    assert got["idle_in_step_share.closed"] == pytest.approx(
        34.65402595833376, rel=1e-12)
    assert got["jit_misses_in_window"] == 0
    # 36 steps of one request each, a jit-cache hit each: the four
    # timings add up to the steps' own time
    steps, children, answered = spans.ring_steps(record)
    assert answered == len(steps) == 36
    assert sorted(n for n, *_ in children) == sorted(
        [spans.PLAN, spans.SEED, spans.DISPATCH] * 36)
    total = sum(e - s for _, s, e, _, _ in steps) * 1e-6 / 36
    assert sum(got[m] for m in RING_METRICS) == pytest.approx(total)
    # under the profiler's Python tracer the Python work swells 2-2.5
    # times against the same run's untraced ring (0.134 / 0.141 / 0.017 /
    # 0.317 ms), the jitted call's native dispatch hardly at all
    assert [got[m] for m in RING_METRICS] == pytest.approx(
        [0.28210725, 0.350023277777, 0.038345222222, 0.334957083333],
        rel=1e-9)


def test_alignment_on_the_chip_trace(stencil_spans):
    al = spans.alignment(spans.load_trace(stencil_spans))
    assert al["programs"] == 35     # one program's enqueue precedes the trace
    assert 0 < al["lower_ns"] <= al["upper_ns"]
