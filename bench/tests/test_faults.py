"""The comparison sees the faults a cell can have, and its control.

Each test drives a whole benchmark run on the CPU at a tiny size
(``--rehearse``), with the timed path broken underneath, and reads the
``correct`` of its result line.  A sound run comes out correct; a run
whose answers are altered where they are produced, or whose frontend
leaves half of its batch unanswered, does not; nor does the bfloat16
control in the program's place.  Besides the committed cells, two cells
whose mixes are written inline, as a later cell would write its files,
cover the kinds of request no committed mix sends: vector operations, an
open loop with bursts, Zipf draws over fields and named regions.
"""
import json
import os
import sys

import jax.numpy as jnp
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import control  # noqa: E402
import run  # noqa: E402
from repro.analytics.engine import BatchedAnalytics  # noqa: E402
from repro.serve import AnalyticsFrontend  # noqa: E402

REGIONS = {"eye": [[40, 60], [200, 300], [200, 300]],
           "layer": [[45, 55], [0, 500], [0, 500]]}
MIXES = {
    "ocean.vector": ("ocean_2d", {
        "loop": "closed", "sample": 8, "templates": [
            {"vector": [{"op": "curl", "components": [0, 1]},
                        {"op": "divergence", "components": [0, 1]}],
             "stage": "Q"},
            {"ops": ["gradient"], "fields": [1],
             "vector": [{"op": "curl", "components": [1, 0]}],
             "stage": "P"}]}),
    "hurricane.roi_open": ("hurricane_isabel", {
        "loop": "open", "rate_per_s": 20, "block": 16, "sample": 8,
        "burst": {"on_s": 0.2, "off_s": 0.2}, "regions": REGIONS,
        "templates": [
            {"ops": ["mean", "std"], "fields": {"zipf": 1.1},
             "region": {"zipf": 1.0}, "stage": "auto"},
            {"vector": [{"op": "curl", "components": [0, 1, 2]},
                        {"op": "divergence", "components": [0, 1, 2]}],
             "region": "eye", "stage": "auto"}]}),
}
CELLS = ["ocean.stencil", "ocean.stats", "hurricane.scan", *MIXES]


@pytest.fixture(autouse=True)
def inline_mixes(monkeypatch):
    real = run.load_json

    def load(*parts):
        name = os.path.basename(parts[-1])
        if name == "BENCHMARK.json":
            bench = real(*parts)
            bench["workloads"] += [
                {"name": w, "config": c, "traffic": w, "chips": 1}
                for w, (c, _) in MIXES.items()]
            return bench
        if name[:-len(".json")] in MIXES:
            return json.loads(json.dumps(MIXES[name[:-len(".json")]][1]))
        return real(*parts)

    monkeypatch.setattr(run, "load_json", load)


def bench(capsys, workload, seed=2 ** 31 + 11):
    assert run.main(["--workload", workload, "--seed", str(seed),
                     "--seconds", "0.5", "--rehearse", "32"]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(capsys, workload):
    res = bench(capsys, workload)
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("workload", CELLS)
def test_altered_answer_is_caught(capsys, monkeypatch, workload):
    real = BatchedAnalytics.run_expr

    def altered(self, *a, **k):
        return [jnp.asarray(v) + 1e-3 if not isinstance(v, tuple)
                else tuple(c + 1e-3 for c in v) for v in real(self, *a, **k)]

    monkeypatch.setattr(BatchedAnalytics, "run_expr", altered)
    res = bench(capsys, workload)
    assert not res["correct"]
    assert any(c["value"] > c["limit"] for c in res["checks"].values())


@pytest.mark.parametrize("workload", CELLS)
def test_half_the_batch_left_out_is_caught(capsys, monkeypatch, workload):
    real = AnalyticsFrontend.step
    calls = {"n": 0}

    def half(self):
        calls["n"] += 1
        if calls["n"] % 2 == 0:  # every other request is dropped unanswered
            self._queue = self._queue[1:]
        return real(self)

    monkeypatch.setattr(AnalyticsFrontend, "step", half)
    monkeypatch.setattr(run, "DRAIN_S", 0.2)
    res = bench(capsys, workload)
    assert not res["correct"] and res["failed"] > 0


@pytest.mark.parametrize("workload", CELLS)
def test_bfloat16_control_fails(workload):
    cell = run.Cell(run.load_json(run.ROOT, "BENCHMARK.json"), workload, 32)
    r = control.readings(cell, seed=11)
    assert any(v > cell.cfg["limits"][k] for k, v in r.items())
