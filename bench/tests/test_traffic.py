"""The traffic generator reads every kind of mix from data alone.

Mixes here are written inline, as a later cell would write its file:
closed and open loops, on/off bursts, weighted templates in shuffled
order, and Zipf draws over fields and named regions.  ``test_faults.py``
runs two such mixes, with vector operations, through the whole harness.
"""
import itertools
import os
import sys
from collections import Counter

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import run  # noqa: E402
import traffic  # noqa: E402

BIG_SEED = 2 ** 31 + 12345
REGIONS = {"eye": [[40, 60], [200, 300], [200, 300]],
           "layer": [[45, 55], [0, 500], [0, 500]]}


def take(mix, n, n_fields=13, seed=BIG_SEED, stream=0):
    return list(itertools.islice(
        traffic.requests(mix, n_fields, seed, stream), n))


@pytest.mark.parametrize("name", ["scan", "stats", "stencil"])
def test_committed_mixes_cycle_from_a_seeded_offset(name):
    mix = run.load_json(HERE, "..", "traffic", name + ".json")
    a, b = take(mix, 12), take(mix, 12)
    assert a == b  # the same seed gives the same requests
    tpls = traffic.templates(mix, 13)
    start = tpls.index(a[0][1])
    assert [t for _, t in a] == [tpls[(start + i) % len(tpls)]
                                 for i in range(12)]
    assert all(g is None for g, _ in a)


def test_shuffle_holds_each_template_by_weight_in_every_block():
    mix = {"loop": "closed", "order": "shuffle", "block": 8, "templates": [
        {"ops": ["mean"], "fields": [0], "weight": 3},
        {"ops": ["std"], "fields": [1], "weight": 1}]}
    reqs = take(mix, 32)
    for k in range(4):
        block = Counter(t.ops for _, t in reqs[8 * k:8 * k + 8])
        assert block == {("mean",): 6, ("std",): 2}
    assert reqs != take(mix, 32, seed=BIG_SEED + 1)


def test_open_loop_gaps_have_the_mean_rate_in_every_block():
    mix = {"loop": "open", "rate_per_s": 40.0, "block": 64,
           "templates": [{"ops": ["mean"], "fields": [0]}]}
    gaps = np.array([g for g, _ in take(mix, 128)])
    assert np.all(gaps > 0)
    for k in range(2):
        assert gaps[64 * k:64 * k + 64].mean() == pytest.approx(
            1 / 40.0, rel=0.05)


def test_bursts_arrive_only_in_on_phases_at_the_same_mean_rate():
    mix = {"loop": "open", "rate_per_s": 20.0, "block": 200,
           "burst": {"on_s": 0.5, "off_s": 1.5},
           "templates": [{"ops": ["mean"], "fields": [0]}]}
    times = np.cumsum([g for g, _ in take(mix, 400)])
    assert np.all(np.mod(times, 2.0) <= 0.5 + 1e-9)
    assert np.sum(times < 16.0) / 16.0 == pytest.approx(20.0, rel=0.05)


def test_zipf_over_fields_and_regions():
    mix = {"loop": "closed", "block": 100, "regions": REGIONS,
           "templates": [{"ops": ["mean", "std"], "fields": {"zipf": 1.1},
                          "region": {"zipf": 1.0}}]}
    reqs = take(mix, 100)
    fields = Counter(t.fields[0] for _, t in reqs)
    regions = Counter(t.region for _, t in reqs)
    assert fields[0] == max(fields.values()) and len(fields) == 13
    assert fields == Counter(traffic._quantiles(traffic._zipf(13, 1.1), 100))
    assert regions["eye"] == 67 and regions["layer"] == 33
    assert len(traffic.templates(mix, 13)) == 13 * 2
    assert {t for _, t in reqs} <= set(traffic.templates(mix, 13))
