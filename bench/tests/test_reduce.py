"""The trace reduction, on a trace recorded on one TPU v5e.

``testdata/stencil_window.xplane.pb.gz`` is the profiler trace of 0.1 s of
an ``ocean.stencil`` window (``run.py --trace 1 --trace-seconds 0.1``);
that run reported ``busy_s`` 0.038437061 and ``window_s`` 0.100888418.
"""
import gzip
import os
import shutil
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import reduce  # noqa: E402

TRACE = os.path.join(os.path.dirname(HERE), "testdata",
                     "stencil_window.xplane.pb.gz")


@pytest.fixture(scope="module")
def events(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    with gzip.open(TRACE) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return reduce.load_events(str(path))


def test_planes_found(events):
    assert list(events["devices"]) == ["/device:TPU:0"]
    names = {n for n, _, _ in events["host"]}
    assert reduce.WINDOW in names
    assert {"frontend.step", "block_until_ready", "generator"} <= names


def test_matches_the_chip_run(events):
    r = reduce.reduce_events(events)
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(0.100888418, abs=1e-9)
    assert r["busy_s"] == pytest.approx(0.038437061, abs=1e-9)


def test_busy_is_the_union(events):
    # an independent union: sweep the sorted starts with a running max end
    (lo, hi), = [(s, e) for n, s, e in events["host"] if n == reduce.WINDOW]
    iv = np.array([(max(s, lo), min(e, hi)) for _, s, e in
                   events["devices"]["/device:TPU:0"] if e > lo and s < hi],
                  np.float64)
    iv = iv[np.argsort(iv[:, 0])]
    prev_end = np.concatenate([[lo], np.maximum.accumulate(iv[:, 1])[:-1]])
    busy = np.sum(np.maximum(iv[:, 1] - np.maximum(iv[:, 0], prev_end), 0))
    r = reduce.reduce_events(events)
    assert r["busy_s"] == pytest.approx(busy * 1e-9, rel=1e-12)


def test_gaps_and_ops_account_for_the_window(events):
    r = reduce.reduce_events(events)
    idle = sum(v for _, v in r["idle_gaps"])
    assert idle + r["busy_s"] == pytest.approx(r["window_s"], rel=1e-9)
    assert {n for n, _ in r["idle_gaps"]} <= set(reduce.HOST_SPANS) | {
        "other"}
    assert 0 < len(r["device_ops"]) <= reduce.TOP
    names = [n for n, _ in r["device_ops"]]
    assert all(" " not in n and not n.startswith("%") and
               not n.rsplit(".", 1)[-1].isdigit() for n in names)
    assert "lorenzo2d" in names and "unpack" in names
    times = [v for _, v in r["device_ops"]]
    assert times == sorted(times, reverse=True) and times[-1] > 0


def test_no_window_reads_nothing(events):
    ev = {"host": [e for e in events["host"] if e[0] != reduce.WINDOW],
          "devices": events["devices"]}
    assert reduce.reduce_events(ev) is None


def test_readers_skip_without_a_trace():
    record = {"trace": None, "requests": []}
    assert reduce.idle_share(record) is None
    assert reduce.roofline_share(record, lambda r: True) is None
    assert reduce.host_ms_per_request(record) is None
