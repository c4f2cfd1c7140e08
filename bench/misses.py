"""The store's miss path in the program's spans: ``repro.store.materialize``.

A materialization span nests inside the step's ``repro.store.seed`` span
and carries no step serial (``repro.obs``), so it is found by time: the
materialization spans that lie inside the steps ``spans.ring_steps``
selects (in the window, outside its traced part).  A program whose
``repro.obs`` has no such span gives no value.
"""
from __future__ import annotations

import bisect

import spans

MATERIALIZE = "repro.store.materialize"


def _known() -> bool:
    try:
        from repro import obs
    except ImportError:
        return False
    return MATERIALIZE in obs.NAMES


def in_steps(record: dict):
    """``(materialization spans inside the selected steps, requests those
    steps answered)``; ``None`` where the program has no such span or the
    steps answered nothing."""
    if not _known():
        return None
    r = spans.ring_steps(record)
    if r is None or not r[2]:
        return None
    steps, _, answered = r
    steps = sorted((s[1], s[2]) for s in steps)
    starts = [s for s, _ in steps]
    ring = spans.read_ring(int(record["window"][0] * 1e9), None) or []
    found = []
    for sp in ring:
        if sp[0] != MATERIALIZE:
            continue
        i = bisect.bisect_right(starts, sp[1]) - 1
        if i >= 0 and sp[2] <= steps[i][1]:
            found.append(sp)
    return found, answered
