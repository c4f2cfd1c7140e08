"""The program's own spans (``repro.obs``), read for the per-layer metrics.

Two sources, shared by the readers ``metrics/<name>.py``:

* The ring (``repro.obs.spans``), read in the run's own process once the
  window has closed.  A reader takes the ``repro.frontend.step`` spans that
  start inside ``record["window"]`` and outside ``record["trace_window"]``
  (the profiler slows the host there, as for ``host_ms_per_request``) and
  the spans those steps contain; "per answered request" divides by the
  requests the steps recorded as finished.  The children of a step are
  siblings, so the step's self time plus their times is the step.
* The profiler trace of the traced part: the newest ``.xplane.pb`` under
  ``.bench_trace/``, restricted to ``bench.window`` and parsed once per
  process.  Its host plane holds the same spans (``jax.profiler``
  annotations); its device planes hold the programs and operations, on the
  device's clock.

A program without ``repro.obs``, or a trace without its spans, gives no
value: each reader returns ``None``.

Alignment.  The device plane's clock runs early against the host's: on a
v5e the ``XLA Modules`` event of a program starts before the host's
``DoEnqueueProgram`` that launched it (matched by ``run_id``).  A device
cannot start a program before it is enqueued, so the device clock is at
least ``lower_ns`` early, the largest enqueue-to-start lead; it is at most
``upper_ns`` early, the smallest time from a program's end to the end of
the host's completion event (``tpu::System::Execute=>Done``, taken in
order).  Device intervals are shifted later by ``lower_ns``.  A trace with
no enqueue event pairs each program, in order, with the start of the
program's own ``repro.engine.dispatch`` span instead.

    python bench/spans.py [trace dir]   # prints the alignment of a trace
"""
from __future__ import annotations

import functools
import json
import os
import sys

import reduce

HERE = os.path.dirname(os.path.abspath(__file__))
TRACE_ROOT = os.path.join(os.path.dirname(HERE), ".bench_trace")

STEP = "repro.frontend.step"
PLAN = "repro.query.plan"
SEED = "repro.store.seed"
DISPATCH = "repro.engine.dispatch"
BUILD = "repro.engine.build"
ENQUEUE = "DoEnqueueProgram"
COMPLETION = "tpu::System::Execute=>Done"
MODULES_LINE = "XLA Modules"
_HOST_EVENTS = {reduce.WINDOW, STEP, DISPATCH, COMPLETION}


# ---------------------------------------------------------------------------
# the ring
# ---------------------------------------------------------------------------

def read_ring(since_ns: int, until_ns: int | None) -> list | None:
    """``repro.obs.spans(since_ns, until_ns)``, or ``None`` where the
    program has no ``repro.obs``."""
    try:
        from repro import obs
    except ImportError:
        return None
    return obs.spans(since_ns, until_ns)


def ring_steps(record: dict):
    """``(steps, children, answered)``: the step spans that start in the
    window and outside its traced part, the spans inside those steps, and
    the requests the steps finished; ``None`` without a ring."""
    t0, t1 = record["window"]
    spans = read_ring(int(t0 * 1e9), None)
    if spans is None:
        return None
    end = int(t1 * 1e9)
    tw = record.get("trace_window")
    traced = ((int(tw[0] * 1e9), int(tw[1] * 1e9))
              if tw and tw[0] is not None else (0, 0))
    steps = {s[3]: s for s in spans if s[0] == STEP and s[1] < end
             and not traced[0] <= s[1] < traced[1]}
    children = [s for s in spans if s[0] != STEP and s[3] in steps]
    answered = sum(s[4] or 0 for s in steps.values())
    return list(steps.values()), children, answered


def _ms(spans) -> float:
    return sum(e - s for _, s, e, _, _ in spans) * 1e-6


def child_ms_per_request(record: dict, name: str) -> float | None:
    """Milliseconds in the ``name`` spans of the steps, per answered
    request."""
    r = ring_steps(record)
    if r is None or not r[2]:
        return None
    _, children, answered = r
    return _ms(c for c in children if c[0] == name) / answered


def self_ms_per_request(record: dict) -> float | None:
    """Milliseconds of the steps outside their children, per answered
    request."""
    r = ring_steps(record)
    if r is None or not r[2]:
        return None
    steps, children, answered = r
    return (_ms(steps) - _ms(children)) / answered


def builds_in_window(record: dict) -> int | None:
    """``repro.engine.build`` spans (jit-cache misses) that start in the
    window, the traced part included."""
    t0, t1 = record["window"]
    spans = read_ring(int(t0 * 1e9), int(t1 * 1e9))
    if spans is None:
        return None
    return sum(s[0] == BUILD for s in spans)


# ---------------------------------------------------------------------------
# the trace
# ---------------------------------------------------------------------------

def newest_trace() -> str | None:
    return reduce.newest_xplane(TRACE_ROOT)


@functools.lru_cache(maxsize=2)
def _load(path: str, mtime: float) -> dict:
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    tr = {"window": None, "steps": [], "dispatch": [], "completion": [],
          "enqueue": {}, "modules": {}, "ops": {}}
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    name = e.name
                    if name == ENQUEUE:
                        st = dict(e.stats)
                        tr["enqueue"][(st.get("run_id"),
                                       st.get("device_ordinal"))] = e.start_ns
                    elif name in _HOST_EVENTS:
                        iv = (e.start_ns, e.start_ns + e.duration_ns)
                        if name == reduce.WINDOW:
                            tr["window"] = iv
                        else:
                            tr[{STEP: "steps", DISPATCH: "dispatch",
                                COMPLETION: "completion"}[name]].append(iv)
        elif reduce.DEVICE_PLANE.match(plane.name):
            ordinal = int(plane.name.rsplit(":", 1)[1])
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    tr["modules"][ordinal] = [
                        (e.start_ns, e.start_ns + e.duration_ns,
                         dict(e.stats).get("run_id")) for e in line.events]
                elif line.name == reduce.OPS_LINE:
                    tr["ops"][ordinal] = [
                        (e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events]
    for k in ("steps", "dispatch", "completion"):
        tr[k].sort()
    return tr


def load_trace(path: str) -> dict:
    """The events of one trace the readers use (parsed once a process)."""
    return _load(path, os.path.getmtime(path))


def alignment(tr: dict) -> dict | None:
    """How early the device clock runs, ``{"lower_ns", "upper_ns",
    "programs"}`` (``upper_ns`` ``None`` without completion events), over
    the programs that start inside ``bench.window``; ``None`` when no
    program can be paired with the host event that launched it."""
    if tr["window"] is None:
        return None
    lo, hi = tr["window"]
    mods = sorted((s, e, rid, dev) for dev, ms in tr["modules"].items()
                  for s, e, rid in ms if lo <= s < hi)
    pairs = [(s, e, tr["enqueue"][(rid, dev)]) for s, e, rid, dev in mods
             if (rid, dev) in tr["enqueue"]]
    if not tr["enqueue"]:
        starts = [s for s, _ in tr["dispatch"] if lo <= s < hi]
        if len(starts) == len(mods):
            pairs = [(s, e, h) for (s, e, _, _), h in zip(mods, starts)]
    if not pairs:
        return None
    lower = max(h - s for s, _, h in pairs)
    upper, j, done = None, 0, tr["completion"]
    for _, e, h in sorted(pairs, key=lambda p: p[2]):
        while j < len(done) and done[j][0] < h:
            j += 1
        if j == len(done):
            break
        gap = done[j][1] - e
        upper = gap if upper is None else min(upper, gap)
        j += 1
    return {"lower_ns": lower, "upper_ns": upper, "programs": len(pairs)}


def _clip(intervals, lo, hi):
    return reduce.union((max(s, lo), min(e, hi)) for s, e in intervals
                        if e > lo and s < hi)


def _overlap(a, b) -> float:
    """Length of the intersection of two sorted, disjoint interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        total += max(0.0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_in_step_share(record: dict) -> float | None:
    """Per cent of the traced window in which the device runs nothing while
    the host is inside ``repro.frontend.step``, device times aligned."""
    if record.get("trace") is None:     # not a traced run
        return None
    path = newest_trace()
    if path is None:
        return None
    tr = load_trace(path)
    al = alignment(tr)
    if al is None or not tr["steps"]:
        return None
    lo, hi = tr["window"]
    steps = _clip(tr["steps"], lo, hi)
    in_steps = sum(e - s for s, e in steps)
    shift = al["lower_ns"]
    idle = []
    for ops in tr["ops"].values():
        busy = _clip(((s + shift, e + shift) for s, e in ops), lo, hi)
        if busy:
            idle.append(in_steps - _overlap(steps, busy))
    if not idle:
        return None
    return 100.0 * sum(idle) / len(idle) / (hi - lo)


if __name__ == "__main__":
    path = reduce.newest_xplane(sys.argv[1] if len(sys.argv) > 1
                                else TRACE_ROOT)
    print(json.dumps(None if path is None else
                     {"trace": path, **(alignment(load_trace(path)) or {})}))
