"""Spans and counters of the served path.

One mechanism with two sinks.  :class:`span` times one layer of the host
dispatch path and

* appends ``(name, start_ns, end_ns, step, count)`` (``time.perf_counter_ns``)
  to a bounded in-memory ring, read back in time order by :func:`spans`;
* enters ``jax.profiler.TraceAnnotation(name, step=...)`` while a profiler
  trace is being taken, so the same span lands in the ``.xplane.pb`` host
  plane next to the device planes.

The span names are fixed; the spans of one serving step are siblings inside
:data:`FRONTEND_STEP` and partition it:

* :data:`FRONTEND_STEP` -- ``AnalyticsFrontend.step``; carries the step
  serial shared by every request the step answers, and counts the requests
  it finished.  Its self time (the step minus its children) is drain,
  per-request validation, grouping and scatter;
* :data:`QUERY_PLAN` -- DAG analysis, leaf resolution, bound validation,
  store residency probes and stage planning;
* :data:`STORE_SEED` -- the store's ``seed`` calls (a miss materializes
  inside it);
* :data:`ENGINE_DISPATCH` -- an engine program: building its cache key and,
  on a jit-cache hit, the jitted call;
* :data:`ENGINE_BUILD` -- a jit-cache miss: ``jax.jit`` through the first
  call, which traces and compiles (or loads) the program.

A span opened inside a step records that step's serial, except
:data:`STORE_MATERIALIZE` -- a store miss that builds a materialization
(``FieldStore.seed``/``ensure``), nested inside :data:`STORE_SEED` when a
step seeds.  It records no step serial, so the spans that carry one still
partition the step and the seed span keeps the materializations it
contains; a reader finds the materializations of a step by time.

:data:`counters` holds the process-wide jit-cache totals (each engine
keeps its own in ``BatchedAnalytics.stats``); ``plan_resident_promotions``:
planned store-backed components that ``stage="auto"`` put above the stage
storeless planning picks (``repro.analytics.planner``); and the store's
``store_materializations`` (materializations built), of them
``store_materializations_fused`` (built by the one-dispatch 3-D Lorenzo
kernel, ``repro.store.materialize``), and ``store_evictions`` (cache
entries dropped, as ``StoreStats.evictions`` counts them), over every
store; ``vector_fused``: programs built with the 3-D divergence/curl
kernel (``repro.core.oplib.lower_vectors``, counted as each traces).
Spans sit on the host path only: none is opened inside traced or jitted
code.
"""
from __future__ import annotations

import itertools
import struct
import time

import jax

FRONTEND_STEP = "repro.frontend.step"
QUERY_PLAN = "repro.query.plan"
STORE_SEED = "repro.store.seed"
ENGINE_DISPATCH = "repro.engine.dispatch"
ENGINE_BUILD = "repro.engine.build"
STORE_MATERIALIZE = "repro.store.materialize"
NAMES = (FRONTEND_STEP, QUERY_PLAN, STORE_SEED, ENGINE_DISPATCH,
         ENGINE_BUILD, STORE_MATERIALIZE)

#: spans the ring holds: one 40 s window of the fastest benchmark cell
#: (about 19k steps of 4 spans) with room to spare
RING_SIZE = 1 << 18

#: process-wide totals (monotone): jit-cache events over every engine,
#: store-backed stage promotions of the planner, and store cache churn
counters = {"jit_hits": 0, "jit_misses": 0, "jit_evictions": 0,
            "plan_resident_promotions": 0, "store_materializations": 0,
            "store_materializations_fused": 0, "store_evictions": 0,
            "vector_fused": 0}

_ID = {name: i for i, name in enumerate(NAMES)}
_UNSTEPPED = frozenset({STORE_MATERIALIZE})   # nested: no step serial
_NONE = -1                  # step / count not given
_ROW = struct.Struct("qqqqq")   # name index, start_ns, end_ns, step, count
_TraceMe = jax.profiler.TraceAnnotation
_now = time.perf_counter_ns


class Ring:
    """The newest ``size`` closed spans, packed in one buffer as rows of
    ``(index in NAMES, start_ns, end_ns, step, count)``."""

    def __init__(self, size: int = RING_SIZE):
        self.size = size
        self.rows = bytearray(_ROW.size * size)
        self.n = 0                      # spans appended and readable
        self._seq = itertools.count()   # slot claims: atomic under the GIL

    def append(self, name: str, t0: int, t1: int, step: int,
               count: int) -> None:
        k = next(self._seq)
        _ROW.pack_into(self.rows, k % self.size * _ROW.size, _ID[name], t0,
                       t1, step, count)
        self.n = k + 1

    def read(self, since_ns: int | None, until_ns: int | None) -> list:
        n = self.n
        lo = -1 << 63 if since_ns is None else since_ns
        hi = 1 << 63 if until_ns is None else until_ns
        out = []
        for k in range(max(0, n - self.size), n):
            name, t0, t1, step, count = _ROW.unpack_from(
                self.rows, k % self.size * _ROW.size)
            if lo <= t0 < hi:
                out.append((NAMES[name], t0, t1,
                            None if step == _NONE else step,
                            None if count == _NONE else count))
        out.sort(key=lambda s: (s[1], -s[2]))
        return out


_ring = Ring()
_enabled = True
_current_step = _NONE


class span:
    """Context manager timing one layer span, named from :data:`NAMES` (see
    the module docstring).  ``step`` marks a serving step: spans opened
    inside it record its serial.  Set ``count`` before the span closes to
    record how many requests it finished."""

    __slots__ = ("name", "step", "count", "_t0", "_tm", "_outer")

    def __init__(self, name: str, step: int | None = None):
        self.name = name
        self.step = step
        self.count = None

    def __enter__(self) -> "span":
        global _current_step
        if not _enabled:
            self._t0 = 0
            return self
        self._outer = _current_step
        if self.step is not None:
            _current_step = self.step
        self._tm = None
        if _TraceMe.is_enabled():
            self._tm = (_TraceMe(self.name) if self.step is None
                        else _TraceMe(self.name, step=self.step))
            self._tm.__enter__()
        self._t0 = _now()
        return self

    def __exit__(self, *exc) -> bool:
        global _current_step
        if not self._t0:
            return False
        t1 = _now()
        if self._tm is not None:
            if self.count is not None:
                self._tm.set_metadata(count=self.count)
            self._tm.__exit__(*exc)
        step = _NONE if self.name in _UNSTEPPED else _current_step
        _current_step = self._outer
        _ring.append(self.name, self._t0, t1, step,
                     _NONE if self.count is None else self.count)
        return False


def spans(since_ns: int | None = None,
          until_ns: int | None = None) -> list[tuple]:
    """Spans still in the ring that start in ``[since_ns, until_ns)``
    (``time.perf_counter_ns`` clock), ordered by start (an enclosing span
    before its children), as ``(name, start_ns, end_ns, step, count)``;
    ``step`` and ``count`` are ``None`` where not recorded."""
    return _ring.read(since_ns, until_ns)


def set_enabled(flag: bool) -> None:
    """Turn both sinks (the ring and the profiler annotations) on or off;
    counters keep counting.  For measuring what the spans cost."""
    global _enabled
    _enabled = bool(flag)
