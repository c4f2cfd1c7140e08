"""Reduction of a profiler trace to the benchmark's device numbers.

The traced part of a run lies inside the host span ``bench.window``.  A
device's busy time is the union of the intervals of its operations (line
``XLA Ops`` of each ``/device:TPU:<n>`` plane) inside that span, averaged
over the devices that ran any; the idle share is one minus busy over the
span.  Each idle gap is put down to the harness's own host span that
overlaps it most (``frontend.step``, ``generator``, ``block_until_ready``,
``wait``), or to ``other``.
"""
from __future__ import annotations

import bisect
import glob
import os
import re

WINDOW = "bench.window"
HOST_SPANS = ("frontend.step", "generator", "block_until_ready", "wait")
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
TOP = 10


def newest_xplane(trace_dir: str) -> str | None:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def op_name(hlo: str) -> str:
    """The kind of one device operation: ``fusion`` of ``%fusion.7 =
    (s32[...]) fusion(...), ...``, ``lorenzo2d`` of ``%lorenzo2d.2 = ...``
    (XLA's instance numbers dropped, so the top list sums over a kind)."""
    return re.sub(r"\.\d+$", "", hlo.split(" = ", 1)[0].lstrip("%"))


def load_events(path: str) -> dict:
    """``{"host": [(name, start_ns, end_ns)], "devices": {plane: [...]}}``
    from one ``.xplane.pb`` file."""
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    host, devices = [], {}
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events
                            if e.name == WINDOW or e.name in HOST_SPANS)
        elif DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [
                        (op_name(e.name), e.start_ns,
                         e.start_ns + e.duration_ns) for e in line.events]
    return {"host": host, "devices": devices}


def union(intervals) -> list[tuple[float, float]]:
    """Disjoint, sorted union of ``(start, end)`` intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def reduce_events(ev: dict) -> dict | None:
    """Busy and idle time, the top device operations and the idle gaps by
    host span, in seconds; ``None`` when the trace holds no window or no
    device operation."""
    windows = [(s, e) for n, s, e in ev["host"] if n == WINDOW]
    if not windows:
        return None
    lo, hi = windows[0]
    busy, per_op, gaps_all = [], {}, []
    for events in ev["devices"].values():
        clipped = [(n, max(s, lo), min(e, hi)) for n, s, e in events
                   if e > lo and s < hi]
        if not clipped:
            continue
        for n, s, e in clipped:
            per_op[n] = per_op.get(n, 0.0) + (e - s) * 1e-9
        u = union((s, e) for _, s, e in clipped)
        busy.append(sum(e - s for s, e in u))
        edges = [lo] + [x for iv in u for x in iv] + [hi]
        gaps_all.extend((a, b) for a, b in zip(edges[::2], edges[1::2])
                        if b > a)
    if not busy:
        return None
    # host spans of one thread do not overlap: sorted by end, the spans
    # that meet a gap start at the first one ending after it
    spans = sorted(((e, s, n) for n, s, e in ev["host"] if n in HOST_SPANS))
    ends = [e for e, _, _ in spans]
    by_span: dict[str, float] = {}
    for a, b in gaps_all:
        best, best_len = "other", 0.0
        i = bisect.bisect_right(ends, a)
        while i < len(spans) and spans[i][1] < b:
            e, s, n = spans[i]
            if min(b, e) - max(a, s) > best_len:
                best, best_len = n, min(b, e) - max(a, s)
            i += 1
        by_span[best] = by_span.get(best, 0.0) + (b - a) * 1e-9 / len(busy)
    window_s = (hi - lo) * 1e-9
    busy_s = sum(busy) / len(busy) * 1e-9

    def top(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])
                ][:TOP]

    return {"window_s": window_s, "busy_s": busy_s,
            "devices": len(busy), "device_ops": top(per_op),
            "idle_gaps": top(by_span)}


def reduce_dir(trace_dir: str) -> dict | None:
    path = newest_xplane(trace_dir)
    return None if path is None else reduce_events(load_events(path))


# ---------------------------------------------------------------------------
# helpers of the metric readers (``bench/metrics/<name>.py``)
# ---------------------------------------------------------------------------

def idle_share(record: dict) -> float | None:
    """Per cent of the traced window in which the device ran nothing."""
    tr = record.get("trace")
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def roofline_share(record: dict, select) -> float | None:
    """Per cent of the chip's HBM roofline: the bytes that the selected
    requests answered inside the traced window need (``bytes.py``), at
    peak bandwidth, over the device's busy time in that window."""
    tr = record.get("trace")
    if not tr or tr["busy_s"] <= 0 or not record.get("peaks"):
        return None
    t0, t1 = record["trace_window"]
    work = sum(r["work"] for r in record["requests"]
               if r["error"] is None and r["done"] is not None
               and t0 <= r["done"] <= t1 and select(r))
    if work <= 0:
        return None
    return (100.0 * work / record["peaks"]["hbm_bytes_per_s"]
            / tr["busy_s"])


def host_ms_per_request(record: dict) -> float | None:
    """Host milliseconds inside ``AnalyticsFrontend.step`` per request it
    answered (a step's time shared among its answers), over the answers
    outside the traced part of the window, where the profiler does not
    slow the host (over all of them when the whole window was traced)."""
    done = [r for r in record["requests"] if r["host_s"] is not None]
    tw = record.get("trace_window")
    if tw:
        untraced = [r for r in done if not tw[0] <= r["done"] <= tw[1]]
        done = untraced or done
    return 1e3 * sum(r["host_s"] for r in done) / len(done) if done else None
