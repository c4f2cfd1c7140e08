"""On-chip benchmark of the compressed-field analytics service.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One run builds the cell's deployment from the seed (fields made on the
device, compressed, encoded at one common width and registered in a
``FieldStore``), warms up on the cell's own traffic, then drives
``AnalyticsFrontend.step`` with expression requests over the stored field
ids for ``--seconds`` seconds.  Once the window has closed it reads the
device's memory peak, frees the service, checks a seeded sample of the
answers against the plain reference (``reference.py``) and prints one JSON
line: the cell's end-to-end metrics (``--trace 0``) or its per-layer
metrics (``--trace 1``, with a profiler trace of the window's first
``--trace-seconds``).

Everything a cell is made of is found by name: ``BENCHMARK.json`` names
the configuration (``configs/<config>.json``), the traffic mix
(``traffic/<traffic>.json``, read by ``traffic.py``) and the metrics, each
read by ``metrics/<metric>.py``.

Without a TPU, or with fewer chips than the cell asks for, the run exits 2
and prints no result.  ``--rehearse N`` runs the cell on whatever JAX
finds, with every dimension divided by ``N`` and no metric reported: a
rehearsal of the control flow and of the comparison, not a measurement.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (HERE, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import bytes as workbytes  # noqa: E402
import fields  # noqa: E402
import reduce as trace_reduce  # noqa: E402
import reference  # noqa: E402
import traffic  # noqa: E402

DRAIN_S = 60.0          # how long past the window an answer may still come
QUIET_S = 2.0           # warm-up ends after this long without a new program
WARMUP_CAP_S = 300.0    # ... or after this long in all
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


def log(*parts) -> None:
    print(f"[{time.perf_counter() - T_START:8.2f} s]", *parts,
          file=sys.stderr, flush=True)


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as fh:
        return json.load(fh)


class Compiles:
    """Program builds (backend compiles and persistent-cache loads alike),
    counted by a ``jax.monitoring`` listener."""

    count = 0
    installed = False

    @classmethod
    def install(cls) -> None:
        def on_event(event, duration, **_):
            if event == BACKEND_COMPILE:
                cls.count += 1
        if not cls.installed:
            jax.monitoring.register_event_duration_secs_listener(on_event)
            cls.installed = True


# ---------------------------------------------------------------------------
# the deployment
# ---------------------------------------------------------------------------

def build_store(cfg: dict, dims: tuple[int, ...], seed: int):
    """Make every field on the device, compress it and encode it at the
    configuration's fixed width (the same programs for every seed), and
    register it; returns ``(store, ids)``."""
    from repro.core import by_name
    from repro.store import FieldStore

    comp = by_name(cfg["scheme"])
    compress = jax.jit(lambda ph, fr, key: comp.compress(
        fields._make(dims, ph, fr, key), rel_eb=cfg["rel_eb"]))
    comps = []
    for f in range(cfg["n_fields"]):
        ph, fr, k = fields.field_params(cfg["dataset"], f, seed, len(dims))
        comps.append(compress(jnp.asarray(ph), jnp.asarray(fr),
                              jax.random.PRNGKey(k)))
    bits = int(cfg["bits"])
    need = max(comp.max_bits(c) for c in comps)
    if need > bits:
        raise SystemExit(f"bench: a field needs {need} bits, more than the "
                         f"configuration's fixed width of {bits}")
    encode = jax.jit(lambda c: comp.encode(c, bits))
    store = FieldStore(cache_bytes=int(cfg["cache_bytes"]))
    ids = []
    for f in range(cfg["n_fields"]):
        ids.append(store.put(f"{cfg['name']}/{f}", encode(comps[f])))
        comps[f] = None
    log(f"deployment: {cfg['n_fields']} x {dims} {cfg['scheme']} fields, "
        f"{bits} bits a value (the widest field needs {need})")
    return store, ids


class Cell:
    """One cell's configuration, traffic and request factory."""

    def __init__(self, bench: dict, name: str, rehearse: int):
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise SystemExit(f"unknown workload {name!r}; "
                             f"known: {sorted(cells)}")
        self.spec = cells[name]
        conf = {c["name"]: c for c in bench["configs"]}[self.spec["config"]]
        self.cfg = load_json(ROOT, conf["file"])
        self.mix = load_json(HERE, "traffic", self.spec["traffic"] + ".json")
        self.full_dims = tuple(self.cfg["dims"])
        self.dims = (tuple(max(8, d // rehearse) for d in self.full_dims)
                     if rehearse else self.full_dims)
        self.n_fields = self.cfg["n_fields"]
        self.ids: list[str] = []

    def region(self, tpl):
        return traffic.region_bounds(self.mix, tpl.region, self.dims,
                                     self.full_dims)

    def shape(self, tpl) -> tuple[int, ...]:
        reg = self.region(tpl)
        return self.dims if reg is None else tuple(b - a for a, b in reg)

    def request(self, uid: int, tpl):
        from repro.core import Stage, expr
        from repro.serve import AnalyticsRequest

        roots = []
        for f in tpl.fields:
            for op in tpl.ops:
                if op.startswith("derivative"):
                    roots.append(expr.derivative(
                        self.ids[f], axis=int(op[len("derivative"):])))
                else:
                    roots.append(expr.op(op, self.ids[f]))
        for op, comps in tpl.vector:
            roots.append(expr.op(op, tuple(self.ids[c] for c in comps)))
        stage = tpl.stage if tpl.stage == "auto" else Stage[tpl.stage]
        return AnalyticsRequest(uid=uid, exprs=roots, stage=stage,
                                region=self.region(tpl))

    def record(self, uid: int, tpl, due: float) -> dict:
        shape = self.shape(tpl)
        n_read = len(tpl.all_fields())
        work = workbytes.request_bytes(
            tpl.ops, len(set(tpl.fields)), shape,
            vector=[(op, len(c)) for op, c in tpl.vector], n_read=n_read)
        return {"uid": uid, "tpl": tpl, "due": due, "sent": None,
                "done": None, "error": None, "host_s": None,
                "covered": workbytes.covered_bytes(n_read, shape),
                "work": work["read"] + work["write"],
                "stencil": bool(tpl.vector) or any(
                    reference.kind(o) == "stencil" for o in tpl.ops)}


def outputs(tpl, result) -> list[tuple[tuple[int, ...], str, list]]:
    """``(fields, op, [output arrays])`` of one answer, in request order:
    one field for a per-field op, the components for a vector op."""
    named = [((f,), op) for f in tpl.fields for op in tpl.ops]
    named += [(comps, op) for op, comps in tpl.vector]
    return [(fs, op, list(v) if isinstance(v, tuple) else [v])
            for (fs, op), v in zip(named, result)]


# ---------------------------------------------------------------------------
# the client
# ---------------------------------------------------------------------------

class Client:
    """Drives the frontend: a closed loop of one client, or an open loop on
    the mix's schedule.  Keeps a seeded reservoir sample of the answers."""

    def __init__(self, cell: Cell, fe, seed: int, sample: int):
        self.cell, self.fe = cell, fe
        self.uid = 0
        self.sample = sample
        self.kept: list = []
        self.n_answered = 0
        self.rng = np.random.default_rng([seed % (1 << 64), 7])
        self.steps_finished = 0

    def _keep(self, rec: dict, result) -> None:
        """Reservoir sampling over answers, in the order they come."""
        i = self.n_answered
        self.n_answered += 1
        if i < self.sample:
            self.kept.append((rec, result))
            return
        j = int(self.rng.integers(i + 1))
        if j < self.sample:
            self.kept[j] = (rec, result)

    def _step(self, pending: dict, records: list | None) -> int:
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("frontend.step"):
            fin = self.fe.step()
        host_s = (time.perf_counter() - t0) / max(1, len(fin))
        self.steps_finished += len(fin)
        with jax.profiler.TraceAnnotation("block_until_ready"):
            for r in fin:
                if r.error is None:
                    jax.block_until_ready(r.result)
                rec = pending.pop(r.uid, None)
                if rec is None:  # sent by an earlier run of this client
                    continue
                rec["done"] = time.perf_counter()
                rec["error"] = r.error
                rec["host_s"] = host_s
                if records is not None and r.error is None:
                    self._keep(rec, r.result)
                r.result = None
        return len(fin)

    def _send(self, tpl, due: float, pending: dict, records) -> None:
        with jax.profiler.TraceAnnotation("generator"):
            rec = self.cell.record(self.uid, tpl, due)
            req = self.cell.request(self.uid, tpl)
            self.uid += 1
            pending[rec["uid"]] = rec
            if records is not None:
                records.append(rec)
            rec["sent"] = time.perf_counter()
            self.fe.add_request(req)

    def settle(self, pending: dict, records, uid: int | None = None) -> None:
        """Step until request ``uid`` (or every pending one) is answered,
        for at most ``DRAIN_S``."""
        limit = time.perf_counter() + DRAIN_S
        while (uid in pending if uid is not None else pending) and (
                time.perf_counter() < limit):
            self._step(pending, records)

    def run(self, gen, until, records: list | None = None,
            on_tick=None) -> None:
        """Send requests due before ``until()`` turns true, then wait for
        every one sent (at most ``DRAIN_S`` past the close)."""
        pending: dict[int, dict] = {}
        closed = self.cell.mix["loop"] == "closed"
        gap, tpl = next(gen)
        now = time.perf_counter()
        due = now + (gap or 0.0)
        while True:
            if on_tick is not None:
                on_tick()
            now = time.perf_counter()
            if until(due):
                break
            if closed:
                self._send(tpl, due, pending, records)
                self.settle(pending, records, self.uid - 1)
                due = time.perf_counter()
                _, tpl = next(gen)
                continue
            while due <= now and not until(due):
                self._send(tpl, due, pending, records)
                gap, tpl = next(gen)
                due += gap
            if pending:
                self._step(pending, records)
            else:
                with jax.profiler.TraceAnnotation("wait"):
                    time.sleep(max(0.0, due - time.perf_counter()))
        self.settle(pending, records)
        for rec in pending.values():
            rec["error"] = "no answer within the drain limit"
            rec["done"] = time.perf_counter()


def warm_up(client: Client, cell: Cell, seed: int) -> int:
    """Run each distinct template once, then the cell's own traffic on a
    warm-up stream until no new program for ``QUIET_S`` (and every closed
    template twice) or ``WARMUP_CAP_S``; returns the requests sent."""
    pending: dict[int, dict] = {}
    for tpl in traffic.templates(cell.mix, cell.n_fields):
        client._send(tpl, time.perf_counter(), pending, None)
        client.settle(pending, None)
    t0 = time.perf_counter()
    state = {"last": Compiles.count, "t": t0, "n": 0}
    min_n = 2 * len(cell.mix["templates"]) * (cell.mix["loop"] == "closed")

    def until(due):
        now = time.perf_counter()
        if Compiles.count != state["last"]:
            state["last"], state["t"] = Compiles.count, now
        state["n"] += 1
        quiet = now - state["t"] >= QUIET_S and state["n"] > min_n
        return quiet or now - t0 >= WARMUP_CAP_S

    client.run(traffic.requests(cell.mix, cell.n_fields, seed, stream=1),
               until)
    return client.uid


# ---------------------------------------------------------------------------
# results
# ---------------------------------------------------------------------------

def load_reader(name: str):
    path = os.path.join(HERE, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(bench: dict, cell_name: str, trace: bool) -> list[dict]:
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group
            if "workloads" not in m or cell_name in m["workloads"]]


def check_answers(cell: Cell, kept: list, seed: int, dims) -> dict:
    """The comparison: each sampled answer against the reference, grouped
    by the fields it reads, so that only those fields' references are in
    memory at a time (one field for a per-field op, its components for a
    vector op)."""
    readings: dict = {}
    by_fields: dict[tuple[int, ...], list] = {}
    for rec, outs in kept:
        for fs, op, arrays in outs:
            by_fields.setdefault(fs, []).append((rec, op, arrays))
    truths: dict[int, reference.FieldTruth] = {}
    for fs in sorted(by_fields):
        for f in [f for f in truths if f not in fs]:
            del truths[f]
        for f in fs:
            if f not in truths:
                truths[f] = reference.FieldTruth(
                    fields.make_field(cell.cfg["dataset"], f, dims, seed),
                    cell.cfg["rel_eb"])
        for rec, op, arrays in by_fields[fs]:
            reference.compare(readings, op, arrays,
                              [truths[f] for f in fs],
                              cell.region(rec["tpl"]))
    return readings


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-seconds", type=float, default=4.0,
                    help="length of the traced part of the window")
    ap.add_argument("--rehearse", type=int, default=0, metavar="N",
                    help="divide every dimension by N and run on any "
                         "platform; reports no metric")
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        log(f"bench: the program (src/repro) is not in {ROOT}")
        return 2
    bench = load_json(ROOT, "BENCHMARK.json")
    cell = Cell(bench, args.workload, args.rehearse)
    log("imports done")
    devs = jax.devices()
    dev = devs[0]
    log(f"backend up: {len(devs)} {dev.platform} device(s)")
    if not args.rehearse and (dev.platform != "tpu"
                              or len(devs) < cell.spec["chips"]):
        log(f"bench: cell {args.workload} needs {cell.spec['chips']} TPU "
            f"chip(s); JAX found {len(devs)} {dev.platform} device(s)")
        return 2
    peaks = None
    if not args.rehearse:
        table = load_json(HERE, "peaks.json")
        if dev.device_kind not in table:
            log(f"bench: no peaks for device kind {dev.device_kind!r} in "
                "peaks.json")
            return 2
        peaks = table[dev.device_kind]

    from repro.launch.cache import use_compile_cache
    from repro.serve import AnalyticsFrontend

    cache_dir = use_compile_cache(ROOT)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    Compiles.install()
    log(f"device {dev.platform} {dev.device_kind} x {len(devs)}; compile "
        f"cache {cache_dir}")

    store, cell.ids = build_store(cell.cfg, cell.dims, args.seed)
    fe = AnalyticsFrontend(store=store)
    client = Client(cell, fe, args.seed, int(cell.mix.get("sample", 16)))
    n_warm = warm_up(client, cell, args.seed)
    client.kept, client.n_answered = [], 0
    # The deployment's peak: set-up and warm-up ran every program the window
    # runs, at the same shapes, and kept no answer; the window adds only the
    # comparison's sampled answers, which the service does not hold.
    peak_bytes = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    log(f"warm-up: {n_warm} requests, {Compiles.count} program builds, "
        f"store {store.stats}, device peak {peak_bytes} B")

    trace_dir = os.path.join(ROOT, ".bench_trace", args.workload)
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
    records: list[dict] = []
    client.steps_finished = 0
    seconds = float(args.seconds)
    trace_s = min(seconds, args.trace_seconds) if args.trace else 0.0
    tr = {"on": False, "t0": None, "t1": None, "ann": None}

    def on_tick():
        now = time.perf_counter()
        if args.trace and tr["t0"] is None:
            jax.profiler.start_trace(trace_dir)
            tr["ann"] = jax.profiler.TraceAnnotation("bench.window")
            tr["ann"].__enter__()
            tr["t0"], tr["on"] = time.perf_counter(), True
        elif tr["on"] and now - tr["t0"] >= trace_s:
            tr["t1"] = time.perf_counter()
            tr["ann"].__exit__(None, None, None)
            jax.profiler.stop_trace()
            tr["on"] = False

    compiles0 = Compiles.count
    t0 = time.perf_counter()
    setup_s = t0 - T_START
    end = t0 + seconds
    client.run(traffic.requests(cell.mix, cell.n_fields, args.seed, stream=0),
               lambda due: due >= end, records, on_tick)
    if tr["on"]:
        tr["t1"] = time.perf_counter()
        tr["ann"].__exit__(None, None, None)
        jax.profiler.stop_trace()
    compiles = Compiles.count - compiles0
    held = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    log(f"window: {len(records)} requests, {client.steps_finished} answered "
        f"by {args.workload}, {compiles} program builds, store {store.stats}"
        f", device peak {held} B with {len(client.kept)} sampled answers")

    kept = [(rec, outputs(rec["tpl"], res)) for rec, res in client.kept]
    record = {
        "seconds": seconds, "setup_s": setup_s, "window": (t0, end),
        "requests": records, "compiles_in_window": compiles,
        "peaks": peaks, "trace": None,
        "trace_window": (tr["t0"], tr["t1"]) if args.trace else None,
    }
    del fe, store, client
    gc.collect()

    late = sorted(r["sent"] - r["due"] for r in records
                  if r["sent"] is not None)
    failed = sum(r["error"] is not None for r in records)
    if late:
        log(f"generator lateness: median {1e3 * late[len(late) // 2]:.3f} "
            f"ms, max {1e3 * late[-1]:.3f} ms")
    if args.trace:
        record["trace"] = trace_reduce.reduce_dir(trace_dir)
    values = {}
    for m in cell_metrics(bench, args.workload, bool(args.trace)):
        v = load_reader(m["name"])(record)
        if v is not None:
            values[m["name"]] = {"value": v, "unit": m["unit"]}

    t_ref = time.perf_counter()
    readings = check_answers(cell, kept, args.seed, cell.dims)
    limits = cell.cfg["limits"]
    checks = {k: {"value": v, "limit": limits[k]}
              for k, v in sorted(readings.items())}
    correct = (bool(kept) and failed == 0
               and all(c["value"] <= c["limit"] for c in checks.values()))
    log(f"reference: {len(kept)} answers compared in "
        f"{time.perf_counter() - t_ref:.1f} s")

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": peak_bytes}
    result = {"correct": correct, "attempted": len(records),
              "failed": failed, "metrics": {} if args.rehearse else values,
              "device": device}
    if args.trace and record["trace"] is not None:
        device["busy_s"] = record["trace"]["busy_s"]
        device["window_s"] = record["trace"]["window_s"]
        result["breakdown"] = {k: record["trace"][k]
                               for k in ("device_ops", "idle_gaps")}
    if args.rehearse:
        result["rehearsal"] = {"dims": cell.dims, "answered": len(kept),
                               "would_report": sorted(values)}
    result["checks"] = checks
    for k, c in checks.items():
        log(f"check {k} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
