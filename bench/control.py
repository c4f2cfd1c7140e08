"""The control of the comparison: the reference in bfloat16, in the
program's place.

    python bench/control.py --workload <name> --seeds 1 2 3 [--rehearse N]

For each seed it makes the cell's fields, computes every distinct request
of the cell's traffic with the reference one precision below the
configuration's float32 (``jnp`` stencils on the device and numpy
statistics, both in bfloat16), compares those answers with the reference
exactly as a benchmark run compares the program's, and prints one JSON
line of readings per seed.  Every seed has to read above at least one of
the configuration's limits: a comparison that passes this control could
not tell the program from a bfloat16 shortcut.  Benchmark runs do not run
it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import jax.numpy as jnp  # noqa: E402
import ml_dtypes  # noqa: E402

import fields  # noqa: E402
import reference  # noqa: E402
import run as harness  # noqa: E402
import traffic  # noqa: E402


def control_answer(op: str, truths: list, region) -> list:
    """The bfloat16 reference's outputs of ``op`` on one field, or on the
    components of a vector op."""
    crop = (slice(None),) if region is None else tuple(
        slice(a, b) for a, b in region)
    if reference.kind(op) == "stats":
        x = truths[0].host()[crop]
        return [reference.apply_stats(op, x.astype(ml_dtypes.bfloat16))]
    xs = [t.x[crop].astype(jnp.bfloat16) for t in truths]
    if op in reference.VECTOR:
        return [sum(xs[c][sl] * jnp.asarray(w, jnp.bfloat16)
                    for w, c, sl in out)
                for out in reference.vector_terms(op, len(xs), xs[0].ndim)]
    return reference.apply_stencil(op, xs[0])


def readings(cell: harness.Cell, seed: int) -> dict:
    """Every distinct request of the cell's traffic, answered by the
    control and compared as a run compares the program's answers."""
    out: dict = {}
    answers = []
    for tpl in traffic.templates(cell.mix, cell.n_fields):
        answers += [((f,), op, tpl) for f in tpl.fields for op in tpl.ops]
        answers += [(comps, op, tpl) for op, comps in tpl.vector]
    truths: dict = {}
    for fs, op, tpl in sorted(answers, key=lambda a: a[0]):
        for f in [f for f in truths if f not in fs]:
            del truths[f]
        for f in fs:
            if f not in truths:
                truths[f] = reference.FieldTruth(
                    fields.make_field(cell.cfg["dataset"], f, cell.dims,
                                      seed), cell.cfg["rel_eb"])
        ts, region = [truths[f] for f in fs], cell.region(tpl)
        reference.compare(out, op, control_answer(op, ts, region), ts,
                          region)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rehearse", type=int, default=0, metavar="N")
    args = ap.parse_args(argv)
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    cell = harness.Cell(bench, args.workload, args.rehearse)
    limits = cell.cfg["limits"]
    failed_all = True
    for seed in args.seeds:
        r = readings(cell, seed)
        over = sorted(k for k, v in r.items() if v > limits[k])
        failed_all &= bool(over)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "readings": r, "over_limit": over}), flush=True)
    return 0 if failed_all else 1


if __name__ == "__main__":
    sys.exit(main())
