"""Derived operators: vorticity from (u, v) as one expression query.

Builds a rigid-rotation velocity field (closed-form vorticity == +2),
registers u and v in a FieldStore, and computes

    vorticity = dv/dx - du/dy

as ONE expression query (DESIGN.md §10): one compiled program, one stage
reconstruction per component, store-seeded on the second run.  Compares
against the naive spelling (two single-derivative queries composed on the
host) for both correctness and dispatch count, then shows a couple more
derived quantities riding the same store.

    PYTHONPATH=src python examples/derived_operators.py [--n 192]
"""
import argparse
import os
import time

import numpy as np
import jax.numpy as jnp

from repro.analytics import query
from repro.analytics.engine import BatchedAnalytics
from repro.core import by_name, expr
from repro.store import FieldStore
from repro.launch.cache import use_compile_cache


def main():
    use_compile_cache(os.path.join(os.path.dirname(__file__), ".."))
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=192)
    args = ap.parse_args()
    n = args.n

    # rigid rotation (u, v) = (-y, x): vorticity dv/dx - du/dy == 2 exactly
    i = np.arange(n, dtype=np.float32)[:, None] + np.zeros((n, n), np.float32)
    j = np.arange(n, dtype=np.float32)[None, :] + np.zeros((n, n), np.float32)
    comp = by_name("hszp_nd")
    store = FieldStore(cache_bytes=256 << 20)
    store.put("u", comp.compress(jnp.asarray(-j), abs_eb=0.25))
    store.put("v", comp.compress(jnp.asarray(i), abs_eb=0.25))
    engine = BatchedAnalytics()

    vort = expr.sub(expr.derivative("v", axis=0), expr.derivative("u", axis=1))
    res = query(exprs=[vort], store=store, engine=engine)   # cold: materializes
    t0 = time.perf_counter()
    res = query(exprs=[vort], store=store, engine=engine)   # warm: seeded
    dt = time.perf_counter() - t0
    w = np.asarray(res.values[0])
    print(f"vorticity: shape {w.shape}, mean {w.mean():+.6f} (exact +2), "
          f"stage {res.stages[0].name}, {res.n_dispatches} dispatch(es), "
          f"store hits {res.store_hits}, {dt * 1e3:.2f} ms warm")

    # naive composition: one query per derivative, combined on the host
    naive = query(exprs=[expr.op("derivative", "v", axis=0)],
                  store=store, engine=engine)
    naive2 = query(exprs=[expr.op("derivative", "u", axis=1)],
                   store=store, engine=engine)
    w_naive = np.asarray(naive.values[0]) - np.asarray(naive2.values[0])
    print(f"naive compose: {naive.n_dispatches + naive2.n_dispatches} "
          f"dispatches, max |delta| vs expression "
          f"{np.abs(w - w_naive).max():.2e}")

    # several derived quantities in one program: leaves decode once each
    batch = query(exprs=[vort,
                         expr.laplacian("u") + expr.laplacian("v"),
                         2.0 * expr.mean("u") - expr.std("v")],
                  store=store, engine=engine)
    print(f"3 derived roots over 2 leaves: {batch.n_dispatches} dispatch(es), "
          f"stages {[s.name for s in batch.stages]}")


if __name__ == "__main__":
    main()
