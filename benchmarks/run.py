"""Benchmark harness: one benchmark per paper table/figure + framework benches.

Prints ``name,us_per_call,derived`` CSV rows.  Datasets are deterministic
synthetic analogues of the paper's five benchmarks (Table III), scaled by
``--scale`` (default 8: Ocean 300x450, NYX 64^3, ...) so the suite runs on
one CPU core; the compressor/operator code paths are identical at any scale.

Paper figure -> benchmark:
  Fig. 2   compression ratios                -> fig2_compression_ratio
  Fig. 3/4 decompression throughput by stage -> fig34_decompression
  Fig. 5-8 mean/std throughput by stage      -> fig58_statistics
  Fig. 9/10 derivative/Laplacian throughput  -> fig910_differentiation
  Fig. 11/12 divergence/curl throughput      -> fig1112_multivariate
  Table IV decompression/compute breakdown   -> table4_breakdown
  Table V  homomorphic operation errors      -> table5_op_errors
Framework-level (beyond paper):
  checkpoint bytes + homomorphic validation  -> fw_checkpoint
  compressed-collective wire bytes           -> fw_collective_bytes
  fused op sets vs sequential single ops     -> fw_fused_analytics
  expression DAGs vs per-leaf recompute      -> fw_expr_analytics
  store-backed hot-cache vs cold queries     -> fw_store_analytics
  streaming append+query vs re-encode        -> fw_stream_analytics
  fused Pallas kernels vs XLA lowering       -> fw_kernel_analytics
  sharded store vs single-device bytes/wall  -> fw_shard_analytics

``--filter PREFIX[,PREFIX...]`` runs only the row families whose name
starts with a prefix (e.g. ``--filter fw_store`` or ``--filter fig2,fw_``),
so CI gates and local iteration stop paying for the whole suite.

``--json-dir DIR`` writes every machine-readable row family as
``BENCH_*.json`` under DIR for the CI regression gates; the per-family
``--json`` / ``--json-expr`` / ``--json-store`` / ``--json-stream`` /
``--json-kernel`` flags remain as deprecated aliases.
"""
from __future__ import annotations
from collections.abc import Callable

import argparse
import json
import os
import time
import warnings

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import Stage, by_name, encode, homomorphic as H
from repro.core import region as region_mod
from repro.data.scientific import dataset_dims, synth_field

ROWS: list[tuple[str, float, str]] = []
FUSED_JSON: list[dict] = []
EXPR_JSON: list[dict] = []
STORE_JSON: list[dict] = []
STREAM_JSON: list[dict] = []
KERNEL_JSON: list[dict] = []
SHARD_JSON: list[dict] = []
SCALE = 8
REPS = 3

#: every machine-readable row family --json-dir emits, one file per gate
JSON_FILES = (("BENCH_fused.json", FUSED_JSON),
              ("BENCH_expr.json", EXPR_JSON),
              ("BENCH_store.json", STORE_JSON),
              ("BENCH_stream.json", STREAM_JSON),
              ("BENCH_kernel.json", KERNEL_JSON),
              ("BENCH_shard.json", SHARD_JSON))

COMPRESSORS = ["hszp", "hszx", "hszp_nd", "hszx_nd"]
EBS = [1e-1, 1e-2, 1e-3]
BENCH_SETS = ["Ocean", "Miranda", "NYX"]


def row(name: str, us: float, derived: str):
    ROWS.append((name, us, derived))


def timeit(fn: Callable, *args) -> tuple[float, object]:
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(REPS):
        out = fn(*args)
        jax.block_until_ready(out)
    return (time.perf_counter() - t0) / REPS * 1e6, out


def best_of(fn: Callable, *args, k: int = 7) -> float:
    """Min-of-k microseconds: contention only ever inflates a timing, so the
    minimum is the robust estimator the CI speedup gates need."""
    out = fn(*args)
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(max(k, REPS)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def _fields():
    for ds in BENCH_SETS:
        dims = dataset_dims(ds, SCALE)
        yield ds, jnp.asarray(synth_field(ds, 0, dims))


# ---------------------------------------------------------------------------

def fig2_compression_ratio():
    for ds, data in _fields():
        for name in COMPRESSORS:
            comp = by_name(name)
            for eb in EBS:
                c = comp.compress(data, rel_eb=eb)
                ratio = float(comp.compression_ratio(c))
                row(f"fig2/{ds}/{name}/eb{eb:g}", 0.0, f"ratio={ratio:.2f}")


def fig34_decompression():
    for ds, data in _fields():
        nbytes = data.size * 4
        for name in COMPRESSORS:
            comp = by_name(name)
            c = comp.compress(data, rel_eb=1e-2)
            e = comp.encode(c)
            for stage, tag in ((Stage.P, "p"), (Stage.Q, "q"), (Stage.F, "f")):
                fn = jax.jit(lambda enc, s=stage: comp.decompress(enc, s))
                us, _ = timeit(fn, e)
                gbps = nbytes / (us * 1e-6) / 1e9
                row(f"fig34/{ds}/{name}-{tag}", us, f"GBps={gbps:.2f}")


def fig58_statistics():
    for ds, data in _fields():
        nbytes = data.size * 4
        for name in COMPRESSORS:
            comp = by_name(name)
            c = comp.compress(data, rel_eb=1e-2)
            e = comp.encode(c)
            stages = [(Stage.P, "p"), (Stage.Q, "q"), (Stage.F, "f")]
            if comp.scheme.is_blockmean:
                stages.insert(0, (Stage.M, "m"))
            for op_name, op in (("mean", H.mean), ("std", H.std)):
                for stage, tag in stages:
                    if op_name == "std" and stage == Stage.M:
                        continue
                    fn = jax.jit(lambda enc, s=stage, o=op: o(enc, s))
                    us, _ = timeit(fn, e)
                    gbps = nbytes / (us * 1e-6) / 1e9
                    row(f"fig58/{ds}/{op_name}/{name}-{tag}", us, f"GBps={gbps:.2f}")


def fig910_differentiation():
    for ds, data in _fields():
        nbytes = data.size * 4
        for name in ("hszp_nd", "hszx_nd"):
            comp = by_name(name)
            c = comp.compress(data, rel_eb=1e-2)
            e = comp.encode(c)
            for op_name, op in (("deriv", lambda enc, s: H.derivative(enc, s, 0)),
                                ("laplacian", H.laplacian)):
                for stage, tag in ((Stage.P, "p"), (Stage.Q, "q"), (Stage.F, "f")):
                    fn = jax.jit(lambda enc, s=stage, o=op: o(enc, s))
                    us, _ = timeit(fn, e)
                    gbps = nbytes / (us * 1e-6) / 1e9
                    row(f"fig910/{ds}/{op_name}/{name}-{tag}", us, f"GBps={gbps:.2f}")


def fig1112_multivariate():
    for ds in BENCH_SETS:
        dims = dataset_dims(ds, SCALE)
        nd = len(dims)
        for name in ("hszp_nd", "hszx_nd"):
            comp = by_name(name)
            fields = [comp.encode(comp.compress(
                jnp.asarray(synth_field(ds, i, dims)), rel_eb=1e-2))
                for i in range(nd)]
            nbytes = nd * int(np.prod(dims)) * 4
            for op_name, op in (("div", H.divergence), ("curl", H.curl)):
                for stage, tag in ((Stage.P, "p"), (Stage.Q, "q"), (Stage.F, "f")):
                    fn = jax.jit(lambda *fs, s=stage, o=op: o(list(fs), s))
                    us, _ = timeit(fn, *fields)
                    gbps = nbytes / (us * 1e-6) / 1e9
                    row(f"fig1112/{ds}/{op_name}/{name}-{tag}", us, f"GBps={gbps:.2f}")


def table4_breakdown():
    """Decompression vs computation split for a 3-D derivative (NYX)."""
    dims = dataset_dims("NYX", SCALE)
    data = jnp.asarray(synth_field("NYX", 0, dims))
    comp = by_name("hszp_nd")
    c = comp.compress(data, rel_eb=1e-3)
    e = comp.encode(c)
    us_dec_p, _ = timeit(jax.jit(lambda enc: encode.decode_device(enc).residuals), e)
    us_op_p, _ = timeit(jax.jit(lambda enc: H.derivative(enc, Stage.P, 0)), e)
    us_dec_q, _ = timeit(jax.jit(lambda enc: comp.decompress(enc, Stage.Q)), e)
    us_op_q, _ = timeit(jax.jit(lambda enc: H.derivative(enc, Stage.Q, 0)), e)
    us_dec_f, _ = timeit(jax.jit(lambda enc: comp.decompress(enc, Stage.F)), e)
    us_op_f, _ = timeit(jax.jit(lambda enc: H.derivative(enc, Stage.F, 0)), e)
    row("table4/Dp", us_op_p, f"decode_us={us_dec_p:.0f}")
    row("table4/Dq", us_op_q, f"decode_us={us_dec_q:.0f}")
    row("table4/Df", us_op_f, f"decode_us={us_dec_f:.0f}")


def table5_op_errors():
    dims = dataset_dims("NYX", SCALE)
    u = jnp.asarray(synth_field("NYX", 0, dims))
    v = jnp.asarray(synth_field("NYX", 1, dims))
    w = jnp.asarray(synth_field("NYX", 2, dims))
    for name in COMPRESSORS:
        comp = by_name(name)
        cu = comp.compress(u, rel_eb=1e-3)
        errs = {}
        ref = float(H.mean(cu, Stage.F))
        stages = [Stage.P, Stage.Q] + ([Stage.M] if comp.scheme.is_blockmean else [])
        errs["mean"] = max(abs(float(H.mean(cu, s)) - ref) / max(abs(ref), 1e-12)
                           for s in stages)
        ref = float(H.std(cu, Stage.F))
        errs["std"] = max(abs(float(H.std(cu, s)) - ref) / ref
                          for s in (Stage.P, Stage.Q))
        if comp.scheme.is_nd:
            cv, cw = comp.compress(v, rel_eb=1e-3), comp.compress(w, rel_eb=1e-3)
            for op_name, fn in (
                    ("deriv", lambda s: H.derivative(cu, s, 0)),
                    ("laplacian", lambda s: H.laplacian(cu, s)),
                    ("div", lambda s: H.divergence([cu, cv, cw], s)),
                    ("curl", lambda s: H.curl([cu, cv, cw], s)[0])):
                refv = np.asarray(fn(Stage.F))
                scale = max(np.abs(refv).max(), 1e-12)
                errs[op_name] = max(
                    float(np.abs(np.asarray(fn(s)) - refv).max()) / scale
                    for s in (Stage.P, Stage.Q))
        for k, val in errs.items():
            row(f"table5/{name}/{k}", 0.0, f"max_rel_err={val:.2e}")


def fw_checkpoint():
    """HSZ checkpoints: bytes vs zstd-lossless + homomorphic validation."""
    import os
    import tempfile
    from repro.train import checkpoint as ckpt
    rng = np.random.default_rng(0)
    params = {"w": jnp.asarray(np.cumsum(rng.normal(0, 1e-2, (512, 256)),
                                         axis=0).astype(np.float32)),
              "b": jnp.asarray(rng.normal(0, 1e-2, (4096,)).astype(np.float32))}
    raw = sum(np.asarray(v).nbytes for v in params.values())
    for mode in ("lossless", "hsz"):
        with tempfile.TemporaryDirectory() as d:
            t0 = time.perf_counter()
            ckpt.save(d, 0, params, mode=mode, rel_eb=1e-4)
            us = (time.perf_counter() - t0) * 1e6
            step_dir = os.path.join(d, "step_00000000")
            total = sum(os.path.getsize(os.path.join(step_dir, "arrays", f))
                        for f in os.listdir(os.path.join(step_dir, "arrays")))
            row(f"fw_ckpt/{mode}", us, f"bytes={total} ratio={raw/total:.2f}")


def fw_batched_analytics():
    """Batched vmap analytics vs an equal-work per-field jitted loop.

    Same fields, same op, same stage: the batched engine issues ONE dispatch
    (stack fused into the compiled program) where the loop issues one per
    field.  The workload is the serving regime this engine exists for — many
    small same-layout fields (timestep/variable tiles), where per-call
    dispatch dominates — so the tile size is fixed rather than scaled by
    ``--scale`` (per-op throughput vs size is covered by fig3-12).
    """
    from repro.analytics import BatchedAnalytics, plan_stage

    batch, tile = 64, (64, 64)
    for name in ("hszp_nd", "hszx_nd"):
        comp = by_name(name)
        fields = [comp.compress(jnp.asarray(synth_field("Ocean", 0, tile, seed=i)),
                                rel_eb=1e-2) for i in range(batch)]
        eng = BatchedAnalytics()
        for op_name, op in (("mean", H.mean), ("std", H.std),
                            ("derivative", lambda c, s: H.derivative(c, s, 0))):
            stage = plan_stage(comp.scheme, op_name)
            us_batched, _ = timeit(
                lambda fs, _o=op_name, _s=stage: eng.run(fs, _o, _s), fields)
            loop_fn = jax.jit(lambda c, s=stage, o=op: o(c, s))

            def per_field_loop(fs):
                return [loop_fn(c) for c in fs]

            us_loop, _ = timeit(per_field_loop, fields)
            row(f"fw_batched_analytics/{name}/{op_name}", us_batched,
                f"loop_us={us_loop:.1f} speedup={us_loop / us_batched:.2f}x "
                f"batch={batch} stage={stage.name}")


def fw_fused_analytics():
    """Fused op sets vs sequential single-op queries at one shared stage.

    Same fields, same ops, same stage: the fused program lowers the whole op
    set onto ONE stage reconstruction (``repro.core.oplib``) and issues one
    dispatch, where the sequential baseline re-decodes per op and dispatches
    per op.  The fields are *encoded* (bit-packed) — the paper's serving
    representation — so every sequential op pays the payload unpack the
    fused program pays once.  Both sides run through the batched engine
    (warm jit cache), so the speedup isolates exactly what fusion saves:
    the repeated decode + recorrelation prelude and the per-op dispatch
    overhead.  Rows cover both shared stages (② and ③): how much fusion
    saves is stage-dependent — stage ③ shares the *whole* recorrelation
    pass, stage ② only the decode plus whatever intermediates the set has
    in common — and the calibrated joint planner exists precisely to route
    an op set to the stage where the shared prelude wins.  Like
    ``fw_batched_analytics`` this pins the serving regime (many small
    same-layout fields) rather than scaling with ``--scale``.
    """
    from repro.analytics import BatchedAnalytics

    batch, tile = 32, (64, 64)
    ops = ("mean", "std", "laplacian")
    for name in ("hszp_nd", "hszx_nd"):
        comp = by_name(name)
        cs = [comp.compress(jnp.asarray(synth_field("Ocean", 0, tile, seed=i)),
                            rel_eb=1e-2) for i in range(batch)]
        bits = max(comp.max_bits(c) for c in cs)
        fields = [comp.encode(c, bits=bits) for c in cs]
        eng = BatchedAnalytics()
        for stage, tag in ((Stage.P, "p"), (Stage.Q, "q")):
            us_fused = best_of(lambda fs, s=stage: eng.run(fs, ops, s),
                               fields)

            def sequential(fs, s=stage):
                return [eng.run(fs, op, s) for op in ops]

            us_seq = best_of(sequential, fields)
            speedup = us_seq / us_fused
            row_name = f"fw_fused_analytics/{name}/{'+'.join(ops)}-{tag}"
            row(row_name, us_fused,
                f"seq_us={us_seq:.1f} speedup={speedup:.2f}x batch={batch}")
            FUSED_JSON.append({"name": row_name, "scheme": name,
                               "stage": stage.name, "us": round(us_fused, 1),
                               "speedup": round(speedup, 3)})


def fw_expr_analytics():
    """Expression DAGs vs naive per-leaf recompute of the same derived ops.

    Three classic derived quantities over one encoded (u, v) velocity pair —
    vorticity ``ddx(v) - ddy(u)``, divergence ``ddx(u) + ddy(v)`` and the
    stretching deformation ``ddx(u) - ddy(v)`` — as ONE expression program
    (DESIGN.md §10): four distinct derivative nodes over two leaves, each
    leaf reconstructed exactly once, one compiled dispatch for all three
    roots.  The naive baseline spells the same math the only way the flat
    API allows: one single-derivative query per node (four dispatches, four
    stage reconstructions — u and v each unpacked and recorrelated twice)
    plus host-side combines.  Both sides run through warmed engine caches,
    so the speedup isolates what the DAG compiler saves: the duplicated
    leaf preludes and the per-node dispatch overhead.  Rows cover both
    shared stages (② and ③) per scheme; like the other fw serving benches
    the tile is pinned (per-op throughput vs size is covered by fig3-12).
    """
    from repro.analytics import query
    from repro.analytics.engine import BatchedAnalytics
    from repro.core import expr

    tile = (96, 96)
    for name in ("hszp_nd", "hszx_nd"):
        comp = by_name(name)
        u = comp.encode(comp.compress(
            jnp.asarray(synth_field("Ocean", 0, tile, seed=0)), rel_eb=1e-2))
        v = comp.encode(comp.compress(
            jnp.asarray(synth_field("Ocean", 1, tile, seed=1)), rel_eb=1e-2))
        ddx_u, ddy_u = expr.derivative(u, axis=0), expr.derivative(u, axis=1)
        ddx_v, ddy_v = expr.derivative(v, axis=0), expr.derivative(v, axis=1)
        roots = [ddx_v - ddy_u,   # vorticity
                 ddx_u + ddy_v,   # divergence
                 ddx_u - ddy_v]   # stretching deformation
        singles = [ddx_v, ddy_u, ddx_u, ddy_v]
        for stage, tag in ((Stage.P, "p"), (Stage.Q, "q")):
            eng = BatchedAnalytics()
            us_expr = best_of(lambda s=stage: query(
                exprs=roots, stage=s, engine=eng).values)

            eng2 = BatchedAnalytics()

            def naive(s=stage):
                dvx, duy, dux, dvy = [
                    query(exprs=[e], stage=s, engine=eng2).values[0]
                    for e in singles]
                return [dvx - duy, dux + dvy, dux - dvy]

            us_naive = best_of(naive)
            speedup = us_naive / us_expr
            row_name = f"fw_expr_analytics/{name}/vort+div+stretch-{tag}"
            row(row_name, us_expr,
                f"naive_us={us_naive:.1f} speedup={speedup:.2f}x "
                f"roots=3 leaves=2 nodes=4")
            EXPR_JSON.append({"name": row_name, "scheme": name,
                              "stage": stage.name, "us": round(us_expr, 1),
                              "naive_us": round(us_naive, 1),
                              "speedup": round(speedup, 3)})


def fw_region_analytics():
    """Region queries vs full-field queries at the same (scheme, op, stage).

    A ~10% window of the 2-D Ocean field: the region path unpacks only the
    window's closure blocks and computes only window elements, so its latency
    scales with the window (blockmean) or closure (Lorenzo prefix hull), not
    the field.  ``words`` reports the payload-gather sparsity that drives it.

    Caveat the rows keep honest: the full-field Lorenzo stage-② mean is
    already one contiguous rank-1 pass, so its region variant (scattered
    hull gather) can lose at large sizes — the calibrated region-aware cost
    model exists precisely to route such queries to a stage whose region
    closure wins (here ③).
    """
    dims = dataset_dims("Ocean", SCALE)
    data = jnp.asarray(synth_field("Ocean", 0, dims))
    for name in ("hszx_nd", "hszp_nd"):
        comp = by_name(name)
        c = comp.compress(data, rel_eb=1e-2)
        e = comp.encode(c)
        # ~31.6% extent per axis => ~10% of the area, away from the origin
        region = tuple((s // 8, min(s, s // 8 + max(4, int(s * 0.316))))
                       for s in c.shape)
        ops = (("mean", lambda enc, s, r: H.mean(enc, s, region=r)),
               ("deriv", lambda enc, s, r: H.derivative(enc, s, 0, region=r)))
        for op_name, op in ops:
            for stage, tag in ((Stage.P, "p"), (Stage.Q, "q")):
                fn_full = jax.jit(lambda enc, s=stage, o=op: o(enc, s, None))
                us_full, _ = timeit(fn_full, e)
                fn_reg = jax.jit(lambda enc, s=stage, o=op, r=region: o(enc, s, r))
                us_reg, _ = timeit(fn_reg, e)
                closure = region_mod.op_closure(comp.scheme, "derivative"
                                                if op_name == "deriv" else "mean",
                                                stage, 0)
                plan = region_mod.plan_region(e, region, closure)
                words = plan.payload_gather(e.bits).n_words
                row(f"fw_region_analytics/{name}/{op_name}-{tag}", us_reg,
                    f"full_us={us_full:.1f} speedup={us_full / us_reg:.2f}x "
                    f"words={words}/{e.payload.size} window=10%")


def fw_store_analytics():
    """Hot-cache store-backed fused queries vs cold (storeless) queries.

    Same field, same op set, same stage: the cold program unpacks the
    payload and recorrelates on *every* call; the hot program is seeded
    from the field's resident :class:`~repro.store.MaterializedStage` —
    the reconstruction happened once, at materialization — so each call
    pays only the op postludes.  Both sides run through warmed jit caches,
    so the speedup isolates exactly what residency saves: the per-call
    stage reconstruction.  Stage ③ is the serving sweet spot (the cached
    intermediate replaces unpack + the whole recorrelation pass) and the
    one the CI gate pins at >= 2x.
    """
    from repro.analytics import BatchedAnalytics, query
    from repro.store import FieldStore

    dims = dataset_dims("Ocean", SCALE)
    data = jnp.asarray(synth_field("Ocean", 0, dims))
    # two dashboard shapes: a stats-only set (light flat-reduction
    # postludes, so residency saves nearly the whole call) and the heavier
    # stencil set — the gate takes each scheme's best, the rows show both
    for name in ("hszp_nd", "hszx_nd"):
        comp = by_name(name)
        e = comp.encode(comp.compress(data, rel_eb=1e-2))
        for ops in (("mean", "std"), ("mean", "std", "laplacian")):
            for stage, tag in ((Stage.Q, "q"), (Stage.F, "f")):
                eng = BatchedAnalytics()
                store = FieldStore()
                store.put("bench/ocean0", e)
                # time .values (a pytree) so block_until_ready really blocks
                us_cold = best_of(lambda s=stage, o=ops: query(
                    [e], o, stage=s, engine=eng).values)
                # the first store-backed call materializes (the one
                # reconstruction of the field's lifetime) and compiles the
                # seeded program
                query(["bench/ocean0"], ops, stage=stage, engine=eng,
                      store=store)
                us_hot = best_of(lambda s=stage, o=ops: query(
                    ["bench/ocean0"], o, stage=s, engine=eng,
                    store=store).values)
                speedup = us_cold / us_hot
                row_name = f"fw_store_analytics/{name}/{'+'.join(ops)}-{tag}"
                row(row_name, us_hot,
                    f"cold_us={us_cold:.1f} speedup={speedup:.2f}x "
                    f"hits={store.stats.hits} cached_MB="
                    f"{store.cache_bytes_in_use / 1e6:.1f}")
                STORE_JSON.append({"name": row_name, "scheme": name,
                                   "stage": stage.name,
                                   "us": round(us_hot, 1),
                                   "cold_us": round(us_cold, 1),
                                   "speedup": round(speedup, 3)})


def fw_stream_analytics():
    """Streaming ingest: incremental append+query vs re-encode-from-scratch.

    The incremental path appends ONE compressed slab and merges its integer
    summary into the stream's resident :class:`~repro.stream.TemporalSummary`
    (``repro.stream``, DESIGN.md §9), then answers the temporal op set from
    the merged summary; the baseline re-encodes the *whole* concatenated
    history as a fresh field on every step and recomputes from scratch —
    which is what a store without streaming support would have to do.  Both
    sides run through warmed jit caches and identical op machinery, so the
    speedup isolates exactly what incrementality saves: re-compressing and
    re-reconstructing the history.  Results are bit-identical by the
    integer-merge contract (pinned in ``tests/test_stream.py``); the CI
    gate holds the per-scheme speedup at >= 2x.
    """
    from repro.analytics import BatchedAnalytics, query
    from repro.stream import StreamFieldStore, TemporalField

    # like the other fw serving benches this pins the streaming regime (a
    # steady feed of moderate timestep tiles) instead of scaling the tile
    # with --scale; per-op throughput vs size is covered by fig3-12
    # the baseline history length matches the stream's slab count midway
    # through the incremental measurement (it keeps growing; the
    # incremental cost does not)
    k, n_prefill, n_baseline, tile = 3, 4, 8, (96, 96)
    ops = ("tmean", "tstd", "tdelta")
    # feed sizing: prefill + 2 warm appends + best_of's 1 + max(5, REPS)
    # timed appends (so high --reps never exhausts the stream), + slack
    n_feed = n_prefill + 3 + max(5, REPS) + 2
    slab_data = [np.stack([synth_field("Ocean", 0, tile, seed=i * k + t)
                           for t in range(k)]).astype(np.float32)
                 for i in range(n_feed)]
    for name in COMPRESSORS:
        comp = by_name(name)
        eng = BatchedAnalytics()
        store = StreamFieldStore(engine=eng)
        tf = TemporalField(comp, rel_eb=1e-2, bits=16)
        store.put_temporal("stream/ocean", tf)
        feed = iter(slab_data)
        for _ in range(n_prefill):
            store.append("stream/ocean", next(feed))
        # warm: one cold query (summary build) + one steady append cycle
        query(["stream/ocean"], list(ops), store=store, engine=eng)
        store.append("stream/ocean", next(feed))
        query(["stream/ocean"], list(ops), store=store, engine=eng)

        def inc_step():
            store.append("stream/ocean", next(feed))
            return query(["stream/ocean"], list(ops), store=store,
                         engine=eng).values

        us_inc = best_of(inc_step, k=5)

        history = np.concatenate(slab_data[:n_baseline], axis=0)
        eng2 = BatchedAnalytics()

        def reencode_step():
            fresh = TemporalField(comp, eps=tf.eps, bits=16)
            fresh.append(history)           # re-encode the whole history
            return query([fresh], list(ops), engine=eng2).values

        us_re = best_of(reencode_step, k=5)
        speedup = us_re / us_inc
        row_name = f"fw_stream_analytics/{name}/append+query"
        row(row_name, us_inc,
            f"reencode_us={us_re:.1f} speedup={speedup:.2f}x "
            f"slabs={tf.n_slabs} steps={tf.n_steps} "
            f"merges={store.incremental_merges}")
        STREAM_JSON.append({"name": row_name, "scheme": name,
                            "us": round(us_inc, 1),
                            "reencode_us": round(us_re, 1),
                            "speedup": round(speedup, 3)})


def fw_shard_analytics():
    """Sharded vs single-device analytics: per-shard bytes touched + wall.

    The sharded store's tentpole claim is I/O locality, not CPU speed: a
    region query over a block-striped field gathers payload words only from
    the shards whose stripes the region closure covers, so the *max
    per-shard* bytes touched — the quantity that bounds a real multi-host
    deployment's per-node decode work — drops well below the single-device
    gather.  Two row kinds per scheme:

    * ``region`` — one region op set (mean at ③, window = 1/4 of the rows,
      away from the origin) through :meth:`ShardPrograms.region_compute`
      vs the jitted single-device op.  Bytes come from the *logical*
      8-shard :class:`~repro.shard.BlockPlacement` (the CI placement
      basis, independent of how many XLA devices this process has);
      ``bytes_ratio = max_shard_bytes / single_bytes`` is the gated value
      (< 0.5 on every scheme — the region covers >= 2 stripe units of
      every scheme's striping, so no shard owns more than half its words).
    * ``temporal`` — a cold full-window summary rebuild through the
      sharded banded path vs the single-device ``_cold_summary`` route;
      ``max_band_frac`` reports the largest fraction of window rows any
      one shard reconstructs under the logical placement.

    Wall times use a mesh over however many devices exist (1 on a plain
    CPU run, 8 under ``--xla_force_host_platform_device_count=8``) and are
    informational on CPU — shard_map over virtual devices serializes the
    per-shard work.  Results are bit-identical by construction
    (``tests/test_shard.py``), so the rows compare cost only; the geometry
    is pinned (like the other fw serving benches) so the byte accounting
    is the same at every ``--scale``.
    """
    from repro.analytics.engine import BatchedAnalytics
    from repro.core import oplib
    from repro.launch.mesh import make_analytics_mesh
    from repro.shard import (BlockPlacement, ShardPrograms, ShardedFieldStore,
                             spatial_bands)
    from repro.stream import StreamFieldStore, TemporalField
    from repro.stream.query import _cold_summary

    n_logical = 8
    mesh = make_analytics_mesh(min(n_logical, len(jax.devices())))
    n_mesh = mesh.devices.size

    tile = (256, 192)                     # 16 block-rows for the nd schemes
    data = jnp.asarray(synth_field("Ocean", 0, tile))
    region = ((tile[0] // 4, tile[0] // 2), (0, tile[1]))  # 1/4 of the rows
    stage = Stage.Q
    for name in COMPRESSORS:
        comp = by_name(name)
        e = comp.encode(comp.compress(data, rel_eb=1e-2))
        cl = oplib.set_closure(("mean",), e.scheme, stage, 0)
        plan = region_mod.plan_region(
            e, region_mod.normalize_region(region, e.shape), cl)
        acct = BlockPlacement.of(e, n_logical).payload_bytes(plan, e.bits)
        ratio = acct["max_shard_bytes"] / max(acct["single_bytes"], 1)

        progs = ShardPrograms(mesh)
        pm = BlockPlacement.of(e, n_mesh)
        stripes = [progs.shard_payload(e, pm)]
        us_sh = best_of(lambda: progs.region_compute(
            e, ("mean",), stage, region=region, placements=[pm],
            stripes=stripes)["mean"])
        us_single = best_of(
            jax.jit(lambda enc: H.mean(enc, stage, region=region)), e)
        row_name = f"fw_shard_analytics/{name}/region-mean-q"
        row(row_name, us_sh,
            f"single_us={us_single:.1f} "
            f"max_shard_bytes={acct['max_shard_bytes']} "
            f"single_bytes={acct['single_bytes']} bytes_ratio={ratio:.3f} "
            f"participants={len(acct['participants'])}/{n_logical}")
        SHARD_JSON.append({
            "name": row_name, "scheme": name, "kind": "region",
            "us_sharded": round(us_sh, 1), "us_single": round(us_single, 1),
            "max_shard_bytes": int(acct["max_shard_bytes"]),
            "single_bytes": int(acct["single_bytes"]),
            "bytes_ratio": round(ratio, 4),
            "participants": len(acct["participants"]),
            "n_shards": n_logical})

    k, n_slabs, ttile = 3, 4, (96, 96)
    slab_data = [np.stack([synth_field("Ocean", 0, ttile, seed=i * k + t)
                           for t in range(k)]).astype(np.float32)
                 for i in range(n_slabs)]
    for name in COMPRESSORS:
        comp = by_name(name)
        ref = StreamFieldStore(engine=BatchedAnalytics())
        sh = ShardedFieldStore(mesh, engine=BatchedAnalytics())
        ref.put_temporal("shard/stream", TemporalField(comp, rel_eb=1e-2))
        sh.put_temporal("shard/stream", TemporalField(comp, rel_eb=1e-2))
        for s in slab_data:
            ref.append("shard/stream", jnp.asarray(s))
            sh.append("shard/stream", jnp.asarray(s))

        def cold(store):
            store.invalidate("shard/stream")
            return store.temporal_summary("shard/stream")

        us_single = best_of(cold, ref, k=5)
        us_sh = best_of(cold, sh, k=5)
        slab0 = sh.get("shard/stream").slabs[0]
        p8 = BlockPlacement.of(slab0, n_logical, axis=1)
        per = np.zeros(n_logical)
        for owner, _, _, breg in spatial_bands(slab0, p8):
            per[owner] += breg[0][1] - breg[0][0]
        frac = float(per.max()) / slab0.shape[1]
        row_name = f"fw_shard_analytics/{name}/temporal-summary"
        row(row_name, us_sh,
            f"single_us={us_single:.1f} slabs={n_slabs} "
            f"max_band_frac={frac:.3f}")
        SHARD_JSON.append({
            "name": row_name, "scheme": name, "kind": "temporal",
            "us_sharded": round(us_sh, 1), "us_single": round(us_single, 1),
            "max_band_frac": round(frac, 4), "n_shards": n_logical})


#: jaxpr primitives that are elementwise or pure layout — free under the
#: same fusion assumption ``hlo_analysis.ELEMENTWISE`` makes for HLO ops.
_FREE_PRIMS = frozenset({
    "add", "sub", "mul", "div", "rem", "neg", "sign", "abs", "max", "min",
    "and", "or", "xor", "not", "shift_left", "shift_right_logical",
    "shift_right_arithmetic", "eq", "ne", "lt", "le", "gt", "ge",
    "select_n", "convert_element_type", "integer_pow", "exp", "log",
    "sqrt", "rsqrt", "floor", "ceil", "round", "stop_gradient", "copy",
    "reshape", "squeeze", "broadcast_in_dim", "slice", "concatenate",
    "pad", "iota", "transpose",
})


def _jaxpr_bytes(jaxpr) -> int:
    """HBM-bytes proxy of a (native-lowering) jaxpr: summed output bytes of
    every non-elementwise equation, recursing through call wrappers.

    The counterpart of ``hlo_analysis.analyze`` for programs containing
    ``pallas_call`` equations, which cannot be measured from compiled HLO
    on CPU: interpret mode emulates the grid with per-step dynamic-slice /
    full-array dynamic-update-slice pairs whose HLO bytes are pure
    emulation artifact (24-34x the real kernel I/O).  A pallas_call counts
    its *outputs* only — its operands are the outputs of counted producers
    (the payload word gather, halo gathers) or program arguments, exactly
    as HLO op outputs chain in the proxy.
    """
    total = 0
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "pallas_call":
            total += sum(int(np.prod(v.aval.shape)) * v.aval.dtype.itemsize
                         for v in eqn.outvars)
            continue
        subs = []
        for v in eqn.params.values():
            for s in (v if isinstance(v, (tuple, list)) else (v,)):
                inner = getattr(s, "jaxpr", s)
                if hasattr(inner, "eqns"):
                    subs.append(inner)
        if subs:
            total += sum(_jaxpr_bytes(s) for s in subs)
            continue
        if name in _FREE_PRIMS:
            continue
        total += sum(int(np.prod(v.aval.shape)) * v.aval.dtype.itemsize
                     for v in eqn.outvars)
    return total


def fw_kernel_analytics():
    """Fused Pallas decode+op kernels vs the XLA lowering, per scheme family.

    One covered cell per op family (derivative at ③, laplacian at the
    family's covered stage), Ocean 2-D, both nd schemes, *Encoded*
    containers passed as real jit arguments (a closed-over container
    constant-folds the whole program away).  Two measurements per cell:

    * wall time of the jitted single-op program, fused backend vs
      ``REPRO_KERNELS=off`` — informational only on CPU, where the kernels
      run under interpret-mode emulation;
    * the HBM-bytes proxy — the tentpole's gated claim.  The XLA side
      comes from ``hlo_analysis.analyze`` on the compiled program; the
      fused side from :func:`_jaxpr_bytes` on the ``native``-mode jaxpr
      (traced, never compiled — CPU has no native Pallas lowering), the
      same output-bytes-of-non-elementwise-ops accounting.  The fused
      program reads gathered payload words and writes stencil planes; the
      XLA program materializes the unpacked residuals and the full-field
      integer recorrelation intermediate in between, so the ratio must
      clear the CI gate (< 0.9) for *both* families.

    Results are bit-identical by construction
    (``tests/test_fused_kernels.py``), so the rows compare cost only.
    """
    from repro.kernels import ops as kops
    from repro.launch import hlo_analysis

    dims = dataset_dims("Ocean", SCALE)[:2]
    data = jnp.asarray(synth_field("Ocean", 0, dims))
    cells = [("derivative", Stage.Q,
              lambda e: H.derivative(e, Stage.Q, 0)),
             ("laplacian", Stage.P,
              lambda e: H.laplacian(e, Stage.P))]
    for name in ("hszp_nd", "hszx_nd"):
        comp = by_name(name)
        enc = comp.encode(comp.compress(data, rel_eb=1e-3))
        for op, stage, call in cells:
            us_fused = best_of(jax.jit(call), enc)
            with kops.override_mode("native"):
                bytes_fused = _jaxpr_bytes(jax.make_jaxpr(call)(enc).jaxpr)
            with kops.override_mode("off"):
                xla_fn = jax.jit(call)
                us_xla = best_of(xla_fn, enc)
                bytes_xla = hlo_analysis.analyze(
                    xla_fn.lower(enc).compile().as_text())["bytes_proxy"]
            row_name = f"fw_kernel_analytics/{name}/{op}-{stage.name.lower()}"
            row(row_name, us_fused,
                f"xla_us={us_xla:.1f} bytes_fused={bytes_fused:.3g} "
                f"bytes_xla={bytes_xla:.3g} "
                f"bytes_ratio={bytes_fused / max(bytes_xla, 1):.3f}")
            KERNEL_JSON.append({
                "name": row_name, "scheme": name, "op": op,
                "stage": stage.name, "us_fused": round(us_fused, 1),
                "us_xla": round(us_xla, 1),
                "bytes_fused": round(bytes_fused),
                "bytes_xla": round(bytes_xla),
                "bytes_ratio": round(bytes_fused / max(bytes_xla, 1), 4)})


def fw_collective_bytes():
    """Wire bytes of the gradient all-reduce: f32 baseline vs hom-int16.

    Static accounting (per the ring cost model, 2x payload); the dry-run
    HLO confirms the wire dtype (EXPERIMENTS.md §Perf).
    """
    from repro.comm import bit_budget
    n_params = 4_000_000_000
    for world in (16, 256, 512):
        f32 = 2 * n_params * 4
        i16 = 2 * n_params * 2
        bits = bit_budget(world)
        row(f"fw_collective/world{world}", 0.0,
            f"f32_GB={f32/1e9:.1f} hom16_GB={i16/1e9:.1f} budget_bits={bits}")


BENCHES = [fig2_compression_ratio, fig34_decompression, fig58_statistics,
           fig910_differentiation, fig1112_multivariate, table4_breakdown,
           table5_op_errors, fw_batched_analytics, fw_fused_analytics,
           fw_expr_analytics, fw_region_analytics, fw_store_analytics,
           fw_stream_analytics, fw_kernel_analytics, fw_shard_analytics,
           fw_checkpoint, fw_collective_bytes]


def select_benches(benches, filter_spec: str | None, only: str | None):
    """Row families selected by ``--filter`` (comma-separated name prefixes)
    and ``--only`` (substring, kept for compatibility)."""
    out = list(benches)
    if filter_spec:
        prefixes = [p for p in filter_spec.split(",") if p]
        out = [b for b in out
               if any(b.__name__.startswith(p) for p in prefixes)]
        if not out:
            known = ", ".join(b.__name__ for b in benches)
            raise SystemExit(
                f"--filter {filter_spec!r} matches no row family; "
                f"families: {known}")
    if only:
        out = [b for b in out if only in b.__name__]
    return out


def main() -> None:
    global SCALE, REPS
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=int, default=8)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--only", default=None)
    ap.add_argument("--filter", default=None, metavar="PREFIX[,PREFIX...]",
                    help="run only row families whose name starts with a "
                         "given prefix (e.g. fw_store or fig2,fw_)")
    ap.add_argument("--json-dir", default=None, metavar="DIR",
                    help="write every machine-readable row family into DIR "
                         "under its canonical name (BENCH_fused.json, "
                         "BENCH_expr.json, BENCH_store.json, "
                         "BENCH_stream.json, BENCH_kernel.json, "
                         "BENCH_shard.json) — the one flag the CI gates "
                         "consume; families not selected by --filter come "
                         "out as empty lists")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="deprecated alias: write only the "
                         "fw_fused_analytics rows to PATH (use --json-dir)")
    ap.add_argument("--json-expr", default=None, metavar="PATH",
                    help="deprecated alias: write only the "
                         "fw_expr_analytics rows to PATH (use --json-dir)")
    ap.add_argument("--json-store", default=None, metavar="PATH",
                    help="deprecated alias: write only the "
                         "fw_store_analytics rows to PATH (use --json-dir)")
    ap.add_argument("--json-stream", default=None, metavar="PATH",
                    help="deprecated alias: write only the "
                         "fw_stream_analytics rows to PATH (use --json-dir)")
    ap.add_argument("--json-kernel", default=None, metavar="PATH",
                    help="deprecated alias: write only the "
                         "fw_kernel_analytics rows to PATH (use --json-dir)")
    args = ap.parse_args()
    SCALE, REPS = args.scale, args.reps
    from repro.launch.cache import use_compile_cache
    use_compile_cache(os.path.join(os.path.dirname(__file__), ".."))
    print("name,us_per_call,derived")
    for bench in select_benches(BENCHES, args.filter, args.only):
        t0 = time.time()
        bench()
        print(f"# {bench.__name__} done in {time.time()-t0:.1f}s", flush=True)
        while ROWS:
            name, us, derived = ROWS.pop(0)
            print(f"{name},{us:.1f},{derived}")
    if args.json_dir is not None:
        os.makedirs(args.json_dir, exist_ok=True)
        for fname, rows_json in JSON_FILES:
            with open(os.path.join(args.json_dir, fname), "w") as f:
                json.dump(rows_json, f, indent=2)
    aliases = (("--json", args.json, FUSED_JSON),
               ("--json-expr", args.json_expr, EXPR_JSON),
               ("--json-store", args.json_store, STORE_JSON),
               ("--json-stream", args.json_stream, STREAM_JSON),
               ("--json-kernel", args.json_kernel, KERNEL_JSON))
    for flag, path, rows_json in aliases:
        if path is None:
            continue
        warnings.warn(f"{flag} is a deprecated alias; use --json-dir DIR "
                      "(writes every BENCH_*.json)", DeprecationWarning,
                      stacklevel=2)
        with open(path, "w") as f:
            json.dump(rows_json, f, indent=2)


if __name__ == "__main__":
    main()
