"""The paper's end-to-end pipeline (Fig. 1) over its five benchmark datasets.

Compresses synthetic analogues of Ocean/Miranda/Hurricane/NYX/JHTDB, then
runs all six analytical operations at their cheapest supported stage and
reports ratio / throughput / error vs full decompression.

    PYTHONPATH=src python examples/homomorphic_analytics.py [--scale 16]
"""
import argparse
import os
import time

import numpy as np
import jax

from repro.analytics import query
from repro.core import Stage, by_name, homomorphic as H
from repro.core import region as region_mod
from repro.data.scientific import DATASETS, ScientificStore, dataset_dims
from repro.serve import AnalyticsFrontend, AnalyticsRequest
from repro.store import FieldStore
from repro.launch.cache import use_compile_cache


def main():
    use_compile_cache(os.path.join(os.path.dirname(__file__), ".."))
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=int, default=16)
    ap.add_argument("--rel-eb", type=float, default=1e-3)
    args = ap.parse_args()

    print(f"{'dataset':10s} {'dims':>18s} {'comp':8s} {'ratio':>6s} "
          f"{'mean(M/P)':>10s} {'std(P)':>8s} {'deriv(Q)':>9s} {'max err':>9s}")
    for ds in DATASETS:
        dims = dataset_dims(ds, args.scale)
        for comp_name in ("hszp_nd", "hszx_nd"):
            store = ScientificStore(compressor_name=comp_name,
                                    scale=args.scale, rel_eb=args.rel_eb)
            c = store.get(ds, 0).open()
            comp = by_name(comp_name)
            ratio = float(comp.compression_ratio(c))
            raw = np.asarray(store.raw(ds, 0))

            stage1 = Stage.M if c.scheme.is_blockmean else Stage.P
            t0 = time.perf_counter()
            mu = float(H.mean(c, stage1))
            t_mu = time.perf_counter() - t0
            sd = float(H.std(c, Stage.P))
            t0 = time.perf_counter()
            d0 = np.asarray(H.derivative(c, Stage.Q, 0))
            t_d = time.perf_counter() - t0

            ref0 = np.asarray(H.derivative(c, Stage.F, 0))
            err = max(abs(mu - raw.mean()),
                      abs(sd - raw.std(ddof=1)),
                      float(np.abs(d0 - ref0).max()))
            print(f"{ds:10s} {str(dims):>18s} {comp_name:8s} {ratio:6.2f} "
                  f"{t_mu*1e3:9.2f}ms {sd:8.4f} {t_d*1e3:8.2f}ms {err:9.2e}")

    print("\nMulti-operation reuse (paper §VI-C.6): one lowered stage-③ "
          "reconstruction feeds gradient + curl on NYX velocity:")
    store = ScientificStore(compressor_name="hszp_nd", scale=args.scale)
    comps = [store.get("NYX", i).open() for i in range(3)]
    t0 = time.perf_counter()
    grads = [H.gradient(cc, Stage.Q) for cc in comps]  # 9 derivatives, 3 decodes
    curl = H.curl(comps, Stage.Q)
    jax.block_until_ready((grads, curl))
    print(f"3 gradients + 3-component curl at stage Q: "
          f"{(time.perf_counter()-t0)*1e3:.1f} ms")

    print("\nBatched analytics (repro.analytics): all Hurricane variables, "
          "one vmapped dispatch, stage planned automatically:")
    store = ScientificStore(compressor_name="hszx_nd", scale=args.scale)
    n_vars = DATASETS["Hurricane"][0]
    fields = [store.get("Hurricane", i).open() for i in range(n_vars)]
    res = query(fields, "mean", stage="auto")        # warm the jit cache
    t0 = time.perf_counter()
    res = query(fields, "mean", stage="auto")
    jax.block_until_ready(res.values)
    t_batch = time.perf_counter() - t0
    print(f"  mean over {n_vars} variables at stage {res.stages[0].name}: "
          f"{t_batch*1e3:.2f} ms ({res.n_batches} dispatch)")

    print("\nFused multi-op dashboard query: mean + std + laplacian over "
          "every variable from ONE stage reconstruction per layout group "
          "(bit-packed fields: sequential ops re-decode, the fused set "
          "decodes once):")
    comp_x = by_name("hszx_nd")
    bits = max(comp_x.max_bits(c) for c in fields)
    enc = [comp_x.encode(c, bits=bits) for c in fields]
    dashboard = ["mean", "std", "laplacian"]
    fused = query(enc, dashboard)                    # warm both jit caches
    for op in dashboard:
        query(enc, op, stage=fused.stages[0][op])

    def best_of(fn, k=3):                            # min-of-k: robust timing
        best = float("inf")
        for _ in range(k):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            best = min(best, time.perf_counter() - t0)
        return best

    t_fused = best_of(lambda: [v for d in query(enc, dashboard).values
                               for v in d.values()])
    t_seq = best_of(lambda: [v for op in dashboard
                             for v in query(enc, op,
                                            stage=fused.stages[0][op]).values])
    stage_names = {op: s.name for op, s in fused.stages[0].items()}
    print(f"  {len(dashboard)} ops x {n_vars} variables at stages "
          f"{stage_names}: fused {t_fused*1e3:.2f} ms "
          f"({fused.n_dispatches} dispatch) vs sequential {t_seq*1e3:.2f} ms "
          f"({len(dashboard)} dispatches); "
          f"var0 mean={float(fused.values[0]['mean']):.4f} "
          f"std={float(fused.values[0]['std']):.4f}")

    print("\nBlock-sparse region queries (windowed/ROI workload): a ~10% "
          "window decodes only its covering blocks:")
    c = fields[0]
    region = tuple((s // 4, s // 4 + max(4, int(s * 0.32))) for s in c.shape)
    e = by_name("hszx_nd").encode(c)
    plan = region_mod.plan_region(e, region, "cover")
    words = plan.payload_gather(e.bits).n_words
    full_fn = jax.jit(lambda enc: H.mean(enc, Stage.P))
    reg_fn = jax.jit(lambda enc: H.mean(enc, Stage.P, region=region))
    jax.block_until_ready(full_fn(e)), jax.block_until_ready(reg_fn(e))
    t0 = time.perf_counter()
    jax.block_until_ready(full_fn(e))
    t_full = time.perf_counter() - t0
    t0 = time.perf_counter()
    mu_win = float(reg_fn(e))
    t_reg = time.perf_counter() - t0
    print(f"  window {region}: mean={mu_win:.4f} in {t_reg*1e3:.2f} ms vs "
          f"{t_full*1e3:.2f} ms full-field ({words}/{e.payload.size} payload "
          f"words gathered)")

    print("\nServing front-end:")
    fe = AnalyticsFrontend()
    for i, c in enumerate(fields):
        fe.add_request(AnalyticsRequest(uid=i, fields=c, op="std"))
    fe.add_request(AnalyticsRequest(uid=100, fields=fields[0], op="laplacian"))
    fe.add_request(AnalyticsRequest(uid=101, fields=fields[0], op="std",
                                    region=region))
    fe.add_request(AnalyticsRequest(uid=102, fields=fields[0],
                                    op=["mean", "std", "laplacian"]))
    done = fe.run_until_drained()
    stds = [f"{float(r.result):.3f}" for r in done if r.op == "std" and r.region is None]
    win_std = next(float(r.result) for r in done if r.region is not None)
    multi = next(r for r in done if r.uid == 102)
    print(f"  {len(done)} requests drained "
          f"({fe.engine.cache_size} compiled programs); stds: {stds[:4]} ...; "
          f"window std: {win_std:.3f}; fused request: "
          f"mean={float(multi.result['mean']):.3f} "
          f"std={float(multi.result['std']):.3f} at one "
          f"stage-{multi.result_stage['mean'].name} reconstruction")

    print("\nStore-backed serving (repro.store): fields registered under "
          "string ids, one stage reconstruction per field *lifetime* — "
          "clients stop shipping arrays:")
    fstore = FieldStore(cache_bytes=256 << 20)
    for i, ec in enumerate(enc):
        fstore.put(f"hurricane/var{i}", ec)
    ids = [f"hurricane/var{i}" for i in range(len(enc))]
    # cold: the first store-backed query materializes (and the jit warms)
    res = query(ids, dashboard, stage=Stage.Q, store=fstore)
    t_cold = best_of(lambda: [v for d in query(enc, dashboard, stage=Stage.Q)
                              .values for v in d.values()])
    t_hot = best_of(lambda: [v for d in query(ids, dashboard, stage=Stage.Q,
                                              store=fstore).values
                             for v in d.values()])
    print(f"  {len(dashboard)} ops x {len(ids)} id-addressed fields: hot "
          f"cache {t_hot*1e3:.2f} ms vs {t_cold*1e3:.2f} ms storeless "
          f"({t_cold/t_hot:.1f}x); stats: {fstore.stats}, "
          f"{fstore.cache_bytes_in_use/1e6:.1f} MB resident")
    sfe = AnalyticsFrontend(store=fstore)
    sfe.add_request(AnalyticsRequest(uid=0, fields=ids[0], op=["mean", "std"]))
    sfe.add_request(AnalyticsRequest(uid=1, fields=ids[1], op=["mean", "std"]))
    done = sfe.run_until_drained()
    print(f"  2 id-addressed requests -> stage "
          f"{done[0].result_stage['mean'].name} (auto, flipped to the "
          f"resident stage), mean={float(done[0].result['mean']):.3f}")

    print("\nStreaming ingest (repro.stream): timestep batches append as "
          "compressed slabs; temporal ops merge per-slab integer summaries "
          "— only the NEW slab is ever reconstructed:")
    from repro.serve import AppendRequest
    from repro.stream import StreamFieldStore, TemporalField

    sstore = StreamFieldStore(cache_bytes=256 << 20)
    comp_p = by_name("hszp_nd")
    sstore.put_temporal("sim/temp", TemporalField(comp_p, rel_eb=1e-3))
    dims2 = dataset_dims("Ocean", args.scale)
    rng = np.random.default_rng(0)

    def timesteps(i, k=3):
        from repro.data.scientific import synth_field
        base = synth_field("Ocean", 0, dims2)
        t = np.arange(i * k, (i + 1) * k, dtype=np.float32)[:, None, None]
        return (base[None] * (1 + 0.01 * t)
                + rng.normal(0, 0.01, (k,) + base.shape)).astype(np.float32)

    sfe = AnalyticsFrontend(store=sstore)
    for i in range(4):
        sfe.add_request(AppendRequest(uid=i, field_id="sim/temp",
                                      data=timesteps(i)))
    sfe.add_request(AnalyticsRequest(uid=10, fields="sim/temp",
                                     op=["tmean", "tstd", "tdelta"]))
    done = {r.uid: r for r in sfe.run_until_drained()}
    tfield = sstore.get("sim/temp")
    # warm the incremental path (slab summarizer + merge compile once, then
    # every further append reuses them), then time one steady-state cycle
    sstore.append("sim/temp", timesteps(4))
    jax.block_until_ready(
        query(["sim/temp"], ["tmean", "tstd", "tdelta"], store=sstore).values)
    t0 = time.perf_counter()
    sstore.append("sim/temp", timesteps(5))
    hot = query(["sim/temp"], ["tmean", "tstd", "tdelta"], store=sstore)
    jax.block_until_ready(hot.values)
    t_step = time.perf_counter() - t0
    print(f"  {tfield.n_slabs} slabs / {tfield.n_steps} timesteps ingested; "
          f"steady-state append+query {t_step*1e3:.2f} ms "
          f"({sstore.incremental_merges} incremental merges, "
          f"{sstore.summary_rebuilds} rebuild); "
          f"tmean[0,0]={float(hot.values[0]['tmean'][0, 0]):.4f}, "
          f"tdelta max={float(np.abs(np.asarray(hot.values[0]['tdelta'])).max()):.4f}")


if __name__ == "__main__":
    main()
