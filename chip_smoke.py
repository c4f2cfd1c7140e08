"""Chip smoke: compress -> store -> homomorphic query, end to end on a TPU.

Drives the analytics service through the entry points a user calls
(``by_name``, ``FieldStore`` / ``StreamFieldStore`` / ``ShardedFieldStore``,
``query``, ``AnalyticsFrontend``) at the published dimensions of the paper's
Table III datasets, with every field made from ``--seed``:

* ``2d``      Ocean 2400x3600, both fields, ``hszp_nd`` and ``hszx_nd``
  (the Pallas kernel path): dashboard query at ``stage="auto"``,
  derivative / gradient at stage 3, laplacian at stage 2, frontend requests
  (one of them windowed), and every kernel cell bitwise against
  ``override_mode("off")``;
* ``3d``      Hurricane 100x500x500, all 13 variables, ``hszp_nd``: one
  batched mean/std query, divergence and curl of variables 0-2 at stage 3;
* ``stream``  a ``TemporalField`` on Ocean's shape: 3 slabs of 4 timesteps
  appended through the frontend, tmean/tstd/tdelta after each append;
* ``--chips 4`` runs only the sharded phase: NYX 512^3, velocity components
  0-2, ``ShardedFieldStore(make_analytics_mesh(4))`` against a single-device
  ``FieldStore`` holding the same data, bitwise.

Checks: the stage-4 decompression is within the error bound of the
original; each result matches numpy applied to the stage-4 decompression
within its stage's bias bound (``repro.core.error_analysis``) plus the f32
rounding the stage-4 values themselves carry; kernel cells equal the XLA
lowering bit for bit.  A failed check raises, so the script exits non-zero.

The script prints no timings (``bench/run.py`` measures the service).  The
last line of a successful run is ``{"ok": true, "device": {...}}``.  Without a TPU the script exits non-zero
and prints no result.

    python chip_smoke.py [--seed 0] [--chips 4]
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.analytics import query  # noqa: E402
from repro.core import Stage, by_name, error_analysis, expr  # noqa: E402
from repro.data.scientific import DATASETS, synth_field  # noqa: E402
from repro.kernels import ops as kops  # noqa: E402
from repro.serve import (AnalyticsFrontend, AnalyticsRequest,  # noqa: E402
                         AppendRequest)
from repro.store import FieldStore  # noqa: E402
from repro.stream import StreamFieldStore, TemporalField  # noqa: E402

REL_EB = 1e-3
F32_ULP = float(np.finfo(np.float32).eps)


class SmokeFailure(AssertionError):
    """A smoke check failed."""


def check(ok, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(*parts) -> None:
    print(*parts, flush=True)


def peak_bytes() -> str:
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak} B"


# ---------------------------------------------------------------------------
# numpy references on the stage-4 decompression
# ---------------------------------------------------------------------------

def _interior(nd: int, axis: int | None = None, off: int = 0):
    sl = [slice(1, -1)] * nd
    if axis is not None:
        sl[axis] = slice(1 + off, (-1 + off) or None)
    return tuple(sl)


def np_derivative(f: np.ndarray, axis: int) -> np.ndarray:
    return (f[_interior(f.ndim, axis, 1)] - f[_interior(f.ndim, axis, -1)]) / 2


def np_laplacian(f: np.ndarray) -> np.ndarray:
    c = f[_interior(f.ndim)]
    return sum(f[_interior(f.ndim, a, 1)] + f[_interior(f.ndim, a, -1)]
               for a in range(f.ndim)) - 2 * f.ndim * c


def np_curl3(u, v, w):
    return (np_derivative(w, 1) - np_derivative(v, 2),
            np_derivative(u, 2) - np_derivative(w, 0),
            np_derivative(v, 0) - np_derivative(u, 1))


def close(name: str, got, want, bound: float, weight: float, amax: float):
    """``|got - want| <= bound + weight * ulp(amax)``: the stage's bias
    bound plus the f32 rounding of the stage-4 values the reference reads
    (``weight`` = the sum of the reference's absolute stencil weights)."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    check(got.shape == want.shape,
          f"{name}: shape {got.shape} != reference {want.shape}")
    check(bool(np.all(np.isfinite(got))), f"{name}: non-finite values")
    tol = bound + weight * float(np.spacing(np.float32(amax)))
    err = float(np.max(np.abs(got - want))) if got.size else 0.0
    check(err <= tol, f"{name}: max |err| {err:.3e} > bound {tol:.3e}")
    return err


def check_stage4(name: str, comp, e, x: np.ndarray) -> np.ndarray:
    """Stage-4 decompression within the compressor's error bound; returns
    it as float64 for the references."""
    f4 = np.asarray(comp.decompress(e, Stage.F))
    amax = float(np.max(np.abs(x)))
    err = float(np.max(np.abs(f4.astype(np.float64) - x)))
    bound = error_analysis.reconstruction_bound(e, amax)
    check(err <= bound, f"{name}: stage-4 error {err:.3e} > bound {bound:.3e}")
    return f4.astype(np.float64)


def tstd_bound(f4: np.ndarray, eps: float) -> float:
    """f32 error of the temporal std from integer sums: the variance
    ``(S2 - S1^2/T) / (T-1)`` is formed from f32 casts of ``S1``, ``S2``
    (a few ulps each), and ``|sqrt(a) - sqrt(b)| <= sqrt(|a - b|)``."""
    q = f4 / (2 * eps)
    t = f4.shape[0]
    s1, s2 = q.sum(axis=0), (q * q).sum(axis=0)
    var_err = 4 * F32_ULP * (s2 + s1 * s1 / t) / (t - 1)
    return float(2 * eps * np.sqrt(np.max(var_err)))


def check_stats(name, e, stage, mean, std, f4: np.ndarray):
    amax = float(np.max(np.abs(f4)))
    close(f"{name} mean@{stage.name}", mean, f4.mean(),
          error_analysis.mean_bias_bound(e, stage), 1.0, amax)
    close(f"{name} std@{stage.name}", std, f4.std(ddof=1),
          error_analysis.std_bias_bound(e, stage), 1.0, amax)


def encode_common(comp, fields: list) -> list:
    """Encode same-layout fields at one common width (the widest field's
    exact width), so they share a layout and batch into one program."""
    bits = max(comp.max_bits(c) for c in fields)
    return [comp.encode(c, bits=bits) for c in fields]


def encoded_bytes(e) -> int:
    return int(e.payload.nbytes + e.metadata.nbytes + e.bitwidths.nbytes)


def bitwise_vs_off(label: str, run) -> None:
    """The kernel contract: every covered cell equals the XLA lowering
    bit for bit (``kernels/fused.py``)."""
    got = jax.tree.leaves(run())
    with kops.override_mode("off"):
        want = jax.tree.leaves(run())
    check(len(got) == len(want), f"{label}: result arity differs")
    for g, w in zip(got, want):
        g, w = np.asarray(g), np.asarray(w)
        check(g.shape == w.shape and g.tobytes() == w.tobytes(),
              f"{label}: kernel result differs from the XLA lowering")


def custom_calls(fn, *args) -> int:
    """``tpu_custom_call`` ops (Pallas kernels) in the compiled program."""
    return jax.jit(fn).lower(*args).compile().as_text().count(
        "tpu_custom_call")


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_2d(dims=DATASETS["Ocean"][1], n_fields=DATASETS["Ocean"][0],
             seed: int = 0, schemes=("hszp_nd", "hszx_nd")) -> dict:
    """Ocean on the kernel path; returns ``{"custom_calls": n}`` summed over
    the compiled 2-D kernel-cell programs."""
    from repro.core import homomorphic as H

    log(f"[2d] Ocean {dims} x {n_fields} fields, schemes {schemes}")
    store = FieldStore(cache_bytes=2 << 30)
    data = [synth_field("Ocean", f, dims, seed) for f in range(n_fields)]
    n_calls = 0
    window = tuple((d // 4, d // 4 + d // 2) for d in dims)
    for scheme in schemes:
        comp = by_name(scheme)
        comps = [comp.compress(jnp.asarray(x), rel_eb=REL_EB) for x in data]
        encs = encode_common(comp, comps)
        ids, f4s = [], []
        for f, (x, e) in enumerate(zip(data, encs)):
            fid = f"ocean/{scheme}/{f}"
            store.put(fid, e)
            ids.append(fid)
            f4s.append(check_stage4(fid, comp, e, x))
            log(f"  {fid}: shape {e.shape} bits {e.bits}, raw {x.nbytes} B, "
                f"encoded {encoded_bytes(e)} B")
        amax = max(float(np.max(np.abs(f))) for f in f4s)
        eb = encs[0]

        # dashboard: one program, one prelude per field, auto stage
        roots = [r for i in ids for r in
                 (expr.mean(i), expr.std(i), expr.laplacian(i))]
        res = query(exprs=roots, stage="auto", store=store)
        stages = res.stages
        for f, f4 in enumerate(f4s):
            mean, std, lap = res.values[3 * f:3 * f + 3]
            check_stats(ids[f], eb, stages[3 * f], mean, std, f4)
            close(f"{ids[f]} laplacian@{stages[3 * f + 2].name}", lap,
                  np_laplacian(f4), error_analysis.stencil_bias_bound(eb),
                  8.0, amax)

        # stencils at stage 3 and the laplacian at stage 2
        q_roots = [r for i in ids for r in (expr.derivative(i, axis=0),
                                            expr.derivative(i, axis=1),
                                            expr.gradient(i))]
        res_q = query(exprs=q_roots, stage=Stage.Q, store=store).values
        res_p = query(exprs=[expr.laplacian(i) for i in ids], stage=Stage.P,
                      store=store).values
        sb = error_analysis.stencil_bias_bound(eb)
        for f, f4 in enumerate(f4s):
            d0, d1, (g0, g1) = res_q[3 * f:3 * f + 3]
            for name, got, ax in (("derivative0", d0, 0),
                                  ("derivative1", d1, 1),
                                  ("gradient0", g0, 0), ("gradient1", g1, 1)):
                close(f"{ids[f]} {name}@Q", got, np_derivative(f4, ax), sb,
                      1.0, amax)
            close(f"{ids[f]} laplacian@P", res_p[f], np_laplacian(f4), sb,
                  8.0, amax)

        # kernel cells bitwise against the XLA lowering: store-seeded
        # (residual-plane kernels) and storeless Encoded (payload kernels)
        for s, ops in ((Stage.Q, ("derivative0", "derivative1", "gradient")),
                       (Stage.P, ("laplacian",))):
            def cells(leaf, ops=ops):
                out = []
                for op in ops:
                    if op.startswith("derivative"):
                        out.append(expr.derivative(leaf, axis=int(op[-1])))
                    else:
                        out.append(expr.op(op, leaf))
                return out
            bitwise_vs_off(f"{scheme} store @{s.name}", lambda: query(
                exprs=[r for i in ids for r in cells(i)], stage=s,
                store=store).values)
            bitwise_vs_off(f"{scheme} encoded @{s.name}", lambda: query(
                exprs=[r for e in encs for r in cells(e)], stage=s).values)
        if scheme == "hszx_nd":
            bitwise_vs_off(f"{scheme} laplacian @Q", lambda: query(
                exprs=[expr.laplacian(e) for e in encs], stage=Stage.Q).values)
        for fn in (lambda x: H.derivative(x, Stage.Q, 0),
                   lambda x: H.gradient(x, Stage.Q),
                   lambda x: H.laplacian(x, Stage.P)):
            n_calls += custom_calls(fn, eb) + custom_calls(fn, comps[0])
        log(f"  {scheme}: kernel cells bitwise equal to the XLA lowering")

        # the frontend: every request answered, none rejected
        fe = AnalyticsFrontend(store=store)
        fe.add_request(AnalyticsRequest(uid=0, exprs=[expr.mean(ids[0]),
                                                      expr.std(ids[0])]))
        fe.add_request(AnalyticsRequest(uid=1, exprs=expr.gradient(ids[-1]),
                                        stage=Stage.Q))
        fe.add_request(AnalyticsRequest(uid=2, exprs=expr.laplacian(ids[0]),
                                        stage=Stage.Q, region=window))
        fe.add_request(AnalyticsRequest(
            uid=3, exprs=expr.sub(expr.derivative(ids[-1], axis=0),
                                  expr.derivative(ids[0], axis=1)),
            stage=Stage.Q))
        done = {r.uid: r for r in fe.run_until_drained()}
        check(sorted(done) == [0, 1, 2, 3], f"{scheme}: requests lost")
        for r in done.values():
            check(r.done and r.error is None,
                  f"{scheme} request {r.uid} rejected: {r.error}")
        win = f4s[0][tuple(slice(a, b) for a, b in window)]
        close(f"{ids[0]} windowed laplacian@Q", done[2].result,
              np_laplacian(win), sb, 8.0, amax)
        close(f"{ids[0]} frontend mean", done[0].result[0], f4s[0].mean(),
              error_analysis.mean_bias_bound(eb, done[0].result_stage[0]),
              1.0, amax)
        log(f"  {scheme}: {len(done)} frontend requests answered")
    log(f"  peak_bytes_in_use {peak_bytes()}")
    return {"custom_calls": n_calls}


def phase_3d(dims=DATASETS["Hurricane"][1], n_vars=DATASETS["Hurricane"][0],
             seed: int = 0) -> dict:
    """Hurricane: batched statistics over every variable, then divergence
    and curl of variables 0-2 at stage 3."""
    log(f"[3d] Hurricane {dims} x {n_vars} variables, hszp_nd")
    comp = by_name("hszp_nd")
    data, comps, refs, vel = [], [], [], []
    for v in range(n_vars):
        x = synth_field("Hurricane", v, dims, seed)
        comps.append(comp.compress(jnp.asarray(x), rel_eb=REL_EB))
        data.append(x)
    encs = encode_common(comp, comps)
    del comps
    store = FieldStore(cache_bytes=4 << 30)
    ids = []
    for v, (x, e) in enumerate(zip(data, encs)):
        fid = f"hurricane/{v}"
        store.put(fid, e)
        ids.append(fid)
        f4 = check_stage4(fid, comp, e, x)
        refs.append((f4.mean(), f4.std(ddof=1), float(np.max(np.abs(f4)))))
        if v < 3:
            vel.append(f4)
    del data
    log(f"  {n_vars} variables: shape {encs[0].shape} bits {encs[0].bits}, "
        f"encoded {sum(encoded_bytes(e) for e in encs)} B in all")
    roots = [expr.mean(i) for i in ids] + [expr.std(i) for i in ids]
    res = query(exprs=roots, stage="auto", store=store)
    for v, (mu, sd, amax) in enumerate(refs):
        e, s = encs[v], res.stages[v]
        close(f"{ids[v]} mean@{s.name}", res.values[v], mu,
              error_analysis.mean_bias_bound(e, s), 1.0, amax)
        s = res.stages[n_vars + v]
        close(f"{ids[v]} std@{s.name}", res.values[n_vars + v], sd,
              error_analysis.std_bias_bound(e, s), 1.0, amax)
    uvw = tuple(ids[:3])
    div, curl = query(exprs=[expr.divergence(uvw), expr.curl(uvw)],
                      stage=Stage.Q, store=store).values
    amax = max(r[2] for r in refs[:3])
    sb = error_analysis.stencil_bias_bound(encs[0])
    close("divergence@Q", div, sum(np_derivative(f, a)
                                   for a, f in enumerate(vel)), sb, 3.0, amax)
    for k, (got, want) in enumerate(zip(curl, np_curl3(*vel))):
        close(f"curl[{k}]@Q", got, want, sb, 2.0, amax)
    log(f"  peak_bytes_in_use {peak_bytes()}")
    return {}


def phase_stream(dims=DATASETS["Ocean"][1], n_slabs: int = 3, steps: int = 4,
                 seed: int = 0) -> dict:
    """A temporal field on Ocean's shape: appends and temporal queries
    through the frontend, checked after every append."""
    log(f"[stream] Ocean {dims}: {n_slabs} slabs x {steps} timesteps, "
        "hszp_nd")
    store = StreamFieldStore(cache_bytes=2 << 30)
    fid = "ocean/stream"
    store.put_temporal(fid, TemporalField(by_name("hszp_nd"), rel_eb=REL_EB))
    base = synth_field("Ocean", 0, dims, seed)
    rng = np.random.default_rng(seed)
    fe = AnalyticsFrontend(store=store)
    ops = ("tmean", "tstd", "tdelta")
    for k in range(n_slabs):
        t = np.arange(k * steps, (k + 1) * steps, dtype=np.float32)
        slab = (base[None] * (1 + 0.01 * t[:, None, None])
                + rng.normal(0, 0.01, (steps,) + tuple(dims))
                ).astype(np.float32)
        fe.add_request(AppendRequest(uid=2 * k, field_id=fid, data=slab))
        fe.add_request(AnalyticsRequest(
            uid=2 * k + 1, exprs=[expr.op(o, fid) for o in ops]))
        done = {r.uid: r for r in fe.run_until_drained()}
        for r in done.values():
            check(r.done and r.error is None,
                  f"stream request {r.uid} rejected: {r.error}")
        got = dict(zip(ops, done[2 * k + 1].result))
        tf = store.get(fid)
        want = tf.reference(ops)
        for o in ops:
            g, w = np.asarray(got[o]), np.asarray(want[o])
            check(g.tobytes() == w.tobytes(),
                  f"{o} after slab {k}: differs from the full decompression")
        f4 = np.asarray(tf.decompress(Stage.F), np.float64)
        amax = float(np.max(np.abs(f4)))
        sb = error_analysis.stencil_bias_bound(tf.slabs[0])
        close(f"tmean after slab {k}", got["tmean"], f4.mean(axis=0),
              sb, 2.0, amax)
        close(f"tstd after slab {k}", got["tstd"], f4.std(axis=0, ddof=1),
              sb + tstd_bound(f4, float(tf.eps)), 2.0, amax)
        close(f"tdelta after slab {k}", got["tdelta"], f4[-1] - f4[-2],
              sb, 2.0, amax)
        log(f"  slab {k}: {tf.n_steps} timesteps, {len(done)} requests "
            "answered, temporal ops equal the full decompression")
    log(f"  peak_bytes_in_use {peak_bytes()}")
    return {}


def phase_shard(dims=DATASETS["NYX"][1], n_shards: int = 4,
                seed: int = 0) -> dict:
    """NYX velocity over a sharded store against one device, bitwise."""
    from repro.launch.mesh import make_analytics_mesh
    from repro.shard import ShardedFieldStore

    log(f"[shard] NYX {dims} velocity 0-2, hszp_nd, {n_shards} shards")
    comp = by_name("hszp_nd")
    data = [synth_field("NYX", v, dims, seed) for v in range(3)]
    encs = encode_common(
        comp, [comp.compress(jnp.asarray(x), rel_eb=REL_EB) for x in data])
    single = FieldStore(cache_bytes=8 << 30)
    sharded = ShardedFieldStore(make_analytics_mesh(n_shards),
                                cache_bytes_per_shard=8 << 30)
    ids = []
    for v, (x, e) in enumerate(zip(data, encs)):
        fid = f"nyx/velocity{v}"
        check_stage4(fid, comp, e, x)
        single.put(fid, e)
        sharded.put(fid, e)
        ids.append(fid)
    del data
    log(f"  shape {encs[0].shape} bits {encs[0].bits}, encoded "
        f"{sum(encoded_bytes(e) for e in encs)} B in all")
    region = tuple((d // 4, d // 4 + d // 2) for d in dims)  # 1/8 volume
    stats = [r for i in ids for r in
             (expr.mean(i), expr.std(i), expr.laplacian(i))]
    uvw = tuple(ids)
    cases = (("full mean+std+laplacian", stats, "auto", None),
             ("region mean+std+laplacian", stats, "auto", region),
             ("region divergence+curl @3",
              [expr.divergence(uvw), expr.curl(uvw)], Stage.Q, region))
    for label, roots, stage, reg in cases:
        want = query(exprs=roots, stage=stage, region=reg,
                     store=single).values
        got = query(exprs=roots, stage=stage, region=reg,
                    store=sharded).values
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            g, w = np.asarray(g), np.asarray(w)
            check(g.shape == w.shape and g.tobytes() == w.tobytes(),
                  f"sharded {label} differs from the single device")
        log(f"  {label}: sharded == single device, bitwise")
    for fid in ids:
        acct = sharded.payload_accounting(fid, ("mean", "std", "laplacian"),
                                          Stage.Q, region=region)
        log(f"  payload_accounting {fid}: {acct}")
    for d in jax.devices()[:n_shards]:
        s = d.memory_stats() or {}
        log(f"  device {d.id}: bytes_in_use {s.get('bytes_in_use')} "
            f"peak_bytes_in_use {s.get('peak_bytes_in_use')}")
    return {}


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded phase on four chips")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (platform {dev.platform!r}); "
              "this script only runs on the chip", file=sys.stderr)
        return 2
    n_dev = len(jax.devices())
    if n_dev < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"found {n_dev}", file=sys.stderr)
        return 2
    from repro.launch.cache import use_compile_cache
    log(f"device {dev.platform} {dev.device_kind} x {n_dev}; compile cache "
        f"{use_compile_cache(ROOT)}")
    mode = kops.kernel_mode()
    log(f"kernel_mode() = {mode}")
    check(mode == "native", f"kernel mode {mode!r} on a TPU, want 'native'")

    if args.chips == 4:
        phase_shard(seed=args.seed)
    else:
        out = phase_2d(seed=args.seed)
        log(f"  tpu_custom_call in the compiled 2-D kernel-cell programs: "
            f"{out['custom_calls']}")
        check(out["custom_calls"] > 0, "the 2-D programs contain no kernel")
        phase_3d(seed=args.seed)
        phase_stream(seed=args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": n_dev}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
