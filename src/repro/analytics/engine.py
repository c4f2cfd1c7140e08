"""Batch executor: stack same-layout fields, run one jitted vmap per op set.

Many timesteps/variables of a scientific dataset share one compression
layout, so their homomorphic analytics compile to a *single* XLA program
with a leading batch axis instead of one dispatch per field.  Op *sets* fuse
further: ``run(fields, ["mean", "std", "laplacian"])`` compiles one program
whose shared stage-reconstruction prelude (``repro.core.oplib``) feeds every
postlude — one decode pass, a dict of batched results.  The jit cache is
keyed on ``(scheme, block, shape, frozen op-set, stage, region, axis,
batch, seed signature)`` — the full static signature of the compiled
program — and the op-set component is canonically ordered, so
``["std", "mean"]`` and ``["mean", "std"]`` hit the same entry.
Store-seeded programs (``run(..., seeds=)``) take the fields' materialized
intermediates as extra inputs and contain no stage reconstruction; they
compile separately from their cold twins.

Each engine program is a function named by its kind (``expr_dag``,
``op_set``, ``temporal_summarize``, ``merge_summaries``,
``temporal_postlude``), so a profiler trace says which one ran
(``jit_expr_dag(...)``).  Every call runs in a :mod:`repro.obs` span:
``repro.engine.dispatch`` covers building the cache key and, on a hit, the
jitted call; a miss then opens ``repro.engine.build`` from ``jax.jit``
through the first call, which traces and compiles.  Hits, misses and
evictions are counted in :attr:`BatchedAnalytics.stats` and
``repro.obs.counters``.

Stage resolution is layered, not repeated: the engine plans only when given
``stage="auto"`` (or another directive string).  A resolved :class:`Stage`
or :class:`StageSetPlan` — e.g. from :func:`repro.analytics.query.query`,
which already planned the group — is executed as-is; infeasible explicit
stages still raise at trace time from the ops themselves.
"""
from __future__ import annotations
from collections.abc import Mapping, Sequence

import dataclasses
from collections import OrderedDict

import jax
import jax.numpy as jnp

from repro import obs
from repro.core import (Compressed, Encoded, Stage, batch_stack, layout_key,
                        oplib)
from repro.core import region as region_mod

from .planner import CostModel, StageSetPlan, plan_stages

Field = Compressed | Encoded

StageLike = Stage | str | int | StageSetPlan | Mapping[str, Stage]


def batch_key(first: Field, ops: str | Sequence[str], stage: Stage,
              axis: int = 0, n_components: int = 1, batch: int = 1,
              region=None, seed_sig: tuple | None = None) -> tuple:
    """Static signature of one compiled batched-analytics program.

    The batch size is part of the key: stacking happens *inside* the jitted
    program (one dispatch for stack + op set, and XLA elides copies the ops
    never read — e.g. residuals under a stage-① metadata mean), so the
    program arity depends on it.  The (normalized) region is static too: it
    decides the gathered block set and every output shape.  The op set is
    canonically ordered — the key is order-insensitive.  ``seed_sig``
    (:meth:`repro.store.MaterializedStage.sig`) distinguishes store-seeded
    programs — they take the resident intermediates as *inputs* and contain
    no reconstruction — from cold ones.
    """
    if region is not None:
        region = region_mod.normalize_region(region, first.shape)
    names = oplib.canonical_ops(ops)
    # the kernel backend mode is a trace-time input: fused-vs-XLA selection
    # (and the Encoded payload decode path) happens while tracing, so a
    # program compiled under one mode must not serve another
    return layout_key(first) + (names, Stage(stage), axis, n_components,
                                batch, region, seed_sig, oplib.kernel_sig())


@dataclasses.dataclass
class JitStats:
    """Cumulative jit-cache accounting of one engine (monotone counters):
    ``misses`` counts programs built, ``evictions`` programs dropped (by the
    LRU bound, or because their first call raised)."""

    hits: int = 0
    misses: int = 0
    evictions: int = 0


class BatchedAnalytics:
    """Executes one homomorphic op set over a batch of same-layout fields.

    One instance owns one jit cache; module-level :data:`default_engine`
    is shared by :func:`repro.analytics.query.query` and the serve frontend.

    ``bucket_batches`` pads each batch to the next power of two (repeating
    the last field; padded results are sliced off) so a serving queue with
    fluctuating depth compiles O(log max_batch) programs per op set instead
    of one per distinct length.  The cache is LRU-bounded by ``cache_limit``.
    """

    def __init__(self, cost_model: CostModel | None = None, *,
                 bucket_batches: bool = True, cache_limit: int = 128):
        self.cost_model = cost_model
        self.bucket_batches = bucket_batches
        self.cache_limit = cache_limit
        self._jitted: OrderedDict[tuple, object] = OrderedDict()
        self.stats = JitStats()

    @staticmethod
    def _bucket(n: int) -> int:
        return 1 << (n - 1).bit_length()

    @staticmethod
    def _unpad(out, b: int, n: int):
        """Drop the results of the batch padding (``b`` of ``n`` are real)."""
        return out if n == b else jax.tree.map(lambda x: x[:b], out)

    # -- compiled-program cache -------------------------------------------
    def _lookup(self, key: tuple):
        """The program cached under ``key`` (a hit), or ``None``."""
        fn = self._jitted.get(key)
        if fn is not None:
            self._jitted.move_to_end(key)
            self.stats.hits += 1
            obs.counters["jit_hits"] += 1
        return fn

    def _cache_put(self, key: tuple, fn) -> None:
        """Cache a program just built (a miss), evicting the least recently
        used beyond ``cache_limit``."""
        self.stats.misses += 1
        obs.counters["jit_misses"] += 1
        self._jitted[key] = fn
        while len(self._jitted) > self.cache_limit:
            self._jitted.popitem(last=False)
            self._evicted()

    def _evicted(self) -> None:
        self.stats.evictions += 1
        obs.counters["jit_evictions"] += 1

    def _first_call(self, key: tuple, fn, *args):
        """The first call of a program just cached.  An infeasible explicit
        stage raises at its first trace: the program is dropped rather than
        left permanently raising in the cache (warm entries stay through
        transient runtime failures)."""
        try:
            return fn(*args)
        except Exception:
            if self._jitted.pop(key, None) is not None:
                self._evicted()
            raise

    def _compiled(self, key: tuple, ops: tuple[str, ...], stage: Stage,
                  axis: int, n_components: int, batch: int, region=None,
                  seeded: bool = False):
        """Build and cache the op-set program for ``key``."""

        def stack_seeds(seeds):
            return jax.tree.map(lambda *xs: jnp.stack(xs), *seeds)

        if oplib.is_vector_ops(ops):
            def op_set(*flat, _ops=ops, _stage=stage, _b=batch,
                       _nc=n_components, _r=region, _axis=axis):
                comps = [batch_stack(flat[i * _b:(i + 1) * _b])
                         for i in range(_nc)]
                if seeded:  # trailing args: seeds, component-major like fields
                    sc = [stack_seeds(flat[(_nc + i) * _b:(_nc + i + 1) * _b])
                          for i in range(_nc)]
                    return jax.vmap(lambda *args: oplib.compute(
                        list(args[:_nc]), _ops, _stage, axis=_axis, region=_r,
                        seed=list(args[_nc:])))(*comps, *sc)
                return jax.vmap(lambda *cs: oplib.compute(
                    list(cs), _ops, _stage, axis=_axis, region=_r))(*comps)
        else:
            def op_set(*flat, _ops=ops, _stage=stage, _b=batch, _r=region,
                       _axis=axis):
                stacked = batch_stack(flat[:_b])
                if seeded:
                    sstack = stack_seeds(flat[_b:])
                    return jax.vmap(lambda c, m: oplib.compute(
                        c, _ops, _stage, axis=_axis, region=_r,
                        seed=m))(stacked, sstack)
                return jax.vmap(lambda c: oplib.compute(
                    c, _ops, _stage, axis=_axis, region=_r))(stacked)

        fn = jax.jit(op_set)
        self._cache_put(key, fn)
        return fn

    @property
    def cache_size(self) -> int:
        return len(self._jitted)

    # -- temporal (streaming) programs --------------------------------------
    def summarize(self, slabs: Sequence[Field], stage: Stage, *,
                  region=None):
        """Per-slab temporal summaries, batched: one compiled program per
        ``(slab layout, stage, region, padded batch)``.

        The key never includes the stream's total slab count or the slab
        index — every append of a same-layout slab reuses the same program,
        which is what keeps streaming ingest retrace-free
        (``repro.stream``, DESIGN.md §9).  Returns a
        :class:`~repro.core.oplib.TemporalSummary` whose leaves carry a
        leading batch axis (``len(slabs)``); merging is the caller's job —
        summaries are order-sensitive (``last2``), and padding repeats the
        last slab, so a blind in-program reduce would double-count it.
        """
        if not slabs:
            raise ValueError("empty slab batch")
        with obs.span(obs.ENGINE_DISPATCH):
            first = slabs[0]
            stage = Stage(stage)
            norm = (region_mod.normalize_region(region, first.shape[1:])
                    if region is not None else None)
            b = len(slabs)
            padded = list(slabs)
            if self.bucket_batches:
                padded += [slabs[-1]] * (self._bucket(b) - b)
            key = layout_key(first) + ("__temporal_summary__", stage, norm,
                                       len(padded), oplib.kernel_sig())
            fn = self._lookup(key)
            if fn is not None:
                return self._unpad(fn(*padded), b, len(padded))
        with obs.span(obs.ENGINE_BUILD):
            def temporal_summarize(*flat, _stage=stage, _r=norm,
                                   _b=len(padded)):
                stacked = batch_stack(flat[:_b])
                return jax.vmap(lambda c: oplib.summarize_slab(
                    c, _stage, region=_r))(stacked)

            fn = jax.jit(temporal_summarize)
            self._cache_put(key, fn)
            return self._unpad(self._first_call(key, fn, *padded), b,
                               len(padded))

    def merge_summaries(self, a, b):
        """Jitted pairwise summary merge — ONE program per summary
        signature, reused for every append and every fold step, so merging
        a K-slab stream never retraces as K grows."""
        with obs.span(obs.ENGINE_DISPATCH):
            key = ("__temporal_merge__", a.sig(), b.sig())
            fn = self._lookup(key)
            if fn is not None:
                return fn(a, b)
        with obs.span(obs.ENGINE_BUILD):
            fn = jax.jit(oplib.merge_summaries)
            self._cache_put(key, fn)
            return self._first_call(key, fn, a, b)

    def run_temporal(self, ops: str | Sequence[str], summary, eps):
        """Temporal op postludes on one merged summary: one compiled
        program per (canonical op set, summary signature) — independent of
        how many slabs the summary merged, so querying a growing stream
        compiles exactly once."""
        names = oplib.canonical_ops(ops)
        if not oplib.is_temporal_ops(names):
            raise ValueError(f"{names} is not a temporal op set")
        with obs.span(obs.ENGINE_DISPATCH):
            key = ("__temporal_post__", names, summary.sig())
            fn = self._lookup(key)
            if fn is not None:
                return fn(summary, eps)
        with obs.span(obs.ENGINE_BUILD):
            def temporal_postlude(s, e, _names=names):
                return oplib.temporal_postlude(_names, s, e)

            fn = jax.jit(temporal_postlude)
            self._cache_put(key, fn)
            return self._first_call(key, fn, summary, eps)

    # -- expression DAGs ----------------------------------------------------
    def run_expr(self, program, bindings: Sequence, stages: Sequence[Stage],
                 *, region=None, seeds: Sequence | None = None,
                 precomputed: Mapping[str, object] | None = None):
        """Execute one analyzed expression DAG as a single compiled program.

        ``bindings`` holds one entry per leaf slot — a field, a component
        tuple (vector bundles), or ``None`` for temporal slots whose op
        values arrive through ``precomputed`` (keyed by canonical node
        serial; computed outside the trace so streams never enter the jit).
        ``stages`` is the joint per-component plan
        (:class:`~repro.analytics.planner.ExprPlan`); ``seeds`` optionally
        store-seeds individual slots.  The cache key is the program's
        structural hash plus every static input signature, so two
        structurally-identical DAGs over same-layout fields share one
        compiled program regardless of which concrete arrays they bind.
        """
        from repro.core import expr as expr_mod

        precomputed = dict(precomputed or {})
        seeds = list(seeds) if seeds is not None else [None] * len(bindings)
        if len(seeds) != len(bindings):
            raise ValueError(f"{len(seeds)} seeds for {len(bindings)} slots")

        def slot_layout(b):
            if b is None:
                return None
            if isinstance(b, tuple):
                return tuple(layout_key(c) for c in b)
            return layout_key(b)

        def slot_region(b):
            if b is None or region is None:
                return None
            f = b[0] if isinstance(b, tuple) else b
            return region_mod.normalize_region(region, f.shape)

        def slot_seed_sig(s):
            if s is None:
                return None
            if isinstance(s, tuple):
                return tuple(x.sig() for x in s)
            return s.sig()

        with obs.span(obs.ENGINE_DISPATCH):
            pre_keys = tuple(sorted(precomputed))
            pre_sig = tuple((k, jnp.shape(precomputed[k]),
                             str(jnp.result_type(precomputed[k])))
                            for k in pre_keys)
            key = ("__expr__", program.key,
                   tuple(slot_layout(b) for b in bindings),
                   tuple(Stage(s) for s in stages),
                   tuple(slot_region(b) for b in bindings),
                   tuple(slot_seed_sig(s) for s in seeds), pre_sig,
                   oplib.kernel_sig())
            args = (list(bindings), seeds, [precomputed[k] for k in pre_keys])
            fn = self._lookup(key)
            if fn is not None:
                return fn(*args)
        with obs.span(obs.ENGINE_BUILD):
            def expr_dag(binds, sds, pre_vals, _stages=tuple(stages),
                         _r=region):
                return expr_mod.lower(program, binds, _stages, region=_r,
                                      seeds=sds,
                                      precomputed=dict(zip(pre_keys,
                                                           pre_vals)))

            fn = jax.jit(expr_dag)
            self._cache_put(key, fn)
            return self._first_call(key, fn, *args)

    # -- stage resolution ---------------------------------------------------
    def _resolve(self, scheme, names: tuple[str, ...], stage: StageLike,
                 region, field, axis: int) -> StageSetPlan:
        """Plan only when asked to: a resolved Stage / StageSetPlan / per-op
        mapping from an upper layer is executed as-is (no double planning)."""
        if isinstance(stage, StageSetPlan):
            return stage
        if isinstance(stage, Stage):
            return StageSetPlan(names, tuple((op, stage) for op in names),
                                stage)
        if isinstance(stage, Mapping):
            stages = tuple((op, Stage(stage[op])) for op in names)
            resolved = {s for _, s in stages}
            fused = resolved.pop() if len(resolved) == 1 else None
            return StageSetPlan(names, stages, fused)
        return plan_stages(scheme, names, stage, self.cost_model,
                           region=region, field=field, axis=axis)

    # -- execution ---------------------------------------------------------
    def run(self, fields: Sequence, ops: str | Sequence[str],
            stage: StageLike = "auto", *, axis: int = 0, region=None,
            seeds: Sequence | None = None):
        """Run an op (or fused op set) over ``fields`` in jitted vmapped calls.

        ``fields`` is a sequence of same-layout :class:`Compressed` /
        :class:`Encoded` fields — or, for vector op sets
        (``divergence``/``curl``), a sequence of equal-length component
        tuples.  A single op name returns the batched result (leading axis =
        ``len(fields)``); an op *set* returns ``{op: batched result}`` from
        one compiled program per fused plan (falling back to one program per
        op when the plan is unfused).  ``curl`` in 3-D and ``gradient``
        return a tuple of batched components, matching the unbatched ops.
        ``region`` restricts every field to the same window (same-layout
        fields share the block geometry, so one static region plan serves
        the whole batch).

        ``seeds`` optionally supplies one store-resident
        :class:`~repro.store.MaterializedStage` per field (per component
        tuple for vector sets) matching the resolved fused stage: the
        compiled program then takes the intermediates as inputs and skips
        the stage reconstruction entirely.  Seeds require a fused plan (an
        unfused fallback re-plans per op at stages the seeds don't match).
        """
        single = isinstance(ops, str)
        names = oplib.canonical_ops(ops)
        if not fields:
            raise ValueError("empty batch")

        vector = oplib.is_vector_ops(names)
        if vector:
            n_comp = len(fields[0])
            if any(len(f) != n_comp for f in fields):
                raise ValueError("all vector fields must have the same number "
                                 "of components")
            first = fields[0][0]
        else:
            n_comp = 1
            first = fields[0]
        d_axis = axis if any(oplib.OPS[n].needs_axis for n in names) else 0

        plan = self._resolve(first.scheme, names, stage, region, first, d_axis)
        if plan.fused is None:
            out = {op: self.run(fields, op, plan.stage_of(op),
                                axis=axis, region=region)
                   for op in names}
            return out[names[0]] if single else out

        with obs.span(obs.ENGINE_DISPATCH):
            seed_sig = None
            if seeds is not None:
                if len(seeds) != len(fields):
                    raise ValueError(
                        f"{len(seeds)} seeds for {len(fields)} fields")
                # per-component signatures may differ (per-axis band
                # closures); across the batch each component's seeds must
                # agree to stack
                per_comp = (tuple(zip(*seeds)) if vector
                            else (tuple(seeds),))
                comp_sigs = []
                for comp_seeds in per_comp:
                    sigs = {s.sig() for s in comp_seeds}
                    if len(sigs) != 1:
                        raise ValueError(
                            f"seeds must share one layout signature per "
                            f"component, got {sigs}")
                    comp_sigs.append(sigs.pop())
                    # the seed owns the stage-serving rule (③ serves ④, ...)
                    if not comp_seeds[0].serves(plan.fused):
                        raise ValueError(
                            f"seeds materialized at stage "
                            f"{Stage(comp_seeds[0].stage).name} cannot seed "
                            f"a stage-{plan.fused.name} plan")
                seed_sig = tuple(comp_sigs)

            b = len(fields)
            padded = list(fields)
            padded_seeds = list(seeds) if seeds is not None else None
            if self.bucket_batches:
                pad = self._bucket(b) - b
                padded += [fields[-1]] * pad
                if padded_seeds is not None:
                    padded_seeds += [padded_seeds[-1]] * pad
            key = batch_key(first, names, plan.fused, d_axis, n_comp,
                            len(padded), region, seed_sig)
            if vector:
                # component-major flat args: (f0[c], f1[c], ...) for each c
                flat = tuple(f[i] for i in range(n_comp) for f in padded)
                if padded_seeds is not None:
                    flat += tuple(s[i] for i in range(n_comp)
                                  for s in padded_seeds)
            else:
                flat = tuple(padded)
                if padded_seeds is not None:
                    flat += tuple(padded_seeds)
            fn = self._lookup(key)
            if fn is not None:
                out = self._unpad(fn(*flat), b, len(padded))
                return out[names[0]] if single else out
        with obs.span(obs.ENGINE_BUILD):
            fn = self._compiled(key, names, plan.fused, d_axis, n_comp,
                                len(padded), region,
                                seeded=seeds is not None)
            out = self._unpad(self._first_call(key, fn, *flat), b,
                              len(padded))
            return out[names[0]] if single else out


#: process-wide engine (shared jit cache) used by the query front-end.
default_engine = BatchedAnalytics()
