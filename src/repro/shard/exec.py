"""Shard-mapped execution programs: word-merge region decode and
homomorphic temporal-summary all-reduce.

Two program families, both built on one invariant — every cross-shard
combination is an **exact associative integer merge**, so the sharded
result is bit-identical to the single-device path by construction, never
by tolerance:

* **Word merge** (:meth:`ShardPrograms.region_compute`): a region query's
  :class:`~repro.core.region.RegionPlan` names the exact payload words the
  single-device path gathers (``payload_gather``).  Each word is owned by
  exactly one shard (:meth:`~repro.shard.placement.BlockPlacement.word_owner`
  — words are never split), so each shard reads its owned words from its
  *local* payload stripe, scatter-adds them into the gathered-word layout,
  and a ``psum`` over the shard axis reassembles exactly
  ``payload[word_idx]``.  From there the op set lowers through the very
  same ``unpack -> unzigzag -> assemble -> postlude`` sequence as
  ``encode.decode_region`` (``oplib.compute(payload_words=...)``), inside
  the shard-mapped program — the Pallas kernel backend composes here
  unchanged, and kernel mode stays in the program cache key via
  ``oplib.kernel_sig()``.

* **Summary merge** (:meth:`ShardPrograms.merge_band_summaries`):
  per-band partial :class:`~repro.core.oplib.TemporalSummary` leaves are
  all int32 with modular sums, so spatial reassembly is a disjoint scatter
  followed by ``psum`` / ``pmin`` / ``pmax`` — the same homomorphic
  all-reduce shape as ``comm.hom_collectives``, and associative in any
  order.  A summary's per-position leaves depend only on the q integers at
  that position (stage reconstruction is exact), so band partials scattered
  into the window equal the full-window summary bit for bit.

Programs cache in an ``_jitted`` OrderedDict keyed exactly like the
analytics engine's (layout, static geometry, placement/mesh signatures,
kernel mode) — audited by ``repro.audit`` jit-key analysis.
"""
from __future__ import annotations

import dataclasses
from collections import OrderedDict
from collections.abc import Sequence

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import compat
from repro.core import Encoded, Stage, layout_key, oplib
from repro.core import encode as encode_mod
from repro.core import region as region_mod
from repro.launch.mesh import SHARD_AXIS
from repro.shard.placement import BlockPlacement

_INT32_MAX = np.int32(np.iinfo(np.int32).max)
_INT32_MIN = np.int32(np.iinfo(np.int32).min)


def mesh_sig(mesh) -> tuple:
    """Hashable mesh identity (program cache key component)."""
    return (tuple(mesh.axis_names),
            tuple(int(d.id) for d in mesh.devices.flat))


def gather_routing(n_shards: int, placement: BlockPlacement, bits: int,
                   word_idx: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Per-shard (stripe-local source, gathered-set destination) index
    arrays for merging ``word_idx``.  Padding rows scatter into the
    dropped slot ``len(word_idx)``.

    Module-level (mesh-free) so the static audit's ``sharddisjoint``
    analyzer can prove scatter-target disjointness for world sizes the
    host has no devices for; :class:`ShardPrograms` delegates here.
    """
    word_idx = np.asarray(word_idx, dtype=np.int64)
    n_out = len(word_idx)
    owners = placement.word_owner(bits)[word_idx] if n_out else \
        np.zeros((0,), np.int32)
    stripes = placement.shard_word_index(bits)
    per_shard = []
    g_max = 1
    for s in range(n_shards):
        sel = np.nonzero(owners == s)[0]
        src = np.searchsorted(stripes[s], word_idx[sel])
        per_shard.append((src, sel))
        g_max = max(g_max, len(sel))
    src_arr = np.zeros((n_shards, g_max), np.int32)
    dst_arr = np.full((n_shards, g_max), n_out, np.int32)
    for s, (src, sel) in enumerate(per_shard):
        src_arr[s, :len(src)] = src
        dst_arr[s, :len(sel)] = sel
    return src_arr, dst_arr


def region_program(mesh, names: tuple[str, ...], stage: Stage, axis: int,
                   norm, n_outs: tuple[int, ...], vector: bool):
    """The jitted word-merge program of :meth:`ShardPrograms.region_compute`.

    Called as ``fn(stripped, stripes, srcs, dsts)``: the payload-less
    component fields (replicated), their ``[n_shards, w]`` payload stripes
    and the per-shard routing from :func:`gather_routing` (both sharded
    over the shard axis).  Each shard scatter-adds its owned words into the
    gathered-word layout, a ``psum`` reassembles them, and the op set
    lowers on the merged words; ``n_outs`` are the gathered word counts.
    """
    def body(ecs, strs, srcs, dsts):
        merged = []
        for st, sr, ds, n_out in zip(strs, srcs, dsts, n_outs):
            vals = st[0][sr[0]]
            buf = jnp.zeros((n_out + 1,), jnp.uint32).at[ds[0]].add(vals)
            merged.append(jax.lax.psum(buf[:n_out], SHARD_AXIS))
        if norm is None:
            # full field: the merge reassembles the entire payload
            # exactly, so the standard full decode runs unchanged, and
            # vector sets keep their per-op rules
            full = tuple(dataclasses.replace(ec, payload=m)
                         for ec, m in zip(ecs, merged))
            return oplib.compute(full if vector else full[0], names, stage,
                                 axis=axis, vector_kernel=False)
        return oplib.compute(tuple(ecs) if vector else ecs[0], names, stage,
                             axis=axis, region=norm,
                             payload_words=merged if vector else merged[0])

    return jax.jit(compat.shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS)),
        out_specs=P(), check=False))


def materialize_program(mesh, stage: Stage, norm, closure, n_out: int):
    """The jitted word-merge program of :meth:`ShardPrograms.materialize`,
    called as ``fn(stripped, stripes, src, dst)`` like
    :func:`region_program`; returns the stage-② ``sub`` container or the
    stage-③ ``q_spatial`` integers, replicated."""
    def body(ec, st, sr, ds):
        vals = st[0][sr[0]]
        buf = jnp.zeros((n_out + 1,), jnp.uint32).at[ds[0]].add(vals)
        merged = jax.lax.psum(buf[:n_out], SHARD_AXIS)
        if norm is None:
            full = dataclasses.replace(ec, payload=merged)
            ctx = oplib.StageContext(full, stage, None, closure)
        else:
            ctx = oplib.StageContext(ec, stage, norm, closure, words=merged)
        return ctx.sub if stage == Stage.P else ctx.q_spatial

    return jax.jit(compat.shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS)),
        out_specs=P(), check=False))


class ShardPrograms:
    """Compiled ``shard_map`` programs for one analytics mesh.

    Host-static routing (which words / bands belong to which shard) is
    derived from a :class:`BlockPlacement`; the traced programs see only
    uniformly-shaped per-shard arrays, so every shard runs the same SPMD
    program and only the data differs.
    """

    def __init__(self, mesh, *, cache_limit: int = 128):
        self.mesh = mesh
        self.n_shards = int(mesh.devices.size)
        self._jitted: OrderedDict = OrderedDict()
        self._limit = int(cache_limit)

    def _cache_put(self, key, fn):
        self._jitted[key] = fn
        while len(self._jitted) > self._limit:
            self._jitted.popitem(last=False)

    # -- payload striping ---------------------------------------------------
    def shard_payload(self, e: Encoded, placement: BlockPlacement) -> jax.Array:
        """Split a field's payload into per-shard word stripes.

        Returns a ``[n_shards, w_max]`` uint32 array sharded over the mesh's
        shard axis — row ``s`` holds shard ``s``'s owned words (ascending
        global order, zero-padded).  Built once when a field enters the
        sharded store; every query reads from these stripes only.
        """
        self._check(placement)
        idx = placement.shard_word_index(e.bits)
        w_max = max(max((len(i) for i in idx), default=0), 1)
        out = np.zeros((self.n_shards, w_max), np.uint32)
        pay = np.asarray(jax.device_get(e.payload))
        for s, i in enumerate(idx):
            out[s, :len(i)] = pay[i]
        return jax.device_put(
            out, NamedSharding(self.mesh, P(SHARD_AXIS)))

    def _check(self, placement: BlockPlacement):
        if placement.n_shards != self.n_shards:
            raise ValueError(
                f"placement has {placement.n_shards} shards but the mesh "
                f"has {self.n_shards} devices")

    def _gather_routing(self, placement: BlockPlacement, bits: int,
                        word_idx: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        return gather_routing(self.n_shards, placement, bits, word_idx)

    # -- region / full-field op execution -----------------------------------
    def region_compute(self, target, ops, stage: Stage, *, axis: int = 0,
                       region=None, placements=None, stripes=None) -> dict:
        """Lower an op set over shard-striped payload(s), bit-identically.

        ``target`` is one :class:`Encoded` field (field-arity op sets) or a
        sequence of component fields (vector sets); ``placements`` /
        ``stripes`` follow the same arity (``stripes=None`` re-stripes on
        the fly — the store passes its resident stripes).  Returns the same
        ``{op: value}`` dict as :func:`repro.core.oplib.compute`.
        """
        stage = Stage(stage)
        names = oplib.canonical_ops(ops)
        vector = oplib.is_vector_ops(names)
        comps = list(target) if vector else [target]
        for c in comps:
            if not isinstance(c, Encoded):
                raise TypeError(
                    "sharded execution requires Encoded fields (the payload "
                    f"is what is striped); got {type(c).__name__}")
        if placements is None:
            placements = [BlockPlacement.of(c, self.n_shards) for c in comps]
        placements = list(placements) if vector else \
            ([placements] if isinstance(placements, BlockPlacement)
             else list(placements))
        for p in placements:
            self._check(p)
        if stripes is None:
            stripes = [self.shard_payload(c, p)
                       for c, p in zip(comps, placements)]
        else:
            stripes = list(stripes) if vector else (
                [stripes] if not isinstance(stripes, (list, tuple))
                else list(stripes))

        # host-static routing: the exact words the single-device gather reads
        norm = (region_mod.normalize_region(region, comps[0].shape)
                if region is not None else None)
        if vector:
            closures = oplib.component_closures(
                names, [c.scheme for c in comps], stage)
        else:
            closures = [oplib.set_closure(names, comps[0].scheme, stage, axis)]
        routing = []
        for c, p, cl in zip(comps, placements, closures):
            if norm is not None:
                plan = region_mod.plan_region(c, norm, cl)
                word_idx = np.asarray(plan.payload_gather(c.bits).word_idx)
            else:
                word_idx = np.arange(
                    encode_mod.words_for(
                        int(np.prod(c.padded_shape, dtype=np.int64)), c.bits),
                    dtype=np.int64)
            routing.append(self._gather_routing(p, c.bits, word_idx)
                           + (len(word_idx),))

        key = (tuple(layout_key(c) for c in comps), names, stage, axis, norm,
               tuple(p.sig() for p in placements), mesh_sig(self.mesh),
               oplib.kernel_sig(), tuple(r[2] for r in routing),
               tuple(s.shape for s in stripes))
        fn = self._jitted.get(key)
        if fn is None:
            fn = region_program(self.mesh, names, stage, axis, norm,
                                tuple(r[2] for r in routing), vector)
            self._cache_put(key, fn)
        else:
            self._jitted.move_to_end(key)

        stripped = tuple(
            dataclasses.replace(c, payload=jnp.zeros((0,), jnp.uint32))
            for c in comps)
        srcs = tuple(jnp.asarray(r[0]) for r in routing)
        dsts = tuple(jnp.asarray(r[1]) for r in routing)
        return fn(stripped, tuple(stripes), srcs, dsts)

    # -- integer stage materialization ---------------------------------------
    def materialize(self, e: Encoded, stage: Stage, *, region=None,
                    closure="cover", placement: BlockPlacement | None = None,
                    stripes=None):
        """Stage-②/③ *integer* intermediate from shard-striped payload.

        Returns what ``oplib.StageContext`` keeps resident at the storage
        stage — the decoded ``sub`` container (stage ②) or the recorrelated
        ``q_spatial`` integers (stage ③) — computed from the psum-merged
        owned words inside one shard-mapped program.  Every array in either
        intermediate is int32, and integer reconstruction is exact under
        any compilation, so the result is bit-identical to the
        single-device ``repro.store.materialize`` — which is exactly what
        lets the sharded store seed the engine's standard (vmapped, jitted)
        float postludes and inherit the store's seeded == unseeded
        bit-identity guarantee.  The full-field stage-② path runs
        ``encode.decode_device`` on the merged payload, i.e. the Pallas
        bitplane-unpack kernel when kernels are enabled — the kernel
        backend composes inside the shard-mapped program, and kernel mode
        stays in the program key (``oplib.kernel_sig()``).
        """
        stage = Stage(stage)
        if stage not in (Stage.P, Stage.Q):
            raise ValueError(
                f"materializations are stage-② or -③ intermediates, got {stage}")
        if not isinstance(e, Encoded):
            raise TypeError("sharded materialization requires an Encoded field")
        if placement is None:
            placement = BlockPlacement.of(e, self.n_shards)
        self._check(placement)
        if stripes is None:
            stripes = self.shard_payload(e, placement)
        norm = (region_mod.normalize_region(region, e.shape)
                if region is not None else None)
        closure = region_mod.canonical_closure(e.scheme, closure, norm)
        if norm is not None:
            plan = region_mod.plan_region(e, norm, closure)
            word_idx = np.asarray(plan.payload_gather(e.bits).word_idx)
        else:
            word_idx = np.arange(
                encode_mod.words_for(
                    int(np.prod(e.padded_shape, dtype=np.int64)), e.bits),
                dtype=np.int64)
        src, dst = self._gather_routing(placement, e.bits, word_idx)
        n_out = len(word_idx)

        key = ("__shard_materialize__", layout_key(e), stage, norm, closure,
               placement.sig(), mesh_sig(self.mesh), oplib.kernel_sig(),
               n_out, tuple(stripes.shape))
        fn = self._jitted.get(key)
        if fn is None:
            fn = materialize_program(self.mesh, stage, norm, closure, n_out)
            self._cache_put(key, fn)
        else:
            self._jitted.move_to_end(key)

        stripped = dataclasses.replace(
            e, payload=jnp.zeros((0,), jnp.uint32))
        return fn(stripped, stripes, jnp.asarray(src), jnp.asarray(dst))

    # -- temporal summary merge ---------------------------------------------
    def merge_band_summaries(self, bands, win_rows: int,
                             rest: tuple[int, ...]):
        """Homomorphic all-reduce of per-band partial summaries.

        ``bands`` is a list of ``(owner_shard, row0, summary)`` where each
        summary covers rows ``[row0, row0 + rows)`` of a ``(win_rows,
        *rest)`` spatial window (leaves WITHOUT a batch axis).  Each shard
        scatters its bands into the window layout with merge-neutral
        padding (0 for modular sums and ``last2``, INT32_MAX/MIN for
        min/max) and a ``psum``/``pmin``/``pmax`` over the shard axis
        reassembles the full-window summary — int32-exact, so bit-identical
        to summarizing the whole window at once.
        """
        by_shard: list[list] = [[] for _ in range(self.n_shards)]
        for owner, row0, summ in bands:
            by_shard[int(owner) % self.n_shards].append((int(row0), summ))
        b_max = max(max((len(g) for g in by_shard), default=0), 1)
        r_max = max((int(s.q_sum.shape[0]) for _, _, s in bands), default=1)

        def stacked(leaf, neutral, lead=()):
            # [n_shards, b_max, *lead, r_max, *rest] with neutral padding
            full = jnp.full((*lead, r_max, *rest), neutral, jnp.int32)
            rows = []
            for g in by_shard:
                slots = []
                for _, s in g:
                    x = leaf(s)
                    pad = [(0, 0)] * len(lead) + \
                        [(0, r_max - x.shape[len(lead)])] + \
                        [(0, 0)] * len(rest)
                    slots.append(jnp.pad(x, pad, constant_values=neutral))
                slots += [full] * (b_max - len(slots))
                rows.append(jnp.stack(slots))
            return jnp.stack(rows)

        q_sum = stacked(lambda s: s.q_sum, 0)
        q_sumsq = stacked(lambda s: s.q_sumsq, 0)
        q_min = stacked(lambda s: s.q_min, _INT32_MAX)
        q_max = stacked(lambda s: s.q_max, _INT32_MIN)
        last2 = stacked(lambda s: s.last2, 0, lead=(2,))
        count = jnp.stack([
            jnp.stack([s.count for _, s in g] +
                      [jnp.zeros((), jnp.int32)] * (b_max - len(g)))
            for g in by_shard])
        offs = np.zeros((self.n_shards, b_max), np.int32)
        nrows = np.zeros((self.n_shards, b_max), np.int32)
        for s, g in enumerate(by_shard):
            for b, (row0, summ) in enumerate(g):
                offs[s, b] = row0
                nrows[s, b] = int(summ.q_sum.shape[0])

        key = ("__shard_summary_merge__", self.n_shards, b_max, r_max,
               win_rows, rest, mesh_sig(self.mesh))
        fn = self._jitted.get(key)
        if fn is None:
            def body(qs, qq, qn, qx, l2, ct, of, nr, _b=b_max, _r=r_max,
                     _w=win_rows, _rest=rest):
                sbuf = jnp.zeros((_w + 1, *_rest), jnp.int32)
                qbuf = jnp.zeros((_w + 1, *_rest), jnp.int32)
                nbuf = jnp.full((_w + 1, *_rest), _INT32_MAX, jnp.int32)
                xbuf = jnp.full((_w + 1, *_rest), _INT32_MIN, jnp.int32)
                lbuf = jnp.zeros((2, _w + 1, *_rest), jnp.int32)
                r = jnp.arange(_r)
                okx_shape = (_r,) + (1,) * len(_rest)
                for b in range(_b):
                    ok = r < nr[0, b]
                    idx = jnp.where(ok, of[0, b] + r, _w)
                    okx = ok.reshape(okx_shape)
                    sbuf = sbuf.at[idx].add(jnp.where(okx, qs[0, b], 0))
                    qbuf = qbuf.at[idx].add(jnp.where(okx, qq[0, b], 0))
                    nbuf = nbuf.at[idx].min(
                        jnp.where(okx, qn[0, b], _INT32_MAX))
                    xbuf = xbuf.at[idx].max(
                        jnp.where(okx, qx[0, b], _INT32_MIN))
                    lbuf = lbuf.at[:, idx].add(
                        jnp.where(okx[None], l2[0, b], 0))
                return oplib.TemporalSummary(
                    count=jax.lax.pmax(jnp.max(ct[0]), SHARD_AXIS),
                    q_sum=jax.lax.psum(sbuf[:_w], SHARD_AXIS),
                    q_sumsq=jax.lax.psum(qbuf[:_w], SHARD_AXIS),
                    q_min=jax.lax.pmin(nbuf[:_w], SHARD_AXIS),
                    q_max=jax.lax.pmax(xbuf[:_w], SHARD_AXIS),
                    last2=jax.lax.psum(lbuf[:, :_w], SHARD_AXIS))

            fn = jax.jit(compat.shard_map(
                body, mesh=self.mesh,
                in_specs=(P(SHARD_AXIS),) * 8, out_specs=P(), check=False))
            self._cache_put(key, fn)
        else:
            self._jitted.move_to_end(key)
        return fn(q_sum, q_sumsq, q_min, q_max, last2, count,
                  jnp.asarray(offs), jnp.asarray(nrows))


def spatial_bands(field, placement: BlockPlacement, region=None
                  ) -> list[tuple[int, int, int, tuple]]:
    """Owner-assigned spatial bands of a slab field's query window.

    Returns ``(owner, row0_in_window, unit_row0, band_region)`` per band,
    where ``band_region`` is the spatial sub-window the owning shard
    summarizes (rows of spatial axis 0, full extent elsewhere).  nd slab
    layouts band by the compressor's block-rows along slab axis 1 — exactly
    the placement's stripe units, so each band's q reconstruction is
    shard-local; flat layouts split the window into ``n_shards`` contiguous
    bands (block ownership interleaves timesteps there, so banding is a
    grouping heuristic — the merge stays exact either way).
    """
    spatial = field.shape[1:]
    win = (region_mod.normalize_region(region, spatial) if region is not None
           else tuple((0, s) for s in spatial))
    s0, e0 = win[0]
    rest = tuple(win[1:])
    bands = []
    if field.scheme.is_nd:
        h = field.block[1]
        for u in range(s0 // h, -(-e0 // h)):
            r0, r1 = max(s0, u * h), min(e0, (u + 1) * h)
            if r1 <= r0:
                continue
            bands.append((u % placement.n_shards, r0 - s0, r0,
                          ((r0, r1),) + rest))
    else:
        n = placement.n_shards
        h = max(1, -(-(e0 - s0) // n))
        for b in range(-(-(e0 - s0) // h)):
            r0, r1 = s0 + b * h, min(s0 + (b + 1) * h, e0)
            bands.append((b % n, r0 - s0, r0, ((r0, r1),) + rest))
    return bands
