"""Sharded field store: placement, mesh helper, bit-identity, semantics.

Placement and planner logic is pure host code and runs in-process (the
main test process stays single-device — XLA's device count is locked at
first jax init).  Everything that needs real shard_map collectives runs in
a subprocess with 8 fake devices, mirroring ``tests/test_comm.py``: the
subprocess executes the full (scheme x op-set x stage x region) matrix
against the single-device reference and prints one JSON verdict dict the
in-process tests assert on.  The matrix runs once per kernel mode
(``REPRO_KERNELS=off`` / ``interpret``) — the Pallas backend must compose
inside the shard-mapped program.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import Stage, by_name
from repro.core import region as region_mod
from repro.launch.mesh import SHARD_AXIS, make_analytics_mesh
from repro.shard import BlockPlacement, ShardedFieldStore, spatial_bands
from repro.store import FieldStore

SCHEMES = ("hszp", "hszx", "hszp_nd", "hszx_nd")


def _field(scheme, shape=(256, 192), rel_eb=1e-2, seed=0):
    rng = np.random.default_rng(seed)
    data = jnp.asarray(np.cumsum(rng.normal(size=shape), axis=0), jnp.float32)
    comp = by_name(scheme)
    return comp.encode(comp.compress(data, rel_eb=rel_eb))


# ---------------------------------------------------------------------------
# mesh helper
# ---------------------------------------------------------------------------

def test_make_analytics_mesh_defaults_to_all_devices():
    mesh = make_analytics_mesh()
    assert mesh.axis_names == (SHARD_AXIS,)
    assert mesh.devices.size == len(jax.devices())


def test_make_analytics_mesh_validates_count():
    with pytest.raises(ValueError, match="devices"):
        make_analytics_mesh(len(jax.devices()) + 1)
    with pytest.raises(ValueError):
        make_analytics_mesh(0)


# ---------------------------------------------------------------------------
# placement (pure host logic)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("scheme", SCHEMES)
def test_word_partition_is_exact(scheme):
    """Every payload word has exactly one owner; the per-shard word index
    lists are a disjoint, ascending partition of all words."""
    e = _field(scheme)
    p = BlockPlacement.of(e, 8)
    owners = p.word_owner(e.bits)
    n_words = int(e.payload.size)
    assert owners.shape == (n_words,)
    assert owners.min() >= 0 and owners.max() < 8
    stripes = p.shard_word_index(e.bits)
    seen = np.concatenate(stripes)
    assert len(seen) == n_words
    assert sorted(seen.tolist()) == list(range(n_words))
    for s, idx in enumerate(stripes):
        assert (owners[idx] == s).all()
        if len(idx) > 1:
            assert (np.diff(idx) > 0).all()


def test_striping_cycles_over_shards():
    e = _field("hszx_nd")          # (256, 192), block (16, 16): 16 stripe units
    p = BlockPlacement.of(e, 8)
    assert p.n_units == 16
    # consecutive stripe units cycle round-robin over the shards, so every
    # shard owns the same number of units and they interleave
    for s in range(8):
        assert (p.units_of(s) % 8 == s).all()
        assert len(p.units_of(s)) == 2
    cols = p.grid[1]
    block_ids = np.arange(p.n_units * cols)
    assert (p.owner_of_blocks(block_ids)
            == (block_ids // cols) % 8).all()


@pytest.mark.parametrize("scheme", SCHEMES)
def test_payload_bytes_partition_and_locality(scheme):
    """Per-shard region bytes partition the single-device gather bytes, and
    a quarter-row window keeps the busiest shard under the 0.5x CI gate."""
    from repro.core import oplib

    e = _field(scheme)
    region = ((64, 128), (0, 192))     # 1/4 of the rows, off the origin
    cl = oplib.set_closure(("mean",), e.scheme, Stage.Q, 0)
    plan = region_mod.plan_region(
        e, region_mod.normalize_region(region, e.shape), cl)
    p = BlockPlacement.of(e, 8)
    acct = p.payload_bytes(plan, e.bits)
    assert sum(acct["per_shard_bytes"]) == acct["single_bytes"]
    assert acct["max_shard_bytes"] == max(acct["per_shard_bytes"])
    assert set(acct["participants"]) <= set(range(8))
    assert acct["max_shard_bytes"] < 0.5 * acct["single_bytes"], acct


@pytest.mark.parametrize("scheme", SCHEMES)
def test_max_fraction_full_field(scheme):
    e = _field(scheme)
    p = BlockPlacement.of(e, 8)
    # striped placement: no shard owns much more than 1/8 of the blocks
    assert 1 / 8 <= p.max_fraction(None) <= 1 / 8 + 8 / max(p.n_units, 1)


def test_spatial_bands_cover_window():
    e = _field("hszx_nd", shape=(3, 96, 64))
    p = BlockPlacement.of(e, 8, axis=1)
    for region in (None, ((10, 60), (8, 56))):
        bands = spatial_bands(e, p, region)
        win = (region_mod.normalize_region(region, e.shape[1:])
               if region is not None else tuple((0, s) for s in e.shape[1:]))
        rows = sorted((b[3][0][0], b[3][0][1]) for b in bands)
        assert rows[0][0] == win[0][0] and rows[-1][1] == win[0][1]
        for (a, b), (c, d) in zip(rows, rows[1:]):
            assert b == c          # contiguous, non-overlapping
        assert all(0 <= b[0] < 8 for b in bands)


# ---------------------------------------------------------------------------
# planner max-over-shards rule
# ---------------------------------------------------------------------------

def test_planner_max_shard_fraction_bounds():
    from repro.analytics.planner import _max_shard_fraction

    e = _field("hszx_nd")
    p = BlockPlacement.of(e, 8)
    region = region_mod.normalize_region(((64, 128), (0, 192)), e.shape)
    single = region_mod.closure_fraction(e, "mean", Stage.Q, region, axis=0)
    sharded = _max_shard_fraction(e, "mean", Stage.Q, region, 0, p)
    assert 0 < sharded <= single
    # full field: the busiest shard decodes ~1/8 of the blocks, not all
    assert _max_shard_fraction(e, "mean", Stage.Q, None, 0, p) < 0.2
    # stage (1) touches metadata only -> placement-blind spatial fraction
    m = _max_shard_fraction(e, "mean", Stage.M, region, 0, p)
    assert m == region_mod.closure_fraction(e, "mean", Stage.M, region, axis=0)


def test_plan_stages_accepts_placement():
    from repro.analytics.planner import plan_stages

    e = _field("hszx_nd")
    p = BlockPlacement.of(e, 8)
    plan = plan_stages(e.scheme, ("mean", "std"), "auto", None,
                       region=((64, 128), (0, 192)), field=e, placement=p)
    assert plan.fused is not None or len(plan.stages) == 2


# ---------------------------------------------------------------------------
# sharded store semantics reachable on one device
# ---------------------------------------------------------------------------

def test_sharded_store_requires_encoded():
    comp = by_name("hszx_nd")
    c = comp.compress(jnp.ones((32, 32), jnp.float32), rel_eb=1e-2)
    store = ShardedFieldStore(make_analytics_mesh(1))
    with pytest.raises(TypeError, match="encode"):
        store.put("f", c)


def test_router_membership_and_rejection():
    from repro.serve import StoreRouter

    sh = ShardedFieldStore(make_analytics_mesh(1))
    local = FieldStore()
    e = _field("hszx_nd", shape=(64, 48))
    sh.put("big", e)
    local.put("small", e)
    r = StoreRouter(sh, local)
    assert "big" in r and "small" in r and "nope" not in r
    assert r.get("big") is sh.get("big")
    assert r.get("small") is local.get("small")
    assert set(r.ids()) == {"big", "small"}
    with pytest.raises(KeyError, match="big.*small|small.*big"):
        r.get("nope")
    with pytest.raises(ValueError, match="already registered"):
        r.put("big", e)          # id lives in the sharded store
    assert r.placement_of("big") is not None
    assert r.placement_of("small") is None
    with pytest.raises(TypeError, match="streaming"):
        r.append("small", jnp.ones((1, 64, 48)))


def test_router_without_local_store():
    from repro.serve import StoreRouter

    sh = ShardedFieldStore(make_analytics_mesh(1))
    sh.put("only", _field("hszp", shape=(64, 48)))
    r = StoreRouter(sh)
    assert "only" in r and r.get("only") is sh.get("only")
    with pytest.raises(ValueError, match="no local store"):
        r.put("x", _field("hszp", shape=(64, 48)))


# ---------------------------------------------------------------------------
# 8-device matrix (subprocess: collectives need a multi-device mesh)
# ---------------------------------------------------------------------------

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
from functools import reduce

import numpy as np
import jax, jax.numpy as jnp

from repro.analytics.engine import BatchedAnalytics
from repro.analytics.query import query
from repro.core import Stage, by_name, oplib
from repro.launch.mesh import make_analytics_mesh
from repro.serve import AnalyticsFrontend, AnalyticsRequest, AppendRequest, \
    StoreRouter
from repro.shard import BlockPlacement, ShardPrograms, ShardedFieldStore
from repro.store import FieldStore, materialize, materialized_nbytes
from repro.stream import StreamFieldStore, TemporalField, query_temporal

out = {"failures": []}

def check(name, ok):
    out[name] = bool(ok)
    if not ok:
        out["failures"].append(name)

def eq_tree(a, b):
    fa, fb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(fa) == len(fb) and all(
        np.asarray(x).dtype == np.asarray(y).dtype
        and np.array_equal(np.asarray(x), np.asarray(y), equal_nan=True)
        for x, y in zip(fa, fb))

rng = np.random.default_rng(0)
data = jnp.asarray(np.cumsum(rng.normal(size=(128, 96)), axis=0), jnp.float32)
mesh = make_analytics_mesh(8)
progs = ShardPrograms(mesh)
REGION = ((16, 80), (8, 72))

# --- (scheme x op-set x stage x region) bit-identity, ops inside shard_map --
for scheme in ("hszp", "hszx", "hszp_nd", "hszx_nd"):
    comp = by_name(scheme)
    e = comp.encode(comp.compress(data, rel_eb=1e-2))
    cells = [(("mean", "std"), Stage.Q), (("mean",), Stage.P),
             (("mean",), Stage.F)]
    if comp.scheme.is_blockmean:
        cells.append((("mean",), Stage.M))
    for ops, stage in cells:
        for region in (None, REGION):
            tag = f"exec/{scheme}/{'+'.join(ops)}/{stage.name}/" \
                  f"{'region' if region else 'full'}"
            try:
                ref = jax.jit(lambda enc, _o=ops, _s=stage, _r=region:
                              oplib.compute(enc, _o, _s, region=_r))(e)
            except Exception as ex:
                try:
                    progs.region_compute(e, ops, stage, region=region)
                    check(tag + "/raises", False)
                except Exception:
                    check(tag + "/raises", True)
                continue
            got = progs.region_compute(e, ops, stage, region=region)
            check(tag, eq_tree(ref, got))

# --- shard-map materialize == single-device materialize ---------------------
for scheme in ("hszp", "hszx_nd"):
    comp = by_name(scheme)
    e = comp.encode(comp.compress(data, rel_eb=1e-2))
    for stage in (Stage.P, Stage.Q):
        for region in (None, REGION):
            ref = materialize(e, stage, region=region)
            got = progs.materialize(e, stage, region=region)
            leaf = ref.sub if stage == Stage.P else ref.q_spatial
            check(f"mat/{scheme}/{stage.name}/"
                  f"{'region' if region else 'full'}", eq_tree(leaf, got))

# --- store-vs-store query bit-identity (seeded engine programs) -------------
for scheme in ("hszp", "hszx", "hszp_nd", "hszx_nd"):
    comp = by_name(scheme)
    e = comp.encode(comp.compress(data, rel_eb=1e-2))
    ref_store, sh_store = StreamFieldStore(), ShardedFieldStore(mesh)
    ref_store.put("f", e); sh_store.put("f", e)
    for region in (None, REGION):
        for ops, stage in ((["mean", "std"], Stage.Q), ("mean", "auto"),
                           ("laplacian", Stage.F)):
            r1 = query(["f"], ops, stage, region=region, store=ref_store)
            r2 = query(["f"], ops, stage, region=region, store=sh_store)
            r3 = query(["f"], ops, stage, region=region, store=sh_store)
            tag = f"store/{scheme}/{ops if isinstance(ops, str) else '+'.join(ops)}/" \
                  f"{'region' if region else 'full'}"
            check(tag, eq_tree(r1.values[0], r2.values[0])
                  and eq_tree(r2.values[0], r3.values[0]))
    st = sh_store.stats
    check(f"store/{scheme}/hits", st.hits > 0)
    # store-backed auto planning: the sharded store plans what the
    # single-device store plans (Lorenzo std at stage ② would run prefix
    # sums over the resident residuals, so both go to ③)
    want = Stage.Q if comp.scheme.is_lorenzo else Stage.P
    cold = (StreamFieldStore(), ShardedFieldStore(mesh))
    for s in cold:
        s.put("f", e)
    plans = [query(["f"], ["mean", "std"], "auto", store=s).stages[0]
             for s in cold + cold]
    check(f"plan/{scheme}", all(p == {"mean": want, "std": want}
                                for p in plans))

# --- per-shard byte budgets: eviction on one shard leaves siblings ----------
comp = by_name("hszx_nd")
e = comp.encode(comp.compress(
    jnp.asarray(np.cumsum(rng.normal(size=(256, 96)), axis=0), jnp.float32),
    rel_eb=1e-2))
rA = ((0, 16), (0, 96))      # block-row 0 -> home shard 0
rB = ((16, 32), (0, 96))     # block-row 1 -> home shard 1
rC = ((128, 144), (0, 96))   # another row homed on shard 0 (unit 8)
budget = materialized_nbytes(e, Stage.Q, region=rA) + 64
sv = ShardedFieldStore(mesh, cache_bytes_per_shard=budget)
sv.put("f", e)
hA = sv.shard_of("f", Stage.Q, region=rA)
hB = sv.shard_of("f", Stage.Q, region=rB)
hC = sv.shard_of("f", Stage.Q, region=rC)
check("evict/homes-differ", hA != hB and hA == hC)
sv.ensure("f", Stage.Q, region=rA)
sv.ensure("f", Stage.Q, region=rB)
check("evict/both-resident", sv.is_resident("f", Stage.Q, region=rA)
      and sv.is_resident("f", Stage.Q, region=rB))
sv.ensure("f", Stage.Q, region=rC)   # overflows shard hA's budget only
check("evict/lru-evicted-on-home", not sv.is_resident("f", Stage.Q, region=rA))
check("evict/sibling-survives", sv.is_resident("f", Stage.Q, region=rB)
      and sv.is_resident("f", Stage.Q, region=rC))
check("evict/counted", sv.stats.evictions == 1
      and sv.shard_stats[hA].evictions == 1
      and sv.shard_stats[hB].evictions == 0)
got = query(["f"], "mean", Stage.Q, region=rA, store=sv).values[0]
ref = query(["f"], "mean", Stage.Q, region=rA,
            store=(lambda s: (s.put("f", e), s)[1])(StreamFieldStore())
            ).values[0]
check("evict/recompute-bitident", eq_tree(ref, got))

# --- temporal: banded summaries, owning-shard-only append refresh -----------
slabs = [np.cumsum(rng.normal(size=(4, 70, 64)), axis=1).astype(np.float32)
         for _ in range(3)]
for scheme in ("hszp", "hszx_nd"):
    comp = by_name(scheme)
    ref_store, sh_store = StreamFieldStore(), ShardedFieldStore(mesh)
    ref_store.put_temporal("t", TemporalField(comp, rel_eb=1e-2))
    sh_store.put_temporal("t", TemporalField(comp, rel_eb=1e-2))
    for s in slabs[:2]:
        ref_store.append("t", jnp.asarray(s))
        sh_store.append("t", jnp.asarray(s))
    regions = (None, ((8, 52), (10, 60)))
    for region in regions:
        a = query_temporal(["t"], ["tmean", "tstd"], region=region,
                           store=ref_store).values[0]
        b = query_temporal(["t"], ["tmean", "tstd"], region=region,
                           store=sh_store).values[0]
        check(f"temporal/{scheme}/{'region' if region else 'full'}",
              eq_tree(a, b))
    # both summary cells now resident; each lives on exactly one shard
    keys = [k for ch in sh_store._shards for k in ch._cache if k[0] == "t"]
    check(f"temporal/{scheme}/one-owner-per-cell", len(keys) == 2
          and len(set(keys)) == 2)
    owners = {k: [i for i, ch in enumerate(sh_store._shards)
                  if k in ch._cache] for k in keys}
    check(f"temporal/{scheme}/single-shard-cells",
          all(len(v) == 1 for v in owners.values()))
    before = {i: dict(ch._cache) for i, ch in enumerate(sh_store._shards)}
    merges0 = sh_store.incremental_merges
    ref_store.append("t", jnp.asarray(slabs[2]))
    sh_store.append("t", jnp.asarray(slabs[2]))
    check(f"temporal/{scheme}/incremental", sh_store.incremental_merges
          == merges0 + 2)
    # the refresh replaced cells in place on their owning shards only
    for i, ch in enumerate(sh_store._shards):
        owned = [k for k in before[i] if k[0] == "t"]
        foreign_ok = all(k in ch._cache for k in before[i])
        check(f"temporal/{scheme}/shard{i}-keys-stable",
              foreign_ok and set(k for k in ch._cache if k[0] == "t")
              == set(owned))
    for region in regions:
        a = query_temporal(["t"], ["tmean", "tstd", "tdelta"], region=region,
                           store=ref_store).values[0]
        b = query_temporal(["t"], ["tmean", "tstd", "tdelta"], region=region,
                           store=sh_store).values[0]
        check(f"temporal/{scheme}/post-append/"
              f"{'region' if region else 'full'}", eq_tree(a, b))

# --- serve routing: unknown ids reject per-request ---------------------------
sh_store = ShardedFieldStore(mesh)
local = StreamFieldStore()
e = by_name("hszx_nd").encode(by_name("hszx_nd").compress(data, rel_eb=1e-2))
sh_store.put("big", e)
local.put("small", e)
local.put_temporal("t", TemporalField("hszx_nd", rel_eb=1e-2))
fe = AnalyticsFrontend(store=StoreRouter(sh_store, local))
fe.add_request(AnalyticsRequest(uid=1, fields="big", op="mean",
                                region=REGION))
fe.add_request(AnalyticsRequest(uid=2, fields="small", op="mean"))
fe.add_request(AnalyticsRequest(uid=3, fields="nope", op="mean"))
fe.add_request(AppendRequest(uid=4, field_id="t", data=jnp.asarray(slabs[0])))
fe.add_request(AnalyticsRequest(uid=5, fields="t", op="tmean"))
done = {r.uid: r for r in fe.run_until_drained()}
check("serve/sharded-ok", done[1].error is None)
check("serve/local-ok", done[2].error is None)
check("serve/unknown-rejected", done[3].error is not None
      and "unknown field id" in done[3].error)
check("serve/append-ok", done[4].error is None and done[4].slab_index == 0)
check("serve/temporal-ok", done[5].error is None)
ref = query(["big"], "mean", region=REGION, store=sh_store).values[0]
check("serve/value-bitident", eq_tree(ref, done[1].result))

print(json.dumps(out))
"""


@pytest.fixture(scope="module", params=["off", "interpret"])
def shard_results(request):
    env = dict(os.environ, PYTHONPATH="src", REPRO_KERNELS=request.param)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, "-c", SCRIPT], capture_output=True, text=True,
        env=env, cwd=os.path.dirname(os.path.dirname(__file__)))
    assert r.returncode == 0, r.stderr[-4000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def _failing(results, prefix):
    return [k for k in results["failures"] if k.startswith(prefix)]


def test_exec_bit_identity_matrix(shard_results):
    """shard_map region/full op sets == the jitted single-device compute,
    bitwise, for every (scheme, op-set, stage, +-region) cell."""
    assert not _failing(shard_results, "exec/"), shard_results["failures"]


def test_materialize_bit_identity(shard_results):
    assert not _failing(shard_results, "mat/"), shard_results["failures"]


def test_store_query_bit_identity(shard_results):
    assert not _failing(shard_results, "store/"), shard_results["failures"]


def test_sharded_auto_plan_matches_single_device(shard_results):
    assert _failing(shard_results, "plan/") == []
    assert sum(k.startswith("plan/") for k in shard_results) == 4


def test_eviction_is_per_shard(shard_results):
    """Evicting on one shard leaves the sibling materialization on another
    shard resident, and the evicted cell recomputes bit-identically."""
    assert not _failing(shard_results, "evict/"), shard_results["failures"]


def test_temporal_append_refreshes_owning_shard_only(shard_results):
    assert not _failing(shard_results, "temporal/"), shard_results["failures"]


def test_serve_routing_rejects_per_request(shard_results):
    assert not _failing(shard_results, "serve/"), shard_results["failures"]
