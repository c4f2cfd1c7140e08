"""The 3-D vector kernel: divergence and curl of three stage-③ planes in one
Pallas pass (``kernels.fused.vector3d_q``, chosen by
``oplib.lower_vectors``).

Where it covers a vector (3 components of rank 3 at stage ③/④, full
field, rows in multiples of 8, lanes in multiples of 128) its answers
equal the XLA rules' bit for bit: cold and store-seeded, in ``compute``,
under the engine's ``vmap`` and in an expression DAG, which builds one
kernel call for divergence and curl together.  Everywhere else the XLA
rules run as before.  Programs are compared under ``jax.jit`` on both
sides, as the engine runs them.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.analytics import BatchedAnalytics, query
from repro.core import Stage, expr, hszp, hszp_nd, hszx_nd, oplib
from repro.kernels import fused as fk
from repro.kernels import ops as kernel_ops
from repro.store import materialize

COVERED = [(6, 16, 128), (10, 24, 256)]
UNCOVERED = (6, 10, 50)
ABS_EB = (1e-3, 2.5e-3, 7e-4)      # a different eps for each component
OPSETS = {"div": ("divergence",), "curl": ("curl",),
          "div+curl": ("divergence", "curl")}


def _components(shape, comp=hszp_nd, seed=0, encode=True):
    rng = np.random.default_rng([18, seed, *shape])
    axes = [np.linspace(0, 1, d, dtype=np.float32) for d in shape]
    out = []
    for c, eb in enumerate(ABS_EB):
        x = np.zeros(shape, np.float32)
        for a, g in enumerate(axes):
            line = np.sin(2 * np.pi * rng.uniform(1, 3) * g + c)
            x = x + line.reshape([-1 if j == a else 1 for j in range(3)])
        x = x + rng.normal(0, 0.05, shape).astype(np.float32)
        f = comp.compress(jnp.asarray(x), abs_eb=eb)
        out.append(comp.encode(f, bits=16) if encode else f)
    return tuple(out)


def _compute(comps, ops, stage, mode, **kw):
    with kernel_ops.override_mode(mode):
        fused0 = obs.counters["vector_fused"]
        fn = jax.jit(lambda cs: oplib.compute(list(cs), ops, stage, **kw))
        out = jax.block_until_ready(fn(comps))
        return out, obs.counters["vector_fused"] - fused0


def _leaves(out):
    return [np.asarray(x) for x in jax.tree.leaves(out)]


def _assert_bitwise(got, want):
    g, w = _leaves(got), _leaves(want)
    assert len(g) == len(w)
    for a, b in zip(g, w):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(a, b)


def test_tiles_cover_by_shape():
    assert fk.vector3d_tiles((512, 512, 512), 4) == (10, 32)
    assert fk.vector3d_tiles((512, 512, 512), 1) == (17, 32)
    for shape in [UNCOVERED, (6, 8, 128), (6, 16, 100), (16, 16),
                  (2, 16, 128), (100, 500, 500)]:
        assert fk.vector3d_tiles(shape, 4) is None
    k, r = fk.vector3d_tiles((10, 24, 256), 4)
    assert 8 % k == 0 and r == 16


@pytest.mark.parametrize("stage", [Stage.Q, Stage.F], ids=["q", "f"])
@pytest.mark.parametrize("opset", list(OPSETS))
@pytest.mark.parametrize("shape", COVERED, ids=lambda s: "x".join(map(str, s)))
def test_kernel_matches_xla_rules(shape, opset, stage):
    """Cold: the kernel branch equals the rules bit for bit, one build."""
    comps = _components(shape)
    got, n = _compute(comps, OPSETS[opset], stage, "interpret")
    want, n_off = _compute(comps, OPSETS[opset], stage, "off")
    assert (n, n_off) == (1, 0)
    _assert_bitwise(got, want)


@pytest.mark.parametrize("encode", [True, False], ids=["encoded", "container"])
def test_seeded_kernel_matches_cold_rules(encode):
    """Seeded from resident stage-③ planes (any scheme: ``q_spatial`` is
    what both read)."""
    comps = _components(COVERED[1], comp=hszx_nd, encode=encode)
    seeds = [materialize(c, Stage.Q) for c in comps]
    ops = ("divergence", "curl")
    with kernel_ops.override_mode("interpret"):
        fused0 = obs.counters["vector_fused"]
        fn = jax.jit(lambda cs, ss: oplib.compute(list(cs), ops, Stage.F,
                                                  seed=list(ss)))
        got = jax.block_until_ready(fn(comps, seeds))
        assert obs.counters["vector_fused"] - fused0 == 1
    want, _ = _compute(comps, ops, Stage.F, "off")
    _assert_bitwise(got, want)


def test_one_dimensional_scheme_takes_the_kernel():
    comps = _components(COVERED[0], comp=hszp)
    got, n = _compute(comps, ("divergence", "curl"), Stage.Q, "interpret")
    want, _ = _compute(comps, ("divergence", "curl"), Stage.Q, "off")
    assert n == 1
    _assert_bitwise(got, want)


def test_engine_vmap_batch_of_two():
    vecs = [_components(COVERED[0], seed=s) for s in (1, 2)]
    ops = ("divergence", "curl")
    out = {}
    for mode in ("interpret", "off"):
        with kernel_ops.override_mode(mode):
            fused0 = obs.counters["vector_fused"]
            out[mode] = BatchedAnalytics().run(vecs, ops, Stage.Q)
            out[mode + "_n"] = obs.counters["vector_fused"] - fused0
    assert (out["interpret_n"], out["off_n"]) == (1, 0)
    _assert_bitwise(out["interpret"], out["off"])
    # the batch equals each vector alone
    solo, _ = _compute(vecs[1], ops, Stage.Q, "interpret")
    np.testing.assert_array_equal(
        np.asarray(out["interpret"]["divergence"][1]),
        np.asarray(solo["divergence"]))


def test_expression_dag_builds_one_kernel_for_div_and_curl():
    uvw = _components(COVERED[1], seed=3)
    u = uvw[0]
    roots = [expr.divergence(uvw), expr.curl(uvw),
             expr.divergence(uvw) - 2.0 * expr.derivative(u, axis=0)]
    out = {}
    for mode in ("interpret", "off"):
        with kernel_ops.override_mode(mode):
            fused0 = obs.counters["vector_fused"]
            out[mode] = query(exprs=roots, stage=Stage.Q,
                              engine=BatchedAnalytics()).values[0]
            out[mode + "_n"] = obs.counters["vector_fused"] - fused0
    assert (out["interpret_n"], out["off_n"]) == (1, 0)
    _assert_bitwise(out["interpret"], out["off"])


@pytest.mark.parametrize("case", ["two_components", "region", "stage2",
                                  "uncovered_shape", "kernels_off"])
def test_fallbacks_keep_the_xla_rules(case):
    """No kernel build, and the answers the rules gave before."""
    shape = UNCOVERED if case == "uncovered_shape" else COVERED[0]
    comps = _components(shape)
    ops, stage, kw = ("divergence", "curl"), Stage.Q, {}
    mode = "off" if case == "kernels_off" else "interpret"
    if case == "two_components":
        comps = comps[:2]
    elif case == "region":
        kw = {"region": ((1, 5), (2, 14), (0, 100))}
    elif case == "stage2":
        stage = Stage.P
    got, n = _compute(comps, ops, stage, mode, **kw)
    assert n == 0
    want, _ = _compute(comps, ops, stage, "off", **kw)
    _assert_bitwise(got, want)


def test_lower_vectors_can_keep_the_rules():
    """``vector_kernel=False`` (the sharded store's full-field programs)."""
    comps = _components(COVERED[0])
    got, n = _compute(comps, ("divergence",), Stage.Q, "interpret",
                      vector_kernel=False)
    want, _ = _compute(comps, ("divergence",), Stage.Q, "off")
    assert n == 0
    _assert_bitwise(got, want)


def test_reads_seed_holds_for_the_kernel_cells():
    """Auto planning keeps choosing resident stage-③ planes."""
    c = _components(COVERED[0])[0]
    for op in ("divergence", "curl"):
        for stage in (Stage.Q, Stage.F):
            assert oplib.reads_seed(op, c, stage)
