"""Compile-only checks of the main-path programs for a described TPU v5e.

Nothing runs here: each program is lowered and compiled by the TPU
compiler for a chip described by ``jax.experimental.topologies``, so what
Mosaic or XLA would refuse on the chip (block shapes off the (8, 128)
tiling, unsupported in-kernel ops, too much VMEM) fails here at no chip
time.  Kernels are compiled at Ocean's published width (2400x3600,
bits=12) in the form the engine runs them: solo and under ``jax.vmap``
with a batch of 2, which gives the Pallas grid a batch dimension.  The
sharded word-merge programs (uint32 scatter-add + ``psum``) compile over a
``v5e:2x2`` mesh.

One case runs only on a chip: the 3-D vector kernel against the XLA
rules, bitwise, at 512^3 (``test_vector3d_q_bitwise_on_chip``; it skips
elsewhere, where its compile cases stand for it).

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, and it keeps it until it
exits.  The kernels are called with ``interpret=False`` because the default
backend here is still the CPU.
"""
import dataclasses
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import Stage, by_name, oplib
from repro.core import region as region_mod
from repro.kernels import bitpack
from repro.kernels import fused as fk
from repro.launch.mesh import SHARD_AXIS
from repro.shard import BlockPlacement
from repro.shard import exec as shard_exec

OCEAN = (2400, 3600)
BITS = 12
BLOCK = (16, 16)
N_WORDS = -(-OCEAN[0] * OCEAN[1] * BITS // 32)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _words(n):
    return ("words", (n,), jnp.uint32)


def _plane(dtype=jnp.int32):
    return ("plane", OCEAN, dtype)


def _meta():
    return ("meta", (OCEAN[0] // BLOCK[0], OCEAN[1] // BLOCK[1]), jnp.int32)


#: name -> (kernel call, argument shapes)
KERNELS = {"bitpack.unpack": (
    lambda w: bitpack.unpack(w, OCEAN[0] * OCEAN[1], BITS), [_words(N_WORDS)])}
for _what in ("deriv0", "deriv1", "grad", "lap"):
    KERNELS[f"lorenzo2d.{_what}"] = (
        lambda p, _w=_what: fk.lorenzo2d(p, what=_w), [_plane()])
    KERNELS[f"lorenzo_enc2d.{_what}"] = (
        lambda w, _w=_what: fk.lorenzo_enc2d(w, OCEAN, BITS, what=_w),
        [_words(N_WORDS)])
for _what in ("deriv0", "deriv1", "grad", "lap_p", "lap_q"):
    KERNELS[f"blockmean2d.{_what}"] = (
        lambda p, m, _w=_what: fk.blockmean2d(p, m, BLOCK, what=_w),
        [_plane(), _meta()])
    KERNELS[f"blockmean_enc2d.{_what}"] = (
        lambda w, m, _w=_what: fk.blockmean_enc2d(w, m, OCEAN, BLOCK, BITS,
                                                  what=_w),
        [_words(N_WORDS), _meta()])


@pytest.mark.parametrize("batch", [None, 2], ids=["solo", "vmap2"])
@pytest.mark.parametrize("name", list(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name, batch):
    fn, args = KERNELS[name]
    if batch is not None:
        fn = jax.vmap(fn)
    lead = () if batch is None else (batch,)
    shapes = [jax.ShapeDtypeStruct(lead + shape, dtype, sharding=one_chip)
              for _, shape, dtype in args]
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 1


def _abstract_encoded(shape, bits):
    """An Encoded container of ``shape`` as shapes only (no data)."""
    comp = by_name("hszp_nd")
    return jax.eval_shape(
        lambda x: comp.encode(comp.compress(x, eps=jnp.float32(1e-3)),
                              bits=bits),
        jax.ShapeDtypeStruct(shape, jnp.float32))


@pytest.mark.parametrize("program", ["region", "materialize"])
def test_sharded_word_merge_compiles_for_v5e_2x2(topo, program):
    """The sharded store's word-merge programs over a 4-chip mesh, for a
    windowed stage-3 query of an Ocean field: each shard scatter-adds its
    owned uint32 words, a psum reassembles them, the op set decodes."""
    mesh = Mesh(np.asarray(topo.devices[:4]), (SHARD_AXIS,))
    e = _abstract_encoded(OCEAN, BITS)
    region = region_mod.normalize_region(((600, 1800), (900, 2700)), OCEAN)
    names = ("laplacian", "mean")
    closure = region_mod.canonical_closure(
        e.scheme, oplib.set_closure(names, e.scheme, Stage.Q, 0), region)
    plan = region_mod.plan_region(e, region, closure)
    word_idx = np.asarray(plan.payload_gather(BITS).word_idx)
    placement = BlockPlacement.of(e, 4)
    src, dst = shard_exec.gather_routing(4, placement, BITS, word_idx)
    w_max = max(len(i) for i in placement.shard_word_index(BITS))

    rep = NamedSharding(mesh, P())
    split = NamedSharding(mesh, P(SHARD_AXIS))
    stripped = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep),
        dataclasses.replace(e, payload=jax.ShapeDtypeStruct((0,),
                                                            jnp.uint32)))
    stripes = jax.ShapeDtypeStruct((4, w_max), jnp.uint32, sharding=split)
    srcs = jax.ShapeDtypeStruct(src.shape, jnp.int32, sharding=split)
    dsts = jax.ShapeDtypeStruct(dst.shape, jnp.int32, sharding=split)
    if program == "region":
        fn = shard_exec.region_program(mesh, names, Stage.Q, 0, region,
                                       (len(word_idx),), False)
        lowered = fn.lower((stripped,), (stripes,), (srcs,), (dsts,))
    else:
        fn = shard_exec.materialize_program(mesh, Stage.Q, region, closure,
                                            len(word_idx))
        lowered = fn.lower(stripped, stripes, srcs, dsts)
    text = lowered.compile().as_text()
    assert "all-reduce" in text


@pytest.mark.parametrize("padded,shape", [
    ((512, 512, 512), (512, 512, 512)), ((104, 504, 504), (100, 500, 500))],
    ids=["nyx", "hurricane"])
def test_lorenzo3d_q_program_compiles_for_v5e(one_chip, padded, shape):
    """The store's one-dispatch stage-③ program (payload-to-q kernel and
    crop) at NYX and Hurricane widths: rows of whole words with the MXU
    lane prefix, and 12.5-word rows with the shifted-add prefix.  Never
    under ``vmap``: its grid carries a plane."""
    from repro.core import blocking
    n_words = -(-padded[0] * padded[1] * padded[2] * 10 // 32)
    words = jax.ShapeDtypeStruct((n_words,), jnp.uint32, sharding=one_chip)
    fn = jax.jit(lambda w: blocking.crop(fk.lorenzo3d_q(w, padded, 10),
                                         shape))
    compiled = fn.lower(words).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1


NYX = (512, 512, 512)


def _vector_rules(u, v, w, eps, div, curl):
    """The XLA rules of ``oplib`` at stage ③ in 3-D, term for term."""
    def d(q, a, e):
        return oplib._central_diff(q, a, e)

    out = ()
    if div:
        out += (d(u, 0, eps[0]) + d(v, 1, eps[1]) + d(w, 2, eps[2]),)
    if curl:
        out += (d(w, 1, eps[2]) - d(v, 2, eps[1]),
                d(u, 2, eps[0]) - d(w, 0, eps[2]),
                d(v, 0, eps[1]) - d(u, 1, eps[0]))
    return out


@pytest.mark.parametrize("batch", [None, 2], ids=["solo", "vmap2"])
@pytest.mark.parametrize("div,curl", [(True, True), (True, False),
                                      (False, True)],
                         ids=["div+curl", "div", "curl"])
def test_vector3d_q_compiles_for_v5e(one_chip, div, curl, batch):
    """The divergence/curl kernel at NYX's 512^3, as ``compute`` and the
    engine's ``vmap`` run it: one kernel call for the whole op set."""
    fn = lambda u, v, w, e: fk.vector3d_q(u, v, w, e, div=div, curl=curl)
    if batch is not None:
        fn = jax.vmap(fn)
    lead = () if batch is None else (batch,)
    q = jax.ShapeDtypeStruct(lead + NYX, jnp.int32, sharding=one_chip)
    e = jax.ShapeDtypeStruct(lead + (3,), jnp.float32, sharding=one_chip)
    compiled = jax.jit(fn).lower(q, q, q, e).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1


def test_vector3d_q_bitwise_on_chip():
    """On the chip: the kernel at 512^3 equals the XLA rules bit for bit.
    Skipped off the chip (the compile cases above stand for it there)."""
    if jax.default_backend() != "tpu":
        pytest.skip("needs a TPU: runs the kernel at 512^3")
    keys = jax.random.split(jax.random.PRNGKey(18), 3)
    u, v, w = (jax.random.randint(k, NYX, -500, 501, jnp.int32)
               for k in keys)
    eps = jnp.asarray([1.3e-3, 7.1e-4, 2.9e-3], jnp.float32)
    for div, curl in [(True, True), (True, False), (False, True)]:
        got = jax.jit(lambda *a: fk.vector3d_q(*a, div=div, curl=curl))(
            u, v, w, eps)
        want = jax.jit(lambda *a: _vector_rules(*a, div, curl))(u, v, w, eps)
        for g, x in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(x))
