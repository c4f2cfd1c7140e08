"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps."""
import numpy as np
import pytest
import jax.numpy as jnp
try:
    from hypothesis import given, strategies as st
except ImportError:  # optional dep: property-based tests self-skip
    from repro.testing import given, st

from repro.kernels import ops, ref


@pytest.mark.parametrize("shape", [(128, 256), (256, 512), (384, 256)])
@pytest.mark.parametrize("eps", [1e-1, 1e-3])
def test_quant_lorenzo2d(shape, eps):
    rng = np.random.default_rng(hash(shape) % 2**31)
    x = jnp.asarray(rng.normal(0, 3, shape).astype(np.float32))
    got = ops.quant_lorenzo2d(x, jnp.float32(eps))
    want = ref.quant_lorenzo2d(x, jnp.float32(eps))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("bits", list(range(0, 33)))
def test_bitpack_all_widths(bits):
    rng = np.random.default_rng(bits)
    n = 8192
    if bits == 0:
        u = jnp.zeros((n,), jnp.uint32)
    else:
        maxv = (1 << bits) - 1 if bits < 32 else 0xFFFFFFFF
        u = jnp.asarray((rng.integers(0, 2**31, n, dtype=np.uint32)
                         & np.uint32(maxv)))
    packed = ops.pack(u, bits)
    want = ref.pack_uniform(u, bits)
    np.testing.assert_array_equal(np.asarray(packed), np.asarray(want))
    out = ops.unpack(packed, n, bits)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(u))


@pytest.mark.parametrize("shape", [(130, 258), (258, 514)])
def test_stencils(shape):
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.integers(-10000, 10000, shape, dtype=np.int32))
    eps = jnp.float32(5e-3)
    d0, d1 = ops.grad2d(q, eps)
    r0, r1 = ref.stencil_dq_grad2d(q, eps)
    np.testing.assert_array_equal(np.asarray(d0), np.asarray(r0))
    np.testing.assert_array_equal(np.asarray(d1), np.asarray(r1))
    lap = ops.laplacian2d(q, eps)
    rl = ref.stencil_dq_laplacian2d(q, eps)
    np.testing.assert_array_equal(np.asarray(lap), np.asarray(rl))


@pytest.mark.parametrize("bits", list(range(1, 33)))
@pytest.mark.parametrize("n", [100, 4097, 5000])
def test_bitpack_tail_shapes(bits, n):
    """Word-layout parity with the XLA packer at non-multiple-of-VALS sizes.

    The kernel packer pads to VALS-multiples internally and slices; its words
    and recovered values must match ``encode.pack_uniform`` bit for bit so
    payloads produced by either path are interchangeable (decode_device
    routes Encoded payloads through the kernel unpacker)."""
    from repro.core import encode
    rng = np.random.default_rng(bits * 101 + n)
    maxv = (1 << bits) - 1 if bits < 32 else 0xFFFFFFFF
    u = jnp.asarray(rng.integers(0, 2**31, n, dtype=np.uint32)
                    & np.uint32(maxv))
    packed = ops.pack(u, bits)
    want = encode.pack_uniform(u, bits)
    np.testing.assert_array_equal(np.asarray(packed), np.asarray(want))
    out = ops.unpack(packed, n, bits)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(u))
    np.testing.assert_array_equal(
        np.asarray(encode.unpack_uniform(packed, n, bits)), np.asarray(u))


@pytest.mark.parametrize("nb,s", [(256, 128), (512, 256), (1024, 64)])
def test_block_stats(nb, s):
    rng = np.random.default_rng(nb)
    qb = jnp.asarray(rng.integers(-50000, 50000, (nb, s), dtype=np.int32))
    gm, gx = ops.block_stats(qb)
    rm, rx = ref.block_stats(qb)
    np.testing.assert_array_equal(np.asarray(gm), np.asarray(rm))
    np.testing.assert_array_equal(np.asarray(gx), np.asarray(rx))


@pytest.mark.parametrize("block", [(4, 4), (8, 8), (8, 16)])
def test_block_stats_signed_parity_with_core(block):
    """The kernel's per-block rounded mean must agree with the stage-①
    metadata the compressor actually stores (decorrelate.block_means) on
    signed data — both use exact round-half-up, floor((2s + c) / (2c)),
    where flooring (not truncating) the negative sums is the parity trap."""
    from repro.core import blocking, decorrelate
    rng = np.random.default_rng(11)
    q = jnp.asarray(rng.integers(-50000, 50000, (64, 48), dtype=np.int32))
    want = decorrelate.block_means(q, block)
    blocked = blocking.to_blocked(q, block)
    g0, g1, b0, b1 = blocked.shape
    gm, gx = ops.block_stats(blocked.reshape(g0 * g1, b0 * b1))
    np.testing.assert_array_equal(np.asarray(gm).reshape(g0, g1),
                                  np.asarray(want))
    u = np.asarray(blocked.reshape(g0 * g1, b0 * b1))
    zig = ((u << 1) ^ (u >> 31)).astype(np.uint32)
    np.testing.assert_array_equal(np.asarray(gx), zig.max(axis=1))


@pytest.mark.parametrize("shape", [(128, 256), (256, 384)])
def test_prefix_stats(shape):
    rng = np.random.default_rng(3)
    p = jnp.asarray(rng.integers(-8, 8, shape, dtype=np.int32))
    s1, s2 = ops.prefix_stats2d(p)
    r1, r2 = ref.prefix_stats2d(p)
    np.testing.assert_allclose(float(s1), float(r1), rtol=1e-5)
    np.testing.assert_allclose(float(s2), float(r2), rtol=1e-5)


@given(st.integers(1, 31), st.integers(1, 4))
def test_bitpack_roundtrip_property(bits, blocks):
    rng = np.random.default_rng(bits * 131 + blocks)
    n = 4096 * blocks
    u = jnp.asarray(rng.integers(0, 1 << bits, n, dtype=np.uint32))
    np.testing.assert_array_equal(
        np.asarray(ops.unpack(ops.pack(u, bits), n, bits)), np.asarray(u))


def test_kernel_pipeline_consistency(field_2d):
    """Fused kernels reproduce the reference pipeline end to end."""
    from repro.core import hszp_nd
    import repro.core.blocking as blocking
    x = jnp.asarray(np.ascontiguousarray(field_2d[:128, :64]))
    eps = jnp.float32(1e-3)
    p_kernel = ops.quant_lorenzo2d(x, eps)
    c = hszp_nd.compress(x, eps=eps)
    p_pipeline = blocking.crop(c.residuals, x.shape)
    np.testing.assert_array_equal(np.asarray(p_kernel), np.asarray(p_pipeline))


# shape, width: rows whose bits fill whole words and rows that do not (a
# 21x19x37 field pads to 24x24x40, so a 10-bit row is 12.5 words), rows of
# several lane tiles, rows of whole 128-lane blocks at widths the MXU lane
# prefix takes (13x7x250 pads to 16x8x256) and one it does not, and planes
# of 640 KiB, three to a slab, so the slab count does not divide the 8
# planes (7x250x637 pads to 8x256x640 on the MXU path, 8x509x317 to
# 8x512x320 on the shifted-add one)
LORENZO3D_CASES = [
    ((24, 20, 28), 10), ((21, 19, 37), 10), ((13, 7, 250), 1),
    ((13, 7, 250), 10), ((13, 7, 250), 17), ((13, 7, 250), 32),
    ((24, 20, 28), 0), ((24, 20, 28), 1), ((21, 19, 37), 1),
    ((24, 20, 28), 17), ((21, 19, 37), 17), ((24, 20, 28), 32),
    ((21, 19, 37), 32), ((16, 8, 300), 10), ((16, 8, 300), 17),
    ((8, 8, 500), 10), ((7, 250, 637), 17), ((8, 509, 317), 10),
]


@pytest.mark.parametrize(
    "shape,bits", LORENZO3D_CASES,
    ids=[f"{'x'.join(map(str, s))}-b{b}" for s, b in LORENZO3D_CASES])
def test_lorenzo3d_q_matches_xla_chain(shape, bits):
    """The payload-to-q kernel equals the XLA chain (unpack, unzigzag,
    three prefix sums, crop) bit for bit, on payloads of random words (so
    every bit of every value is exercised)."""
    import dataclasses
    from repro.core import Stage, blocking, hszp_nd, oplib
    from repro.kernels import fused
    rng = np.random.default_rng(sum(shape) * 40 + bits)
    c = hszp_nd.compress(jnp.asarray(rng.normal(0, 1, shape), jnp.float32),
                         rel_eb=1e-3)
    e = hszp_nd.encode(c, bits=bits)
    e = dataclasses.replace(e, payload=jnp.asarray(rng.integers(
        0, 2**32, e.payload.shape, dtype=np.uint64).astype(np.uint32)))
    assert fused.lorenzo3d_covers(e.padded_shape, bits)
    got = blocking.crop(fused.lorenzo3d_q(e.payload, e.padded_shape, bits,
                                          interpret=True), shape)
    with ops.override_mode("off"):
        want = oplib.StageContext(e, Stage.Q, None, "cover").q_spatial
    assert got.dtype == want.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
