"""Fused Pallas decode+op kernel lowering: bit-identity pins + fallback proofs.

The contract (ISSUE 8 acceptance):

* every covered (scheme-family, op, stage) cell is *bitwise* identical to
  the XLA lowering — ``np.testing.assert_array_equal``, never allclose —
  for Compressed and Encoded containers, full-field and region-windowed;
* the identity holds in every program shape that composes fused outputs:
  the engine's vmap-batched multivariate path and expression DAGs must
  match per-field / composed single-op results bit for bit (the regression
  trap: a trailing in-kernel eps multiply FMA-contracts into downstream
  adds shape-dependently — see repro.core.fused);
* uncovered cells provably fall back to the XLA rules: the lorenzo ③④
  laplacian has no registry entry, non-2-D contexts fail ``covers``, and
  ``REPRO_KERNELS=off`` deselects every fused rule — all three resolve to
  plain XLA rules through the same ``select_rule`` dispatch.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro import analytics
from repro.core import Stage, expr, homomorphic as H, oplib
from repro.core import fused as fused_mod
from repro.core.encode import decode_device
from repro.core.pipeline import by_name
from repro.data.scientific import dataset_dims, synth_field
from repro.kernels import fused as fk
from repro.kernels import ops as kops
from repro.launch import hlo_analysis

ND_SCHEMES = ["hszp_nd", "hszx_nd"]
STAGES = [Stage.P, Stage.Q, Stage.F]
REGION = ((30, 75), (10, 52))  # unaligned window of the 181x97 field

OPCALLS = {
    "deriv0": lambda f, s, r: H.derivative(f, s, 0, region=r),
    "deriv1": lambda f, s, r: H.derivative(f, s, 1, region=r),
    "gradient": lambda f, s, r: H.gradient(f, s, region=r),
    "laplacian": lambda f, s, r: H.laplacian(f, s, region=r),
}


@pytest.fixture(scope="module", params=ND_SCHEMES)
def pair_2d(request, field_2d):
    """(Compressed, Encoded) of the session 2-D field, one nd scheme."""
    comp = by_name(request.param, (8, 8))
    c = comp.compress(jnp.asarray(field_2d), abs_eb=1e-3)
    return c, comp.encode(c)


def _ab(call):
    """Run ``call`` with the fused backend (default) and with kernels off."""
    got = call()
    with kops.override_mode("off"):
        want = call()
    got = got if isinstance(got, tuple) else (got,)
    want = want if isinstance(want, tuple) else (want,)
    return got, want


# ===========================================================================
# per-cell bit-identity
# ===========================================================================

@pytest.mark.parametrize("container", ["compressed", "encoded"])
@pytest.mark.parametrize("region", [None, REGION], ids=["full", "window"])
@pytest.mark.parametrize("stage", STAGES, ids=lambda s: s.name)
@pytest.mark.parametrize("op", list(OPCALLS))
def test_cell_bit_identity(pair_2d, container, region, stage, op):
    fld = pair_2d[0] if container == "compressed" else pair_2d[1]
    got, want = _ab(lambda: OPCALLS[op](fld, stage, region))
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_decode_device_bit_identity(pair_2d):
    """The Encoded→Compressed device decode routes payload unpacking through
    the Pallas bitpack kernel; the residual planes must match the XLA
    unpacker bit for bit."""
    _, e = pair_2d
    got, want = _ab(lambda: decode_device(e).residuals)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))


def test_payload_kernels_match_plane_kernels(pair_2d):
    """The single-pass payload kernels (in-kernel bitplane unpack) must be
    bit-identical to decode_device + the residual-plane kernels for every
    ``what`` — the unpack arithmetic is the same word/shift/mask math as
    ``encode.unpack_uniform``, so the recovered integers, and hence the
    stencil planes, are the same bits."""
    _, e = pair_2d
    d = decode_device(e)
    shape = tuple(d.residuals.shape)
    if oplib.family_of(e.scheme) == "lorenzo":
        for what in ("deriv0", "deriv1", "lap", "grad"):
            a = fk.lorenzo2d(d.residuals, what=what, interpret=True)
            b = fk.lorenzo_enc2d(e.payload, shape, e.bits, what=what,
                                 interpret=True)
            a = a if isinstance(a, (tuple, list)) else (a,)
            b = b if isinstance(b, (tuple, list)) else (b,)
            for x, y in zip(a, b):
                np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    else:
        blk = tuple(d.block)
        for what in ("deriv0", "deriv1", "lap_p", "lap_q", "grad"):
            a = fk.blockmean2d(d.residuals, d.metadata, blk, what=what,
                               interpret=True)
            b = fk.blockmean_enc2d(e.payload, e.metadata, shape, blk,
                                   e.bits, what=what, interpret=True)
            a = a if isinstance(a, (tuple, list)) else (a,)
            b = b if isinstance(b, (tuple, list)) else (b,)
            for x, y in zip(a, b):
                np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_payload_path_predicate(pair_2d):
    """Payload kernels serve exactly the full-field Encoded contexts; the
    Compressed container and region plans keep the residual-plane / XLA
    gather paths."""
    c, e = pair_2d
    assert fused_mod._payload2(_ctx(e, Stage.Q))
    assert not fused_mod._payload2(_ctx(c, Stage.Q))
    closure = oplib.set_closure(["derivative"], e.scheme, Stage.Q, 0)
    region_ctx = oplib.StageContext(e, Stage.Q, REGION, closure)
    assert not fused_mod._payload2(region_ctx)


# ===========================================================================
# composition shapes: engine vmap batching + expression DAGs
# ===========================================================================

def test_engine_batched_bit_identity(field_2d):
    """The batched engine path (one vmapped program over same-layout fields)
    must produce the same bits as with kernels off — and as the per-field
    jit programs, which test_analytics pins; the kernel mode is part of the
    engine's jit-cache key, so on/off compile separately."""
    rng = np.random.default_rng(5)
    for scheme in ND_SCHEMES:
        comp = by_name(scheme, (8, 8))
        fields = [comp.compress(
            jnp.asarray(field_2d + rng.normal(0, 0.01, field_2d.shape)
                        .astype(np.float32)), abs_eb=1e-3) for _ in range(3)]
        for stage in STAGES:
            got, want = _ab(lambda: tuple(
                jnp.asarray(r) for r in
                analytics.query(exprs=[expr.derivative(f, axis=0)
                                       for f in fields], stage=stage)))
            for g, w in zip(got, want):
                np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_expr_composition_bit_identity(field_2d):
    """Adding two fused derivative outputs inside one program (the
    divergence / vorticity shape) is the exact scenario where an in-kernel
    float tail FMA-contracts shape-dependently; pin in-program composition
    against out-of-program composition, fused on and off."""
    rng = np.random.default_rng(9)
    for scheme in ND_SCHEMES:
        comp = by_name(scheme, (8, 8))
        cu = comp.compress(jnp.asarray(field_2d), abs_eb=1e-3)
        cv = comp.compress(
            jnp.asarray(field_2d[::-1].copy()), abs_eb=1e-3)
        vort = expr.sub(expr.derivative(cv, axis=0),
                        expr.derivative(cu, axis=1))
        for stage in STAGES:
            got = np.asarray(oplib.compute_exprs(vort, stage))
            composed = (
                np.asarray(oplib.compute(cv, "derivative", stage, axis=0)
                           ["derivative"])
                - np.asarray(oplib.compute(cu, "derivative", stage, axis=1)
                             ["derivative"]))
            np.testing.assert_array_equal(got, composed)
            with kops.override_mode("off"):
                off = np.asarray(oplib.compute_exprs(vort, stage))
            np.testing.assert_array_equal(got, off)


# ===========================================================================
# fallback proofs
# ===========================================================================

def _ctx(c, stage):
    closure = oplib.set_closure(["derivative"], c.scheme, stage, 0)
    return oplib.StageContext(c, stage, None, closure)


def test_uncovered_cells_have_no_registry_entry():
    """lorenzo ③④ laplacian is deliberately uncovered (its XLA rule never
    forms q); statistics carry no fused cells at all."""
    assert (Stage.Q, "lorenzo") not in fused_mod.LAPLACIAN
    assert (Stage.F, "lorenzo") not in fused_mod.LAPLACIAN
    for name in ("mean", "std"):
        assert not oplib.OPS[name].fused
    # every fused cell has an XLA fallback (spec_violations enforces this)
    for name in ("derivative", "gradient", "laplacian"):
        assert oplib.spec_violations(oplib.OPS[name]) == []


def test_lap_lorenzo_q_selects_xla_rule(field_2d):
    comp = by_name("hszp_nd", (8, 8))
    c = comp.compress(jnp.asarray(field_2d), abs_eb=1e-3)
    for stage in (Stage.Q, Stage.F):
        rule = oplib.select_rule(oplib.OPS["laplacian"], stage, "lorenzo",
                                 _ctx(c, stage))
        assert not isinstance(rule, fused_mod.FusedRule)
    rule = oplib.select_rule(oplib.OPS["laplacian"], Stage.P, "lorenzo",
                             _ctx(c, Stage.P))
    assert isinstance(rule, fused_mod.FusedRule)


def test_1d_scheme_fails_covers_and_falls_back(field_2d):
    """The 1-D partition schemes have no spatial stencils to fuse: the
    coverage predicate rejects them and dispatch lands on the XLA rule."""
    comp = by_name("hszp", (256,))
    c = comp.compress(jnp.asarray(field_2d), abs_eb=1e-3)
    ctx = _ctx(c, Stage.Q)
    assert not fused_mod._covers_2d(ctx)
    rule = oplib.select_rule(oplib.OPS["derivative"], Stage.Q, "lorenzo", ctx)
    assert not isinstance(rule, fused_mod.FusedRule)


def test_off_mode_deselects_fused_rules(field_2d):
    comp = by_name("hszp_nd", (8, 8))
    c = comp.compress(jnp.asarray(field_2d), abs_eb=1e-3)
    ctx = _ctx(c, Stage.Q)
    on = oplib.select_rule(oplib.OPS["derivative"], Stage.Q, "lorenzo", ctx)
    assert isinstance(on, fused_mod.FusedRule)
    with kops.override_mode("off"):
        off = oplib.select_rule(oplib.OPS["derivative"], Stage.Q, "lorenzo",
                                ctx)
    assert not isinstance(off, fused_mod.FusedRule)
    assert oplib.kernel_sig() in ("auto", "interpret", "native")
    with kops.override_mode("off"):
        assert oplib.kernel_sig() == "off"


# ===========================================================================
# memory traffic: fused kernels vs the XLA lowering
# ===========================================================================

KERNEL_CELLS = {
    "derivative-q": lambda e: H.derivative(e, Stage.Q, 0),
    "laplacian-p": lambda e: H.laplacian(e, Stage.P),
}


@pytest.mark.parametrize("cell", list(KERNEL_CELLS))
@pytest.mark.parametrize("scheme", ND_SCHEMES)
def test_fused_hbm_bytes_below_xla(scheme, cell):
    """The fused kernels' claim is memory traffic: on an Ocean-shaped field
    (half the published dimensions), the HBM-bytes proxy of the ``native``-mode
    program (payload word gathers plus stencil-plane writes) stays under
    0.9x the compiled XLA lowering's, which materializes the unpacked
    residuals and the full-field recorrelation intermediate.  The fused
    side is counted from the jaxpr (CPU has no native Pallas lowering),
    the XLA side from the compiled HLO; both count output bytes of
    non-elementwise ops.  The two proxies count differently, so the fused
    program must also beat the XLA lowering's jaxpr under the same count:
    that comparison is the one that fails with the kernels deselected.
    The *Encoded* container is a real jit argument: a closed-over
    container constant-folds the whole program away."""
    dims = dataset_dims("Ocean", 2)[:2]
    comp = by_name(scheme)
    enc = comp.encode(comp.compress(jnp.asarray(synth_field("Ocean", 0, dims)),
                                    rel_eb=1e-3))
    call = KERNEL_CELLS[cell]
    # a fresh function per mode: jax caches traces by function identity,
    # and the kernel mode is read at trace time
    with kops.override_mode("native"):
        fused = hlo_analysis.jaxpr_bytes(
            jax.make_jaxpr(lambda e: call(e))(enc).jaxpr)
    with kops.override_mode("off"):
        off = lambda e: call(e)
        plain = hlo_analysis.jaxpr_bytes(jax.make_jaxpr(off)(enc).jaxpr)
        xla = hlo_analysis.analyze(
            jax.jit(off).lower(enc).compile().as_text())["bytes_proxy"]
    assert fused < plain, (fused, plain)
    assert fused / max(xla, 1) < 0.9, (fused, xla)
