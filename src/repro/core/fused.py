"""Fused Pallas lowering rules: the alternate ``OpSpec`` backend.

This module binds the VMEM-resident decode+op kernels in
``repro.kernels.fused`` to the lowering-rule registry in
:mod:`repro.core.oplib`.  Each :class:`FusedRule` pairs a rule callable
(same ``fn(ctx, axis)`` signature as the XLA rules) with a static
``covers`` predicate; ``oplib.select_rule`` picks the fused rule for a
``(stage, family)`` cell only when kernels are enabled
(``REPRO_KERNELS`` != ``off``) *and* the predicate accepts the concrete
context — otherwise the cell's XLA rule runs, unchanged.  The registry
invariant (enforced by ``spec_violations``) is that every fused cell has
an XLA rule to fall back to, so disabling kernels can never make an op
infeasible.

Coverage matrix (2-D nd schemes only — 1-D partitioning has no spatial
stencils, and rank != 2 fields fall back, as do planes whose row count no
multiple of 8 rows divides, see ``kernels.fused.band_rows``):

=============  ==========================  ==========================
op             lorenzo (HSZP_ND)           blockmean (HSZX_ND)
=============  ==========================  ==========================
derivative     ② ③ ④                       ② ③ ④
gradient       ② ③ ④                       ② ③ ④
laplacian      ②                           ② ③ ④
=============  ==========================  ==========================

The lorenzo ③④ laplacian cell is *deliberately* uncovered: its XLA rule
reduces over per-axis difference planes without ever forming q, and a
fused variant would have to materialize stage-③ integers to replicate
the rule's exact f32 sequence — the fallback is the honest lowering.
Statistics (mean/std) are likewise uncovered: their flat whole-extent
f32 reductions cannot be reproduced bitwise by a tile-wise kernel
accumulation.

The 3-D vector operators have one kernel of their own, outside the
per-op cells: :func:`vector3d` lowers a 3-component vector's whole set of
divergence and curl in one ``kernels.fused.vector3d_q`` pass, chosen by
``oplib.lower_vectors`` where :func:`covers_vector3d` accepts the
components:

==============  ==========================================================
op set          3 components of rank 3, any scheme, stages ③ ④
==============  ==========================================================
divergence      full field, equal shapes, rows in multiples of 8 (at
curl            least 16), lanes in multiples of 128 (``vector3d_tiles``)
==============  ==========================================================

It departs from the rule below that the multiply and the slice run
outside: its outputs are the stencil interior, so integer outputs with
the float tail in XLA would add a pass over all of them (2.1 GB at NYX's
512^3).  It keeps the rules' bits another way: the kernel moves only
integers (the shifted differences), so its float ops are the rules' own
expressions — one cast and one multiply per term, added in the rules'
order — and XLA's CPU backend contracts both alike.

Bit-identity contract: every covered cell's fused output equals the XLA
rule's output *bitwise* (``np.testing.assert_array_equal``), full-field
and region-windowed, Compressed and Encoded — and the identity must hold
in every *program shape* (solo jit, engine vmap, expression DAGs).  The
kernels therefore emit exact-integer stencil planes (or, for the
block-mean laplacians, the pre-eps f32 accumulation), and the rules here
apply the float tail — the same ``astype(float32)`` / eps-multiply ops
the XLA rules end with — on the already-sliced window.  With the multiply
outside the kernel, the rule's output-producing op is a small plain-HLO
multiply exactly like the XLA rules', so downstream fusion treats both
backends identically; a trailing in-kernel multiply, by contrast, gets
duplicated through the output slice into downstream adds and
FMA-contracted shape-dependently, which broke divergence bit-identity.
Stencil-then-slice equals slice-then-stencil on every interior element
(``tests/test_fused_kernels.py`` pins all cells).

Within a covered cell, each rule picks between the two kernel variants:
full-field :class:`Encoded` contexts (no region plan, no materialized
seed, 0 < bits < 32) take the *payload-input* kernels — gathered payload
words -> in-kernel bitplane unpack -> recorrelation -> stencil, one pass,
no residual plane in HBM — and everything else (Compressed containers,
region plans, seeds) takes the residual-plane kernels on ``ctx.sub``.
The in-kernel unpack is the same word arithmetic as
``encode.unpack_uniform``, so both variants produce identical integers
and the bit-identity contract is variant-independent.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable

import jax
import jax.numpy as jnp

from repro.kernels import fused as fk
from repro.kernels import ops as kops

from .stages import Encoded, Stage


@dataclass(frozen=True)
class FusedRule:
    """A Pallas-backed lowering rule with a static coverage predicate.

    ``reads_seed`` says whether the rule runs off a resident
    materialization of its stage: the stage-② rules run their kernels on
    ``ctx.sub``, which a stage-② seed holds; the stage-③④ rules need the
    residual plane (or the payload) too, which a stage-③ seed does not
    hold, so they decode whatever is resident."""

    fn: Callable          # (ctx, axis) -> result, same signature as XLA rules
    covers: Callable      # (ctx) -> bool: can this rule serve the context?
    reads_seed: bool = False

    def __call__(self, ctx, axis: int):
        return self.fn(ctx, axis)


def _covers_2d(ctx) -> bool:
    """Rank-2 nd fields only: the kernels are 2-D band kernels, and the
    1-D schemes have no spatial stencils to fuse.  The plane the kernel
    runs on (the region plan's gathered blocks, or the padded field) must
    split into bands of a multiple of 8 rows (and of the block rows, for
    the block-mean upsample).  Judged on the container layout and the
    static plan (not ``ctx.sub``) so coverage never forces a decode."""
    f = ctx.field
    if not (ctx.scheme.is_nd and len(f.padded_shape) == 2):
        return False
    shape = (ctx.plan.sub_padded_shape if ctx.plan is not None
             else f.padded_shape)
    mult = f.block[0] if ctx.scheme.is_blockmean else 1
    return fk.band_rows(*shape, mult) is not None


def _payload2(ctx) -> bool:
    """Can this context take the single-pass payload kernels?  Full-field
    :class:`Encoded` queries with a uniformly packed bitstream (0 < bits
    < 32 — bits==0 is the all-zero fast path, bits==32 stores raw words)
    and no materialized seed: the kernel unpacks its band's gathered
    payload words in VMEM and the residual plane never exists in HBM.
    Region plans keep the gather-then-unpack XLA path (the plan's word
    gather already reads only the window's payload)."""
    return (isinstance(ctx.field, Encoded) and ctx.plan is None
            and ctx._seed is None and 0 < ctx.field.bits < 32)


def _window2(ctx) -> tuple[slice, slice]:
    """The stencil-interior slices into the kernels' full padded-shape
    outputs: the region window (or the padding crop) shrunk by one at each
    end, so slicing after the kernel reads exactly the elements the XLA
    rules' window-then-stencil path reads."""
    if ctx.plan is not None:
        w0, w1 = ctx.plan.window
    else:
        w0, w1 = (slice(0, s) for s in ctx.field.shape)
    return slice(w0.start + 1, w0.stop - 1), slice(w1.start + 1, w1.stop - 1)


def _interpret() -> bool:
    return kops._interpret()


# -- lorenzo family ---------------------------------------------------------

def _lz(ctx, what: str):
    if _payload2(ctx):
        f = ctx.field
        return fk.lorenzo_enc2d(f.payload, tuple(f.padded_shape), f.bits,
                                what=what, interpret=_interpret())
    return fk.lorenzo2d(ctx.sub.residuals, what=what, interpret=_interpret())


def _deriv_lorenzo(ctx, axis: int) -> jax.Array:
    out = _lz(ctx, f"deriv{axis}")
    return out[_window2(ctx)].astype(jnp.float32) * ctx.eps


def _grad_lorenzo(ctx, axis: int) -> tuple[jax.Array, ...]:
    d0, d1 = _lz(ctx, "grad")
    w = _window2(ctx)
    return (d0[w].astype(jnp.float32) * ctx.eps,
            d1[w].astype(jnp.float32) * ctx.eps)


def _lap_lorenzo(ctx, axis: int) -> jax.Array:
    out = _lz(ctx, "lap")
    return out[_window2(ctx)].astype(jnp.float32) * (2.0 * ctx.eps)


# -- blockmean family -------------------------------------------------------

def _bm(ctx, what: str):
    if _payload2(ctx):
        f = ctx.field
        return fk.blockmean_enc2d(f.payload, f.metadata,
                                  tuple(f.padded_shape), tuple(f.block),
                                  f.bits, what=what, interpret=_interpret())
    sub = ctx.sub
    return fk.blockmean2d(sub.residuals, sub.metadata, tuple(sub.block),
                          what=what, interpret=_interpret())


def _deriv_blockmean(ctx, axis: int) -> jax.Array:
    out = _bm(ctx, f"deriv{axis}")
    return out[_window2(ctx)].astype(jnp.float32) * ctx.eps


def _grad_blockmean(ctx, axis: int) -> tuple[jax.Array, ...]:
    d0, d1 = _bm(ctx, "grad")
    w = _window2(ctx)
    return (d0[w].astype(jnp.float32) * ctx.eps,
            d1[w].astype(jnp.float32) * ctx.eps)


def _lap_blockmean_p(ctx, axis: int) -> jax.Array:
    return _bm(ctx, "lap_p")[_window2(ctx)] * (2.0 * ctx.eps)


def _lap_blockmean_q(ctx, axis: int) -> jax.Array:
    return _bm(ctx, "lap_q")[_window2(ctx)] * (2.0 * ctx.eps)


# -- registries wired onto the OpSpecs (oplib imports these) ----------------

def _rule(fn, stage: Stage) -> FusedRule:
    return FusedRule(fn, _covers_2d, reads_seed=stage == Stage.P)


#: derivative cells — also dispatched by ``oplib._derivative_at``, which
#: hands the kernels to gradient/divergence/curl compositions for free.
DERIVATIVE: dict[tuple[Stage, str], FusedRule] = {
    (Stage.P, "lorenzo"): _rule(_deriv_lorenzo, Stage.P),
    (Stage.Q, "lorenzo"): _rule(_deriv_lorenzo, Stage.Q),
    (Stage.F, "lorenzo"): _rule(_deriv_lorenzo, Stage.F),
    (Stage.P, "blockmean"): _rule(_deriv_blockmean, Stage.P),
    (Stage.Q, "blockmean"): _rule(_deriv_blockmean, Stage.Q),
    (Stage.F, "blockmean"): _rule(_deriv_blockmean, Stage.F),
}

#: gradient gets its own cells: one dual-output kernel pass instead of two.
GRADIENT: dict[tuple[Stage, str], FusedRule] = {
    (Stage.P, "lorenzo"): _rule(_grad_lorenzo, Stage.P),
    (Stage.Q, "lorenzo"): _rule(_grad_lorenzo, Stage.Q),
    (Stage.F, "lorenzo"): _rule(_grad_lorenzo, Stage.F),
    (Stage.P, "blockmean"): _rule(_grad_blockmean, Stage.P),
    (Stage.Q, "blockmean"): _rule(_grad_blockmean, Stage.Q),
    (Stage.F, "blockmean"): _rule(_grad_blockmean, Stage.F),
}

#: laplacian: lorenzo ③④ deliberately absent (see module docstring).
LAPLACIAN: dict[tuple[Stage, str], FusedRule] = {
    (Stage.P, "lorenzo"): _rule(_lap_lorenzo, Stage.P),
    (Stage.P, "blockmean"): _rule(_lap_blockmean_p, Stage.P),
    (Stage.Q, "blockmean"): _rule(_lap_blockmean_q, Stage.Q),
    (Stage.F, "blockmean"): _rule(_lap_blockmean_q, Stage.F),
}


# -- 3-D vector operators ----------------------------------------------------

#: the vector ops :func:`vector3d` writes, and the planes each one takes
VECTOR_OUTPUTS = {"divergence": 1, "curl": 3}


def covers_vector3d(ctxs, names) -> bool:
    """Can one :func:`vector3d` pass serve the op set ``names`` over the
    component contexts ``ctxs``?  Three components at stage ③ or ④ (both
    read ``q_spatial``), full field, one shape the kernel tiles.  Region
    windows, stage ② and 2-component curl keep the XLA rules."""
    if len(ctxs) != 3 or not set(names) <= set(VECTOR_OUTPUTS):
        return False
    if any(c.stage not in (Stage.Q, Stage.F) or c.plan is not None
           for c in ctxs):
        return False
    shapes = {tuple(c.field.shape) for c in ctxs}
    n_out = sum(VECTOR_OUTPUTS[n] for n in names)
    return (len(shapes) == 1
            and fk.vector3d_tiles(shapes.pop(), n_out) is not None)


def vector3d(ctxs, names) -> dict:
    """``{op: result}`` for divergence and/or curl of a 3-D vector in one
    kernel pass over the components' ``q_spatial`` planes, bitwise the
    ``oplib`` rules' (see the module docstring)."""
    div, curl = "divergence" in names, "curl" in names
    eps = jnp.stack([jnp.asarray(c.eps, jnp.float32) for c in ctxs])
    outs = iter(fk.vector3d_q(*(c.q_spatial for c in ctxs), eps, div=div,
                              curl=curl, interpret=_interpret()))
    out = {}
    if div:
        out["divergence"] = next(outs)
    if curl:
        out["curl"] = (next(outs), next(outs), next(outs))
    return out
