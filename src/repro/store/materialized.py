"""First-class materialized stage reconstructions (DESIGN.md §7).

The paper's premise is that decompression dominates analytics cost; the
operator-lowering core (``repro.core.oplib``) already shares one stage
reconstruction across an op *set*, but the reconstruction itself was
ephemeral — rebuilt inside every ``compute()`` call and thrown away.  A
:class:`MaterializedStage` turns it into a value: the intermediate
representation of one ``(field, stage, region, closure)`` cell, held as a
pytree so it stacks, ``vmap``-s, and enters jitted programs exactly like the
compressed containers themselves.

What each stage keeps resident is exactly the *last integer-exact*
intermediate its postludes consume:

* stage ② — the decoded sub-field (``sub``): residuals + restricted
  metadata, i.e. the honest :class:`~repro.core.stages.Compressed` that
  ``StageContext.sub`` would have decoded;
* stage ③ *and* stage ④ — ``q_spatial``: recorrelated quantization
  integers, cropped or windowed to the queried extent.  Stage ④ is the
  stage-③ intermediate plus a dequantize multiply, which stays in the op
  postlude: one cache entry serves both stages.

Stage ① has nothing to materialize — its metadata is already resident in
the compressed container — so :func:`materialize` rejects it.

Materializations stop at integer intermediates *by design*: integer
reconstruction is exact under any compilation, so a program seeded from a
resident intermediate and a program reconstructing inline share their
entire floating-point expression tail — which is what makes store-backed
results **bit-identical** to storeless ones.  (Caching dequantized floats
instead would hand XLA different float graphs to reassociate, producing
ulp-level drift between hot and cold answers.)
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax

from repro import obs
from repro.core import Compressed, Encoded, Stage, blocking, layout_key, oplib
from repro.core import region as region_mod
from repro.core.region import Closure
from repro.core.stages import _dataclass_pytree
from repro.kernels import ops as kernel_ops
from repro.kernels.fused import lorenzo3d_covers

Field = Compressed | Encoded


def serves(seed_stage: Stage, ctx_stage: Stage) -> bool:
    """Can a materialization at ``seed_stage`` seed a ``ctx_stage`` prelude?
    Exact stage match, plus the one derived case: the stage-③ integers serve
    stage-④ (dequantize is an op-postlude multiply, not a reconstruction)."""
    seed_stage, ctx_stage = Stage(seed_stage), Stage(ctx_stage)
    return seed_stage == ctx_stage or (seed_stage == Stage.Q
                                       and ctx_stage == Stage.F)


def storage_stage(stage: Stage) -> Stage:
    """The stage a materialization is stored at: ④ canonicalizes to ③ (one
    resident integer intermediate serves both)."""
    stage = Stage(stage)
    return Stage.Q if stage == Stage.F else stage


@partial(
    _dataclass_pytree,
    data_fields=("sub", "q_spatial"),
    meta_fields=("stage", "closure", "region"),
)
@dataclass(frozen=True)
class MaterializedStage:
    """One resident intermediate representation.

    Exactly one of ``sub`` / ``q_spatial`` is populated (stage ② / ③); the
    other is ``None`` (an empty pytree subtree, so same-key containers
    always share a treedef and stack cleanly).  The meta triple is the
    cache key the seed must match: the (storage) stage, the *canonical*
    region closure (:func:`repro.core.region.canonical_closure`), and the
    normalized region (``None`` for full-field).
    """

    sub: Compressed | None        # stage ②: decoded sub-field
    q_spatial: jax.Array | None   # stage ③ (and ④): recorrelated integers

    stage: Stage
    closure: Closure
    region: tuple[tuple[int, int], ...] | None

    @property
    def nbytes(self) -> int:
        """Device bytes this materialization keeps resident (LRU accounting)."""
        if self.sub is not None:
            return self.sub.device_bytes()
        q = self.q_spatial
        return int(q.size * q.dtype.itemsize)

    def serves(self, ctx_stage: Stage) -> bool:
        """Can this materialization seed a ``ctx_stage`` prelude?  The one
        authoritative copy of the stage-serving rule — the duck-typed seed
        consumers (`oplib.StageContext`, the engine) call this, so core
        never needs a store dependency."""
        return serves(self.stage, ctx_stage)

    def sig(self) -> tuple:
        """Hashable static signature: part of the engine's jit-cache key, and
        the stacking-compatibility check across a batch of seeds."""
        q = self.q_spatial
        return (self.stage, self.closure, self.region,
                layout_key(self.sub) if self.sub is not None else None,
                (tuple(q.shape), str(q.dtype)) if q is not None else None)


def materialized_nbytes(field: Field, stage: Stage, *, region=None,
                        closure: Closure = "cover") -> int:
    """Exact device bytes :func:`materialize` would keep resident, from
    static geometry alone (no device work) — the store consults this to
    decline cells that could never fit its budget *before* paying the
    reconstruction."""
    stage = storage_stage(stage)
    if stage == Stage.M:
        raise ValueError("stage-1 metadata is never materialized")
    int32 = 4
    if region is not None:
        plan = region_mod.plan_region(field, region, closure)
        if stage == Stage.P:
            meta = (plan.n_sub_blocks if field.scheme.is_blockmean
                    else int(field.metadata.size))
            return int32 * (plan.gathered_elems + meta
                            + 2 * plan.n_sub_blocks) + 4  # + f32 eps
        return int32 * plan.n_window
    if stage == Stage.P:
        n = 1
        for s in field.padded_shape:
            n *= s
        meta = int(field.metadata.size)
        return int32 * (n + meta + 2 * field.n_blocks) + 4
    return int32 * field.n


@partial(jax.jit, static_argnames=("padded", "shape", "bits", "mode"))
def _lorenzo3d_q_program(payload: jax.Array, *, padded: tuple, shape: tuple,
                         bits: int, mode: str) -> jax.Array:
    """The stage-③ plane of a full 3-D Lorenzo field as one program: the
    payload-to-``q`` kernel, then the crop of the padding (what
    ``StageContext.q_spatial`` computes op by op).  ``mode`` keys the
    kernel backend."""
    del mode
    return blocking.crop(kernel_ops.lorenzo3d_q(payload, padded, bits), shape)


def materialize(field: Field, stage: Stage, *,
                region=None, closure: Closure = "cover") -> MaterializedStage:
    """Build the intermediate representation of one cache cell.

    Runs the exact shared prelude the op lowerings use
    (:class:`repro.core.oplib.StageContext`), forces the stage's resident
    intermediate, and wraps it.  Stage ④ requests return the stage-③
    container (see :func:`storage_stage`).  ``closure`` matters only with
    ``region`` (it decides the gathered block set); full-field
    materializations share the canonical ``"cover"`` key regardless of the
    op set that asked.

    The full-field stage-③ plane of an encoded 3-D Lorenzo field is one
    dispatch instead, when the kernels are on and cover its shape
    (:func:`repro.kernels.fused.lorenzo3d_covers`): the Pallas kernel that
    takes the payload words to ``q`` and the crop, as one jitted program
    (counted in ``store_materializations_fused``).  Its integers are the
    prelude's, bit for bit.
    """
    stage = storage_stage(stage)
    if stage == Stage.M:
        raise ValueError(
            "stage-1 metadata is already resident in the compressed "
            "container; there is nothing to materialize")
    norm = (region_mod.normalize_region(region, field.shape)
            if region is not None else None)
    closure = region_mod.canonical_closure(field.scheme, closure, norm)
    if (stage == Stage.Q and norm is None and isinstance(field, Encoded)
            and field.scheme.is_lorenzo and kernel_ops.kernels_enabled()
            and lorenzo3d_covers(field.padded_shape, field.bits)):
        obs.counters["store_materializations_fused"] += 1
        q = _lorenzo3d_q_program(field.payload, padded=field.padded_shape,
                                 shape=field.shape, bits=field.bits,
                                 mode=kernel_ops.kernel_mode())
        return MaterializedStage(sub=None, q_spatial=q, stage=stage,
                                 closure=closure, region=None)
    ctx = oplib.StageContext(field, stage, region, closure)
    sub = q = None
    if stage == Stage.P:
        sub = ctx.sub
    else:
        q = ctx.q_spatial
    return MaterializedStage(sub=sub, q_spatial=q,
                             stage=stage, closure=closure, region=norm)
