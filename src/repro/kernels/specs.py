"""Declarative symbolic specs for every Pallas kernel call site.

``repro.audit.kernelspec`` proves, per kernel, that (a) every block /
halo index map stays in bounds for *all* grid sizes, (b) the grid writes
every output element exactly once, and (c) the per-cell VMEM footprint
fits the budget.  Those proofs need a symbolic description of each
``pl.pallas_call`` site — the grid symbols, the block shapes and index
maps as expressions over those symbols, the host-side halo gathers, and
the algebraic facts tying the sizes together (``n0 == nb*r``).  This
module is that description, kept next to the kernels it describes; the
analyzer cross-checks it against the AST of the call sites
(``undeclared-kernel`` / ``stale-kernel-spec``), so a new kernel cannot
ship unspecified and a spec cannot outlive its kernel.

Expression language: integer arithmetic (``+ - *`` and integer
literals) over the spec's symbols, with parentheses.  Symbol bounds are
inclusive and may reference other symbols (``b`` ranges over
``0 .. nb - 1``); ``None`` means unbounded above.  ``facts`` are
equalities ``"lhs == rhs"`` where ``lhs`` is a single symbol the
analyzer eliminates by rewriting (``n0 == nb*r`` substitutes ``nb*r``
for every ``n0``).  The special symbol ``F`` in ``vmem_elems`` denotes
the audit envelope's ``max_field_elems``.
"""
from __future__ import annotations

from dataclasses import dataclass, field

#: payload-word slack of one lane tile of
#: :func:`repro.kernels.bitpack.unpack_lanes`: a tile's 128 values span
#: ``4*bits`` words from its window start; +1 word for the in-word bit
#: offset, +1 for the carry word read.  The audit's bounded-exhaustive
#: unpack lemma proves this is exactly enough for every (bits, offset)
#: combination, and that the window still fits one 128-lane gather — see
#: ``kernelspec.check_unpack_lemma``.
WPB_EXTRA = 2


@dataclass(frozen=True)
class TileSpec:
    """One ``pl.BlockSpec``-governed operand of a kernel call site.

    ``block`` / ``index`` / ``extent`` are per-dimension expressions:
    the operand's block shape, the *block* index map (what the BlockSpec
    lambda returns for the grid symbols), and the full array extent.
    ``element`` marks a window of ``pl.Element`` dims: its index map
    returns element offsets, so windows may overlap (halos), and its
    extent includes the window's declared padding.
    """

    name: str
    block: tuple[str, ...]
    index: tuple[str, ...]
    extent: tuple[str, ...]
    dtype_bytes: int = 4
    element: bool = False


@dataclass(frozen=True)
class HaloRead:
    """A host-side ±1-row halo gather feeding a kernel input.

    ``index`` is the symbolic row read from an array of row-extent
    ``extent``; ``guard`` (optional) is the predicate under which the
    read is live — reads outside the guard are zero-filled, never
    performed (``"b >= 1"`` / ``"b <= nb - 2"``).
    """

    array: str
    index: str
    extent: str
    guard: str = ""


@dataclass(frozen=True)
class KernelSpec:
    """Symbolic contract of one ``pl.pallas_call`` site.

    ``site``    — (module, wrapper function, ordinal) locating the call.
    ``grid``    — grid symbols, one per grid dimension.
    ``bounds``  — inclusive symbol ranges ``{sym: (lo, hi)}``; ``hi=None``
    is unbounded (the analyzer substitutes the lower bound only).
    Declaration order matters: a symbol's bound expressions may only
    reference symbols declared *after* it.
    ``facts``   — ``"sym == expr"`` size equalities (rewrites).
    ``vmem_elems`` — worst-case 4-byte elements resident in VMEM per grid
    cell (inputs + outputs + temporaries), over the symbols plus ``F``.
    ``unpack_words`` — the kernel runs the in-VMEM bitplane unpack
    (``bitpack.unpack_lanes``); the word-window carry lemma applies.
    ``sequential_revisit`` — the output index map is deliberately
    constant across the grid (TPU sequential-grid accumulator pattern);
    exactly-once coverage is waived, and the kernel must never be
    vmapped (Pallas batching prepends a grid axis, breaking the carry).
    """

    name: str
    site: tuple[str, str, int]
    grid: tuple[str, ...]
    bounds: dict[str, tuple[str, str | None]]
    inputs: tuple[TileSpec, ...]
    outputs: tuple[TileSpec, ...]
    facts: tuple[str, ...] = ()
    halos: tuple[HaloRead, ...] = ()
    vmem_elems: str = "0"
    unpack_words: bool = False
    sequential_revisit: bool = False
    notes: str = ""


def _band_bounds(**extra) -> dict:
    """Common band-kernel symbol ranges: grid step ``b`` over ``nb``
    bands of ``r = 8*rq`` rows, ``n1`` columns."""
    out = {"b": ("0", "nb - 1"), "nb": ("1", None), "rq": ("1", None),
           "n1": ("1", None)}
    out.update(extra)
    return out


#: band rows are a multiple of 8 (``band_rows``); one 8-row halo tile per
#: band; payload word rows are ``W`` wide (``bitpack.window_words``).
_BAND_FACTS = ("n0 == nb*r", "r == 8*rq", "nh == 8*nb")
_BAND = TileSpec("band", ("r", "n1"), ("b", "0"), ("n0", "n1"))
_HALO = TileSpec("halo", ("8", "n1"), ("b", "0"), ("nh", "n1"))
_WORDS = TileSpec("words", ("r", "W"), ("b", "0"), ("n0", "W"))
_OFFS = TileSpec("offs", ("r", "1"), ("b", "0"), ("n0", "1"))
_META = TileSpec("meta", ("rb8", "n1"), ("b", "0"), ("nm", "n1"))
_PLANE = TileSpec("plane", ("r", "n1"), ("b", "0"), ("n0", "n1"))


KERNEL_SPECS: tuple[KernelSpec, ...] = (
    # -- fused Lorenzo family ------------------------------------------------
    KernelSpec(
        name="fused.lorenzo2d",
        site=("fused", "lorenzo2d", 0),
        grid=("b",),
        bounds=_band_bounds(),
        facts=_BAND_FACTS,
        inputs=(_BAND, _HALO),
        outputs=(_PLANE,),
        halos=(
            # _next_rows(p, r): next[b] = p[(b+1)*r], zero last band
            HaloRead("p", "(b + 1)*r", "n0", guard="b <= nb - 2"),
        ),
        # band + halo tile (<= band) double-buffered, prefix planes and
        # their shifts, <= 2 output planes double-buffered
        vmem_elems="12*F",
        notes="grad emits two planes through the same output tile spec",
    ),
    KernelSpec(
        name="fused.lorenzo_enc2d.colsum",
        site=("fused", "lorenzo_enc2d", 0),
        grid=("b",),
        bounds=_band_bounds(W=("128", None)),
        facts=_BAND_FACTS,
        inputs=(_WORDS, _OFFS),
        outputs=(_HALO,),
        vmem_elems="4*F + 8",
        unpack_words=True,
    ),
    KernelSpec(
        name="fused.lorenzo_enc2d.stencil",
        site=("fused", "lorenzo_enc2d", 1),
        grid=("b",),
        bounds=_band_bounds(W=("128", None)),
        facts=_BAND_FACTS,
        inputs=(_WORDS, _OFFS, _HALO),
        outputs=(_PLANE,),
        halos=(
            # unpack_rows(payload, arange(1, nb)*r, ...): rows b*r, b >= 1
            HaloRead("plane", "b*r", "n0", guard="b >= 1"),
        ),
        vmem_elems="13*F",
        unpack_words=True,
    ),
    # -- fused block-mean family ---------------------------------------------
    KernelSpec(
        name="fused.blockmean2d",
        site=("fused", "blockmean2d", 0),
        grid=("b",),
        bounds=_band_bounds(rb=("1", None), g0=("1", None),
                            q8=("1", None)),
        facts=_BAND_FACTS + ("g0 == nb*rb", "rb8 == 8*q8", "nm == nb*rb8"),
        inputs=(_BAND, _META, _HALO),
        outputs=(_PLANE,),
        halos=(
            HaloRead("p", "b*r - 1", "n0", guard="b >= 1"),
            HaloRead("p", "(b + 1)*r", "n0", guard="b <= nb - 2"),
            HaloRead("meta", "b*rb - 1", "g0", guard="b >= 1"),
            HaloRead("meta", "(b + 1)*rb", "g0", guard="b <= nb - 2"),
        ),
        # p, upsampled m, 4 shifted planes, 2 col shifts, <=2 outputs, tiles
        vmem_elems="16*F",
    ),
    KernelSpec(
        name="fused.blockmean_enc2d",
        site=("fused", "blockmean_enc2d", 0),
        grid=("b",),
        bounds=_band_bounds(rb=("1", None), g0=("1", None),
                            q8=("1", None), W=("128", None)),
        facts=_BAND_FACTS + ("g0 == nb*rb", "rb8 == 8*q8", "nm == nb*rb8"),
        inputs=(_WORDS, _OFFS, _META, _HALO),
        outputs=(_PLANE,),
        halos=(
            # unpack_rows at arange(1, nb)*r - 1 and arange(1, nb)*r
            HaloRead("plane", "b*r - 1", "n0", guard="b >= 1"),
            HaloRead("plane", "b*r", "n0", guard="b >= 1"),
            HaloRead("meta", "b*rb - 1", "g0", guard="b >= 1"),
            HaloRead("meta", "(b + 1)*rb", "g0", guard="b <= nb - 2"),
        ),
        vmem_elems="17*F",
        unpack_words=True,
    ),
    # -- 3-D Lorenzo stage-③ plane ------------------------------------------
    KernelSpec(
        name="fused.lorenzo3d_q",
        site=("fused", "lorenzo3d_q", 0),
        grid=("b",),
        bounds={"b": ("0", "nb - 1"), "nb": ("1", None), "S": ("1", None),
                "rq": ("1", None), "n2": ("1", None), "W": ("1", None)},
        facts=("n0 == nb*S", "n1 == 8*rq"),
        inputs=(TileSpec("words", ("S", "n1", "W"), ("b", "0", "0"),
                         ("n0", "n1", "W")),),
        outputs=(TileSpec("plane", ("S", "n1", "n2"), ("b", "0", "0"),
                          ("n0", "n1", "n2")),),
        # word and output slabs (each <= SLAB_BYTES = 2 MiB) double-
        # buffered, the carry plane and two planes of spilled temporaries
        # (each <= PLANE_BYTES = 1 MiB); the [U | J] block is 64 KiB
        vmem_elems="4*524288 + 3*262144 + 16384",
        unpack_words=True,
        notes="the carry plane persists in VMEM scratch across the "
              "sequential grid (axis-0 prefix), so the kernel must never "
              "run under vmap: only the store's materialization calls it; "
              "the MXU variant adds the constant (128, 256) [U | J] block",
    ),
    # -- 3-D vector operators (divergence, curl) -----------------------------
    KernelSpec(
        name="fused.vector3d_q",
        site=("fused", "vector3d_q", 0),
        grid=("b", "c"),
        bounds={"b": ("0", "ng - 1"), "c": ("0", "nb - 1"),
                "ng": ("1", None), "nb": ("1", None), "k": ("1", None),
                "rq": ("1", None), "lq": ("1", None)},
        facts=("n0 == ng*k + 2", "r == 8*rq", "n2 == 128*lq"),
        inputs=(
            TileSpec("eps", ("24", "n2"), ("0", "0"), ("24", "n2")),
            # each component's (k+2, r+8, n2) window at element offsets:
            # its k planes and r rows, then the next 2 planes and 8 rows
            # (halos); the rows carry a high padding up to nb*r + 8
            TileSpec("u", ("k + 2", "r + 8", "n2"), ("b*k", "c*r", "0"),
                     ("n0", "nb*r + 8", "n2"), element=True),
            TileSpec("v", ("k + 2", "r + 8", "n2"), ("b*k", "c*r", "0"),
                     ("n0", "nb*r + 8", "n2"), element=True),
            TileSpec("w", ("k + 2", "r + 8", "n2"), ("b*k", "c*r", "0"),
                     ("n0", "nb*r + 8", "n2"), element=True),
        ),
        # rows past n1 - 2 of the last band (nb = ceil((n1 - 2) / r)) are
        # dropped as the block is written back
        outputs=(TileSpec("out", ("k", "r", "n2 - 2"), ("b", "c", "0"),
                          ("ng*k", "nb*r", "n2 - 2")),),
        # the blocks within VECTOR_BYTES = 12 MiB (vector3d_tiles), and
        # the 8-row values of one step of the unrolled row loop (160 KiB)
        vmem_elems="3145728 + 40960",
        notes="one call per op set writes 1 (divergence), 3 (curl) or 4 "
              "planes through the same output tile spec",
    ),
    # -- bitplane pack / unpack ----------------------------------------------
    KernelSpec(
        name="bitpack.pack",
        site=("bitpack", "pack", 0),
        grid=("i",),
        bounds={"i": ("0", "g - 1"), "g": ("1", None),
                "bits": ("1", "31")},
        facts=("npad == g*4096", "nw == g*wp", "wp == 128*bits"),
        inputs=(TileSpec("u", ("4096",), ("i",), ("npad",)),),
        outputs=(TileSpec("words", ("wp",), ("i",), ("nw",)),),
        # u + (V, bits<=32) bit matrix + word stream + powers
        vmem_elems="4096 + 4096*32 + 4096 + 64",
    ),
    KernelSpec(
        name="bitpack.unpack",
        site=("bitpack", "unpack", 0),
        grid=("i",),
        bounds={"i": ("0", "g - 1"), "g": ("1", None),
                "kq": ("1", "8"), "W": ("128", None)},
        facts=("rows == g*blk", "blk == 8*kq"),
        inputs=(TileSpec("words", ("blk", "W"), ("i", "0"), ("rows", "W")),),
        outputs=(TileSpec("u", ("blk", "1024"), ("i", "0"),
                          ("rows", "1024")),),
        # word rows (W <= 28*31 + 128 words) + lane-tile temporaries + out
        vmem_elems="2*64*1024 + 6*64*1024",
        unpack_words=True,
    ),
    # -- fused quantize + Lorenzo --------------------------------------------
    KernelSpec(
        name="quant_lorenzo.quant_lorenzo2d",
        site=("quant_lorenzo", "quant_lorenzo2d", 0),
        grid=("i", "j"),
        bounds={"i": ("0", "g0 - 1"), "j": ("0", "g1 - 1"),
                "g0": ("1", None), "g1": ("1", None),
                "u0": ("1", "16"), "u1": ("1", "2")},
        facts=("n0 == g0*t0", "n1 == g1*t1", "t0 == 8*u0", "t1 == 128*u1"),
        inputs=(
            TileSpec("x", ("t0", "t1"), ("i", "j"), ("n0", "n1")),
            TileSpec("xr", ("t0", "t1"), ("i", "j"), ("n0", "n1")),
            TileSpec("xc", ("t0", "t1"), ("i", "j"), ("n0", "n1")),
            TileSpec("xrc", ("t0", "t1"), ("i", "j"), ("n0", "n1")),
            TileSpec("eps", ("1",), ("0",), ("1",)),
        ),
        outputs=(TileSpec("p", ("t0", "t1"), ("i", "j"), ("n0", "n1")),),
        # halos are same-shape pre-shifted *views*; no out-of-tile reads
        vmem_elems="9*128*256 + 8",
    ),
    # -- dequantized finite-difference stencils ------------------------------
    KernelSpec(
        name="stencil_dq.grad2d",
        site=("stencil_dq", "grad2d", 0),
        grid=("i", "j"),
        bounds={"i": ("0", "g0 - 1"), "j": ("0", "g1 - 1"),
                "g0": ("1", None), "g1": ("1", None),
                "u0": ("1", "16"), "u1": ("1", "2")},
        facts=("m0 == g0*t0", "m1 == g1*t1", "t0 == 8*u0", "t1 == 128*u1"),
        inputs=(
            TileSpec("qn", ("t0", "t1"), ("i", "j"), ("m0", "m1")),
            TileSpec("qs", ("t0", "t1"), ("i", "j"), ("m0", "m1")),
            TileSpec("qw", ("t0", "t1"), ("i", "j"), ("m0", "m1")),
            TileSpec("qe", ("t0", "t1"), ("i", "j"), ("m0", "m1")),
        ),
        outputs=(
            TileSpec("d0", ("t0", "t1"), ("i", "j"), ("m0", "m1")),
            TileSpec("d1", ("t0", "t1"), ("i", "j"), ("m0", "m1")),
        ),
        vmem_elems="6*128*256",
    ),
    KernelSpec(
        name="stencil_dq.laplacian2d",
        site=("stencil_dq", "laplacian2d", 0),
        grid=("i", "j"),
        bounds={"i": ("0", "g0 - 1"), "j": ("0", "g1 - 1"),
                "g0": ("1", None), "g1": ("1", None),
                "u0": ("1", "16"), "u1": ("1", "2")},
        facts=("m0 == g0*t0", "m1 == g1*t1", "t0 == 8*u0", "t1 == 128*u1"),
        inputs=(
            TileSpec("qc", ("t0", "t1"), ("i", "j"), ("m0", "m1")),
            TileSpec("qn", ("t0", "t1"), ("i", "j"), ("m0", "m1")),
            TileSpec("qs", ("t0", "t1"), ("i", "j"), ("m0", "m1")),
            TileSpec("qw", ("t0", "t1"), ("i", "j"), ("m0", "m1")),
            TileSpec("qe", ("t0", "t1"), ("i", "j"), ("m0", "m1")),
        ),
        outputs=(TileSpec("lap", ("t0", "t1"), ("i", "j"), ("m0", "m1")),),
        vmem_elems="7*128*256",
    ),
    # -- blockwise metadata reduction ----------------------------------------
    KernelSpec(
        name="block_stats.block_stats",
        site=("block_stats", "block_stats", 0),
        grid=("i",),
        bounds={"i": ("0", "g - 1"), "g": ("1", None),
                "k": ("1", "2"), "s": ("1", "4096")},
        facts=("nb == g*rows", "rows == 128*k"),
        inputs=(TileSpec("q", ("rows", "s"), ("i", "0"), ("nb", "s")),),
        outputs=(
            TileSpec("mean", ("rows",), ("i",), ("nb",)),
            TileSpec("maxu", ("rows",), ("i",), ("nb",)),
        ),
        vmem_elems="2*256*4096 + 2*256",
    ),
    # -- sequential prefix stats (deliberately unwired) ----------------------
    KernelSpec(
        name="prefix_stats.prefix_stats2d",
        site=("prefix_stats", "prefix_stats2d", 0),
        grid=("i",),
        bounds={"i": ("0", "g - 1"), "g": ("1", None),
                "k": ("1", "8"), "n1": ("1", None)},
        facts=("n0 == g*rows", "rows == 8*k"),
        inputs=(TileSpec("p", ("rows", "n1"), ("i", "0"), ("n0", "n1")),),
        outputs=(TileSpec("s", ("2",), ("0",), ("2",)),),
        # band + rowcum + q + qf + colsum scratch
        vmem_elems="4*F + 4",
        sequential_revisit=True,
        notes="pl.program_id-keyed carry: every grid step revisits output "
              "block 0 (legal under TPU sequential grid semantics); must "
              "never run under vmap — which is why it stays unwired",
    ),
)
