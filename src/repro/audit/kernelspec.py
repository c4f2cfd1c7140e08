"""Analyzer (5): Pallas kernel grid/bounds/race verification (DESIGN.md §11).

Every kernel in ``repro.kernels`` declares a symbolic spec
(:mod:`repro.kernels.specs`); this pass *proves*, for all grid sizes the
spec's symbol bounds admit:

* **bounds** — every BlockSpec index map and every host-side ±1-row halo
  gather stays inside its array, including the guard predicates that make
  boundary reads zero-filled instead of out-of-bounds;
* **coverage** — the grid writes every output element exactly once: block
  strides match block shapes (no gaps), the first/last blocks land exactly
  on the array edges, and every grid symbol distinguishes the output index
  map (no write races between grid cells) — except where a spec declares
  the sequential-accumulator pattern (``sequential_revisit``);
* **tiling** — every block's last two dims are multiples of ``(8, 128)``
  or equal to the array's dims (the last dim alone for 1-D blocks): the
  rule Mosaic enforces when it compiles the kernel for a TPU;
* **VMEM** — the declared worst-case per-cell footprint fits the budget
  (default 16 MiB, the per-core VMEM size) under the audit envelope;
* **unpack lemma** — the in-kernel bitplane unpack's low and carry word
  reads stay inside each lane tile's ``4*bits + WPB_EXTRA`` words, and
  that window fits one 128-lane gather, by bounded-exhaustive sweep over
  every (bits, in-word offset) combination;
* **no output multiply** — no float multiply is the final op feeding an
  output ref (the FMA-contraction hazard PR 8 debugged bitwise: XLA's CPU
  fusion duplicates a trailing kernel multiply into downstream consumers
  and FMA-contracts it *shape-dependently*; the float tail must live in
  the XLA lowering rule).  ``# audit: waive(output-multiply)`` on the
  store line (or the line above) exempts a deliberate exception.

Abstract domain: polynomials over the spec symbols with interval bounds.
``e >= 0`` is proven by substituting each bounded symbol ``s`` with
``lo + δ`` or ``hi − δ`` (fresh ``δ >= 0``) and checking that some branch
expands to a polynomial with only non-negative coefficients — sound
(never accepts a violable bound), conservative (may reject a true one,
which surfaces as a finding to fix or respecify, never silence).
"""
from __future__ import annotations

import ast
import re
from pathlib import Path

from .findings import Finding
from .intwidth import DEFAULT_ENVELOPE, Envelope

_ANALYZER = "kernelspec"

#: per-core VMEM (see the TPU architecture table in the Pallas guide).
VMEM_BUDGET_BYTES = 16 * 2**20

_WAIVE_RE = re.compile(r"#\s*audit:\s*waive\(([a-z\-,\s]+)\)")
_FRESH = "δ"  # δ — reserved prefix for nonneg slack variables
_GUARD_RE = re.compile(r"^\s*(\w+)\s*(<=|>=)\s*(.+?)\s*$")
_FACT_RE = re.compile(r"^\s*(\w+)\s*==\s*(.+?)\s*$")


# ---------------------------------------------------------------------------
# polynomial domain
# ---------------------------------------------------------------------------

class Poly:
    """Integer polynomial over named symbols (dict monomial -> coeff).

    A monomial is a sorted tuple of ``(symbol, power)`` pairs; the empty
    tuple is the constant term.  Supports +, -, *, substitution, and
    exact equality — everything the bounds/coverage proofs need.
    """

    __slots__ = ("terms",)

    def __init__(self, terms: dict | None = None):
        self.terms = {m: c for m, c in (terms or {}).items() if c != 0}

    @classmethod
    def const(cls, n: int) -> "Poly":
        return cls({(): int(n)})

    @classmethod
    def var(cls, name: str) -> "Poly":
        return cls({((name, 1),): 1})

    def vars(self) -> set[str]:
        return {s for m in self.terms for s, _ in m}

    def is_zero(self) -> bool:
        return not self.terms

    def const_value(self) -> int | None:
        if not self.terms:
            return 0
        if set(self.terms) == {()}:
            return self.terms[()]
        return None

    def __eq__(self, other) -> bool:
        return isinstance(other, Poly) and self.terms == other.terms

    def __hash__(self):
        return hash(frozenset(self.terms.items()))

    def __add__(self, other: "Poly") -> "Poly":
        out = dict(self.terms)
        for m, c in other.terms.items():
            out[m] = out.get(m, 0) + c
        return Poly(out)

    def __neg__(self) -> "Poly":
        return Poly({m: -c for m, c in self.terms.items()})

    def __sub__(self, other: "Poly") -> "Poly":
        return self + (-other)

    def __mul__(self, other: "Poly") -> "Poly":
        out: dict = {}
        for m1, c1 in self.terms.items():
            for m2, c2 in other.terms.items():
                powers: dict[str, int] = {}
                for s, p in m1 + m2:
                    powers[s] = powers.get(s, 0) + p
                m = tuple(sorted(powers.items()))
                out[m] = out.get(m, 0) + c1 * c2
        return Poly(out)

    def subst(self, name: str, repl: "Poly") -> "Poly":
        """Replace every occurrence of ``name`` by the polynomial ``repl``."""
        out = Poly()
        for m, c in self.terms.items():
            power = 0
            rest = []
            for s, p in m:
                if s == name:
                    power = p
                else:
                    rest.append((s, p))
            term = Poly({tuple(rest): c})
            for _ in range(power):
                term = term * repl
            out = out + term
        return out

    def render(self) -> str:
        if not self.terms:
            return "0"
        parts = []
        for m, c in sorted(self.terms.items()):
            sym = "*".join(s if p == 1 else f"{s}^{p}" for s, p in m)
            parts.append(f"{c}" if not sym else
                         (sym if c == 1 else f"{c}*{sym}"))
        return " + ".join(parts)


def parse_expr(expr: str) -> Poly:
    """Parse an integer arithmetic expression (``+ - *``, parentheses,
    names, literals) into a :class:`Poly`."""
    def rec(node: ast.AST) -> Poly:
        if isinstance(node, ast.Constant) and isinstance(node.value, int):
            return Poly.const(node.value)
        if isinstance(node, ast.Name):
            return Poly.var(node.id)
        if isinstance(node, ast.BinOp):
            left, right = rec(node.left), rec(node.right)
            if isinstance(node.op, ast.Add):
                return left + right
            if isinstance(node.op, ast.Sub):
                return left - right
            if isinstance(node.op, ast.Mult):
                return left * right
        if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.USub):
            return -rec(node.operand)
        raise ValueError(f"unsupported spec expression: {expr!r}")

    return rec(ast.parse(expr, mode="eval").body)


# ---------------------------------------------------------------------------
# the nonnegativity prover
# ---------------------------------------------------------------------------

def prove_nonneg(p: Poly, order: list[str],
                 bounds: dict[str, tuple[Poly, Poly | None]]) -> bool:
    """Prove ``p >= 0`` for every assignment inside the bound box.

    Substitutes the first bounded symbol present by ``lo + δ`` (valid for
    the whole domain above ``lo``) or, when an upper bound exists, by
    ``hi − δ`` (valid below ``hi``); a branch succeeds when the fully
    substituted polynomial has only non-negative coefficients over the
    remaining δ's.  Bound expressions may only reference symbols *later*
    in ``order`` (the specs declare grid symbols first).
    """
    for k, sym in enumerate(order):
        if sym not in p.vars():
            continue
        lo, hi = bounds[sym]
        slack = Poly.var(f"{_FRESH}{k}")
        cands = [p.subst(sym, lo + slack)]
        if hi is not None:
            cands.append(p.subst(sym, hi - slack))
        return any(prove_nonneg(c, order[k + 1:], bounds) for c in cands)
    if any(not v.startswith(_FRESH) for v in p.vars()):
        return False  # a symbol with no declared bound survived
    return all(c >= 0 for c in p.terms.values())


# ---------------------------------------------------------------------------
# spec checks
# ---------------------------------------------------------------------------

class _SpecCtx:
    """One spec's parsed bounds, facts, and prover entry points."""

    def __init__(self, spec):
        self.spec = spec
        self.order = list(spec.bounds.keys())
        self.bounds = {
            s: (parse_expr(lo), parse_expr(hi) if hi is not None else None)
            for s, (lo, hi) in spec.bounds.items()}
        self.facts: list[tuple[str, Poly]] = []
        for fact in spec.facts:
            m = _FACT_RE.match(fact)
            if not m:
                raise ValueError(f"{spec.name}: malformed fact {fact!r}")
            self.facts.append((m.group(1), parse_expr(m.group(2))))

    def rw(self, p: Poly) -> Poly:
        """Eliminate fact-defined symbols (``n0 == nb*r`` rewrites)."""
        for _ in range(len(self.facts) + 1):
            q = p
            for sym, rhs in self.facts:
                q = q.subst(sym, rhs)
            if q == p:
                return p
            p = q
        return p

    def poly(self, expr: str) -> Poly:
        return self.rw(parse_expr(expr))

    def nonneg(self, p: Poly, guard: str = "") -> bool:
        bounds = self.bounds
        if guard:
            g = _GUARD_RE.match(guard)
            if not g:
                raise ValueError(
                    f"{self.spec.name}: malformed guard {guard!r}")
            sym, op, rhs = g.group(1), g.group(2), self.rw(
                parse_expr(g.group(3)))
            lo, hi = bounds[sym]
            bounds = dict(bounds)
            bounds[sym] = (rhs, hi) if op == ">=" else (lo, rhs)
        return prove_nonneg(self.rw(p), self.order, bounds)


def _finding(invariant: str, spec, message: str, suggestion: str = "",
             subject: str = "") -> Finding:
    return Finding(_ANALYZER, invariant, message,
                   subject=subject or spec.name,
                   file=f"src/repro/kernels/{spec.site[0]}.py",
                   suggestion=suggestion)


def _check_halos(ctx: _SpecCtx) -> list[Finding]:
    out = []
    for halo in ctx.spec.halos:
        idx = ctx.poly(halo.index)
        ext = ctx.poly(halo.extent)
        ok_lo = ctx.nonneg(idx, halo.guard)
        ok_hi = ctx.nonneg(ext - Poly.const(1) - idx, halo.guard)
        if not (ok_lo and ok_hi):
            side = "below 0" if not ok_lo else "past the extent"
            out.append(_finding(
                "halo-out-of-bounds", ctx.spec,
                f"halo read {halo.array}[{halo.index}] "
                f"(guard {halo.guard or 'none'!s}) can index {side} of "
                f"extent {halo.extent} for some admissible grid size",
                suggestion="tighten the halo guard to the zero-filled "
                           "boundary bands, or shrink the read row "
                           "expression"))
    return out


def _check_input_tiles(ctx: _SpecCtx) -> list[Finding]:
    out = []
    for tile in ctx.spec.inputs:
        bad_dim = None
        for d in range(len(tile.block)):
            idx = ctx.poly(tile.index[d])
            blk = ctx.poly(tile.block[d])
            ext = ctx.poly(tile.extent[d])
            lo = idx if tile.element else idx * blk
            hi = ext - lo - blk
            if not (ctx.nonneg(lo) and ctx.nonneg(hi)):
                bad_dim = d
                break
        if bad_dim is not None:
            out.append(_finding(
                "tile-out-of-bounds", ctx.spec,
                f"input {tile.name!r} dim {bad_dim}: block "
                f"{tile.block[bad_dim]} at index {tile.index[bad_dim]} "
                f"escapes extent {tile.extent[bad_dim]} for some "
                "admissible grid size",
                subject=f"{ctx.spec.name}.{tile.name}",
                suggestion="fix the BlockSpec index map or the declared "
                           "extent fact"))
    return out


def _check_coverage(ctx: _SpecCtx) -> list[Finding]:
    """Exactly-once output coverage: per-dim stride/edge proofs plus the
    no-unused-grid-symbol race condition."""
    spec = ctx.spec
    out: list[Finding] = []
    grid_syms = set(spec.grid)
    for tile in spec.outputs:
        used: set[str] = set()
        dim_findings: list[Finding] = []
        for d in range(len(tile.block)):
            idx = ctx.poly(tile.index[d])
            blk = ctx.poly(tile.block[d])
            ext = ctx.poly(tile.extent[d])
            syms = idx.vars() & grid_syms
            if not syms:
                if not (idx.is_zero() and blk == ext):
                    dim_findings.append(_finding(
                        "grid-write-gap", spec,
                        f"output {tile.name!r} dim {d}: constant index "
                        f"{tile.index[d]} with block {tile.block[d]} does "
                        f"not span extent {tile.extent[d]}",
                        subject=f"{spec.name}.{tile.name}"))
                continue
            if len(syms) > 1:
                dim_findings.append(_finding(
                    "grid-write-gap", spec,
                    f"output {tile.name!r} dim {d}: index map "
                    f"{tile.index[d]} mixes grid symbols "
                    f"{sorted(syms)}; coverage is unprovable",
                    subject=f"{spec.name}.{tile.name}"))
                continue
            (g,) = syms
            used.add(g)
            g_lo, g_hi = ctx.bounds[g]
            step = (idx.subst(g, Poly.var(g) + Poly.const(1)) - idx) * blk
            start = idx.subst(g, ctx.rw(g_lo)) * blk
            end = (idx.subst(g, ctx.rw(g_hi)) * blk + blk
                   if g_hi is not None else None)
            if step != blk:
                kind = ("grid-write-gap"
                        if prove_nonneg(ctx.rw(step - blk - Poly.const(1)),
                                        ctx.order, ctx.bounds)
                        else "grid-write-overlap")
                dim_findings.append(_finding(
                    kind, spec,
                    f"output {tile.name!r} dim {d}: grid stride "
                    f"({step.render()}) != block ({blk.render()}) — "
                    "adjacent grid steps "
                    + ("leave uncovered elements" if kind == "grid-write-gap"
                       else "write overlapping blocks"),
                    subject=f"{spec.name}.{tile.name}"))
            elif not ctx.rw(start).is_zero():
                dim_findings.append(_finding(
                    "grid-write-gap", spec,
                    f"output {tile.name!r} dim {d}: first block starts at "
                    f"{ctx.rw(start).render()}, not 0",
                    subject=f"{spec.name}.{tile.name}"))
            elif end is not None and ctx.rw(end - ext) != Poly.const(0):
                over = ctx.rw(end - ext)
                kind = ("grid-write-gap"
                        if prove_nonneg(ctx.rw(ext - end - Poly.const(1)),
                                        ctx.order, ctx.bounds)
                        else "tile-out-of-bounds")
                dim_findings.append(_finding(
                    kind, spec,
                    f"output {tile.name!r} dim {d}: last block ends at "
                    f"{ctx.rw(end).render()} but the extent is "
                    f"{ext.render()} (difference {over.render()})",
                    subject=f"{spec.name}.{tile.name}"))
        unused = grid_syms - used
        if unused and not spec.sequential_revisit:
            # root cause subsumes any constant-index dim findings
            out.append(_finding(
                "grid-write-overlap", spec,
                f"output {tile.name!r}: grid symbol(s) {sorted(unused)} do "
                "not appear in the output index map — every step of that "
                "grid axis rewrites the same block (write race under "
                "parallel grids, silent last-writer-wins otherwise)",
                subject=f"{spec.name}.{tile.name}",
                suggestion="index the output block by every grid symbol, "
                           "or declare sequential_revisit=True for a "
                           "deliberate TPU sequential-grid accumulator"))
        else:
            out.extend(dim_findings)
    return out


#: Mosaic's block tiling: (sublane, lane) multiples of the last two dims.
_TILING = (8, 128)


def _divisible(p: Poly, k: int) -> bool:
    """``k`` divides ``p`` for every value of its symbols (sufficient:
    every coefficient is a multiple of ``k``)."""
    return all(c % k == 0 for c in p.terms.values())


def _check_tiling(ctx: _SpecCtx) -> list[Finding]:
    out = []
    for tile in ctx.spec.inputs + ctx.spec.outputs:
        nd = len(tile.block)
        for d, k in zip(range(max(0, nd - 2), nd), _TILING[-min(nd, 2):]):
            blk = ctx.poly(tile.block[d])
            if blk == ctx.poly(tile.extent[d]) or _divisible(blk, k):
                continue
            out.append(_finding(
                "block-tiling", ctx.spec,
                f"{tile.name!r} dim {d}: block {tile.block[d]} is neither a "
                f"multiple of {k} nor the array extent {tile.extent[d]} — "
                "the TPU compiler refuses such a block",
                subject=f"{ctx.spec.name}.{tile.name}",
                suggestion=f"size the block in multiples of {k} (declare the "
                           "fact, e.g. r == 8*rq) or make it span the dim"))
            break
    return out


def _check_vmem(ctx: _SpecCtx, env: Envelope, budget: int) -> list[Finding]:
    p = ctx.poly(ctx.spec.vmem_elems).subst(
        "F", Poly.const(env.max_field_elems))
    val = p.const_value()
    if val is None:
        return [_finding(
            "vmem-budget", ctx.spec,
            f"vmem_elems {ctx.spec.vmem_elems!r} does not reduce to a "
            "constant under the envelope (free symbols "
            f"{sorted(p.vars())})",
            suggestion="express the footprint over F and literals")]
    dtype_bytes = max([t.dtype_bytes for t in
                       ctx.spec.inputs + ctx.spec.outputs] or [4])
    used = val * dtype_bytes
    if used > budget:
        return [_finding(
            "vmem-budget", ctx.spec,
            f"per-cell VMEM footprint {used} bytes "
            f"({ctx.spec.vmem_elems} elems at F={env.max_field_elems}) "
            f"exceeds the {budget}-byte budget",
            suggestion="shrink MAX_BAND / the tile, or lower the "
                       "envelope's max_field_elems")]
    return []


# ---------------------------------------------------------------------------
# the bounded-exhaustive unpack lemma
# ---------------------------------------------------------------------------

def check_unpack_lemma(wpb_extra: int | None = None) -> list[Finding]:
    """Prove the in-kernel unpack's per-tile word window is wide enough.

    ``bitpack.unpack_lanes`` unpacks 128-value lane tiles; tile ``c``
    starts ``4*bits*c`` words into its row, and lane ``l`` reads the words
    ``(s + l*bits) >> 5`` and one past it (the carry word, read
    unconditionally), with ``s`` the row's in-word offset.  The tile's
    values span ``Q = 4*bits`` words, so the window must hold
    ``Q + WPB_EXTRA`` words and still fit one 128-lane gather.  Offsets
    grow with the lane, so lane 127 dominates; sweeping every ``(bits,
    s)`` in ``[1,32) x [0,32)`` covers all rows of all lengths.
    """
    if wpb_extra is None:
        from repro.kernels import specs as kspecs
        wpb_extra = kspecs.WPB_EXTRA
    for bits in range(1, 32):
        q = 4 * bits
        for s0 in range(32):
            hi_read = ((s0 + 127 * bits) >> 5) + 1 - q
            if hi_read > wpb_extra - 1 or q + wpb_extra > 128:
                return [Finding(
                    _ANALYZER, "unpack-oob",
                    f"in-kernel unpack at bits={bits}, in-word offset "
                    f"{s0} reads relative word Q{hi_read:+d} but the "
                    f"window has {wpb_extra} words past Q (and must fit "
                    "128 lanes)",
                    subject="bitpack.unpack_lanes",
                    file="src/repro/kernels/bitpack.py",
                    suggestion="restore WPB_EXTRA = 2 in "
                               "repro.kernels.specs (offset word + "
                               "carry word)")]
    return []


# ---------------------------------------------------------------------------
# output-multiply (FMA-contraction hazard) lint
# ---------------------------------------------------------------------------

def _waivers(source: str) -> dict[int, list[tuple[int, str]]]:
    """Line -> [(comment line, invariant)] — a waiver covers its own line
    and the one below."""
    out: dict[int, list[tuple[int, str]]] = {}
    for i, line in enumerate(source.splitlines(), start=1):
        m = _WAIVE_RE.search(line)
        if m:
            for w in m.group(1).split(","):
                w = w.strip()
                if w:
                    out.setdefault(i, []).append((i, w))
                    out.setdefault(i + 1, []).append((i, w))
    return out


def _is_ref_store(target: ast.AST) -> bool:
    """Is this subscript-assignment target an output ref?  Matches
    ``<name>_ref[...]`` and the ``next(outs)[...]`` iterator idiom."""
    if not isinstance(target, ast.Subscript):
        return False
    base = target.value
    if isinstance(base, ast.Name) and base.id.endswith("_ref"):
        return True
    return (isinstance(base, ast.Call) and isinstance(base.func, ast.Name)
            and base.func.id == "next")


def _floatish(node: ast.AST) -> bool:
    """Does the expression involve float arithmetic?  (float constants,
    any dotted name mentioning float, ``.astype(...)`` casts.)"""
    for n in ast.walk(node):
        if isinstance(n, ast.Constant) and isinstance(n.value, float):
            return True
        if isinstance(n, ast.Attribute) and ("float" in n.attr
                                             or n.attr == "astype"):
            return True
        if isinstance(n, ast.Name) and "float" in n.id:
            return True
    return False


class _KernelLint:
    """Resolve stored-expression roots through local helpers and flag
    root-level float multiplies feeding output refs."""

    def __init__(self, tree: ast.Module):
        self.defs: dict[str, ast.AST] = {}
        for n in ast.walk(tree):
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
                # last definition wins; nested defs shadow by name
                self.defs[n.name] = n

    def resolve_root(self, node: ast.AST, fdef: ast.AST,
                     seen: set | None = None) -> ast.AST:
        seen = seen or set()
        while True:
            if isinstance(node, ast.BinOp):
                return node
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Name)
                    and node.func.id in self.defs
                    and node.func.id not in seen):
                seen.add(node.func.id)
                fdef = self.defs[node.func.id]
                rets = [r for r in ast.walk(fdef)
                        if isinstance(r, ast.Return) and r.value is not None]
                if not rets:
                    return node
                node = rets[-1].value
                continue
            if isinstance(node, ast.Name):
                key = (id(fdef), node.id)
                if key in seen:
                    return node
                seen.add(key)
                assigns = [a for a in ast.walk(fdef)
                           if isinstance(a, ast.Assign)
                           and any(isinstance(t, ast.Name) and t.id == node.id
                                   for t in a.targets)]
                if not assigns:
                    return node
                node = assigns[-1].value
                continue
            return node


def lint_kernel_source(source: str, path: str = "<string>"
                       ) -> tuple[list[Finding], list[tuple[int, str]],
                                  set[tuple[int, str]]]:
    """Output-multiply lint for one kernel module.

    Returns ``(findings, declared_waivers, used_waivers)`` so the caller
    can run stale-waiver detection across the package.
    """
    tree = ast.parse(source)
    waivers = _waivers(source)
    declared = sorted({w for ws in waivers.values() for w in ws})
    used: set[tuple[int, str]] = set()
    lint = _KernelLint(tree)
    findings: list[Finding] = []

    def flag(node: ast.AST, message: str):
        line = getattr(node, "lineno", 0)
        hits = [w for w in waivers.get(line, [])
                if w[1] == "output-multiply"]
        if hits:
            used.update(hits)
            return
        findings.append(Finding(
            _ANALYZER, "output-multiply", message,
            subject="kernel store", file=path, line=line,
            suggestion="emit the unscaled integer/accumulated plane and "
                       "apply the float tail in the XLA lowering rule "
                       "(# audit: waive(output-multiply) if deliberate)"))

    for fdef in ast.walk(tree):
        if not isinstance(fdef, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        for stmt in ast.walk(fdef):
            if isinstance(stmt, ast.Assign):
                targets = stmt.targets
                value = stmt.value
            elif isinstance(stmt, ast.AugAssign):
                targets = [stmt.target]
                value = None
            else:
                continue
            if not any(_is_ref_store(t) for t in targets):
                continue
            if isinstance(stmt, ast.AugAssign):
                if (isinstance(stmt.op, ast.Mult)
                        and _floatish(stmt.value)):
                    flag(stmt, "augmented float multiply into an output "
                               "ref (FMA-contraction hazard)")
                continue
            root = lint.resolve_root(value, fdef)
            if (isinstance(root, ast.BinOp)
                    and isinstance(root.op, ast.Mult)
                    and (_floatish(value) or _floatish(root))):
                flag(stmt, "float multiply is the final op feeding an "
                           "output ref — XLA CPU fusion can duplicate and "
                           "FMA-contract it shape-dependently, breaking "
                           "bit-identity (the PR 8 hazard)")
    return findings, declared, used


# ---------------------------------------------------------------------------
# spec <-> call-site sync
# ---------------------------------------------------------------------------

def _scan_sites(src_root: Path) -> dict[tuple[str, str, int], int | None]:
    """Every ``pl.pallas_call`` site under ``kernels/`` keyed by
    (module, enclosing function, ordinal); value is the literal grid
    arity when extractable."""
    sites: dict[tuple[str, str, int], int | None] = {}
    for py in sorted((src_root / "kernels").glob("*.py")):
        module = py.stem
        tree = ast.parse(py.read_text())
        for fdef in tree.body:
            if not isinstance(fdef, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            ordinal = 0
            for node in ast.walk(fdef):
                if not (isinstance(node, ast.Call)
                        and isinstance(node.func, ast.Attribute)
                        and node.func.attr == "pallas_call"):
                    continue
                arity = None
                for kw in node.keywords:
                    if kw.arg == "grid" and isinstance(kw.value, ast.Tuple):
                        arity = len(kw.value.elts)
                sites[(module, fdef.name, ordinal)] = arity
                ordinal += 1
    return sites


def _check_sites(specs, src_root: Path) -> list[Finding]:
    sites = _scan_sites(src_root)
    by_site = {s.site: s for s in specs}
    out: list[Finding] = []
    for site, arity in sorted(sites.items()):
        spec = by_site.get(site)
        if spec is None:
            out.append(Finding(
                _ANALYZER, "undeclared-kernel",
                f"pl.pallas_call site #{site[2]} in {site[1]}() has no "
                "KernelSpec — its grid/bounds/race invariants are "
                "unverified",
                subject=f"{site[0]}.{site[1]}",
                file=f"src/repro/kernels/{site[0]}.py",
                suggestion="declare the site in repro.kernels.specs."
                           "KERNEL_SPECS"))
        elif arity is not None and arity != len(spec.grid):
            out.append(Finding(
                _ANALYZER, "spec-grid-mismatch",
                f"{spec.name}: spec declares {len(spec.grid)} grid "
                f"dimension(s) but the call site has {arity}",
                subject=spec.name,
                file=f"src/repro/kernels/{site[0]}.py",
                suggestion="update the KernelSpec grid symbols"))
    for spec in specs:
        if spec.site not in sites:
            out.append(Finding(
                _ANALYZER, "stale-kernel-spec",
                f"KernelSpec {spec.name!r} names call site {spec.site} "
                "which no longer exists",
                subject=spec.name, file="src/repro/kernels/specs.py",
                suggestion="delete or re-point the spec"))
    return out


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------

def check_spec(spec, env: Envelope = DEFAULT_ENVELOPE, *,
               vmem_budget_bytes: int = VMEM_BUDGET_BYTES) -> list[Finding]:
    """All symbolic checks for one :class:`KernelSpec` (fixture entry)."""
    try:
        ctx = _SpecCtx(spec)
    except ValueError as e:
        return [Finding(_ANALYZER, "spec-unprovable", str(e),
                        subject=spec.name)]
    findings = _check_halos(ctx)
    findings += _check_input_tiles(ctx)
    findings += _check_coverage(ctx)
    findings += _check_tiling(ctx)
    findings += _check_vmem(ctx, env, vmem_budget_bytes)
    return findings


def analyze_kernel_specs(env: Envelope = DEFAULT_ENVELOPE, *,
                         specs=None, src_root: str | Path | None = None,
                         vmem_budget_bytes: int = VMEM_BUDGET_BYTES,
                         wpb_extra: int | None = None) -> list[Finding]:
    """Run the kernel verifier against the live specs and kernel sources.

    ``specs`` / ``src_root`` / ``wpb_extra`` are injectable for the
    sabotage fixtures; defaults audit the real repo.
    """
    if specs is None:
        from repro.kernels.specs import KERNEL_SPECS
        specs = KERNEL_SPECS
    if src_root is None:
        src_root = Path(__file__).resolve().parent.parent
    src_root = Path(src_root)

    findings: list[Finding] = []
    for spec in specs:
        findings.extend(check_spec(spec, env,
                                   vmem_budget_bytes=vmem_budget_bytes))
    if any(s.unpack_words for s in specs):
        findings.extend(check_unpack_lemma(wpb_extra))

    declared_all: list[tuple[str, int, str]] = []
    used_all: set[tuple[str, int, str]] = set()
    kdir = src_root / "kernels"
    if kdir.is_dir():
        for py in sorted(kdir.glob("*.py")):
            rel = str(py.relative_to(src_root.parent.parent))
            fs, declared, used = lint_kernel_source(py.read_text(), rel)
            findings.extend(fs)
            declared_all += [(rel, ln, name) for ln, name in declared
                             if name == "output-multiply"]
            used_all |= {(rel, ln, name) for ln, name in used}
        findings.extend(_check_sites(specs, src_root))
    for rel, ln, name in declared_all:
        if (rel, ln, name) not in used_all:
            findings.append(Finding(
                _ANALYZER, "stale-waiver",
                f"# audit: waive({name}) suppresses no kernelspec finding "
                "— the waived code has moved or been fixed",
                subject=name, file=rel, line=ln, severity="warning",
                suggestion="delete the stale waiver comment"))
    return findings
