"""HBM-bytes proxies of a program: compiled HLO (:func:`analyze`) and
native-lowering jaxprs holding Pallas kernels (:func:`jaxpr_bytes`).

``Compiled.cost_analysis()`` counts each computation once, but ``lax.scan``
lowers to a ``while`` whose body runs L times — so for a loop of L steps
it undercounts FLOPs/bytes/collectives by ~L.  :func:`analyze` parses
``compiled.as_text()`` (the per-device, post-partitioning module):

  1. split the module into computation blocks;
  2. recover each while loop's trip count from its condition block
     (the loop-bound constant — exact for lax.scan lowerings);
  3. propagate multipliers through the call graph (while bodies x trip,
     fusions/calls x callsite multiplier);
  4. per block, account dot/conv FLOPs (operand shapes resolved from local
     SSA defs), elementwise/copy output bytes (HBM-traffic proxy for
     non-fusion-internal ops), and collective payload bytes by kind.

Numbers are per-device (the module is the per-device SPMD program).
"""
from __future__ import annotations

import re
from collections import defaultdict

import numpy as np

DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "s4": 1, "u4": 1, "pred": 1, "c64": 8, "c128": 16,
}
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")

# Ops whose outputs stay in VMEM/registers under TPU fusion — excluded from
# the HBM-traffic proxy.  Structural estimate: real fusion decisions differ,
# but counting every elementwise temp would overstate traffic ~10-30x.
ELEMENTWISE = {
    "add", "subtract", "multiply", "divide", "maximum", "minimum", "negate",
    "abs", "exponential", "exponential-minus-one", "log", "log-plus-one",
    "tanh", "logistic", "rsqrt", "sqrt", "power", "select", "compare", "and",
    "or", "xor", "not", "shift-left", "shift-right-logical",
    "shift-right-arithmetic", "convert", "broadcast", "iota", "reshape",
    "round-nearest-even", "round-nearest-afz", "floor", "ceil", "sign",
    "clamp", "is-finite", "reduce-precision", "sine", "cosine", "expm1",
    "log1p", "rem", "atan2", "pad", "slice", "concatenate", "rev",
}

_BLOCK_START = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w\.\-]+)\s*\((.*)\)\s*->")
_OP_LINE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w\.\-]+)\s*=\s*(\(?[a-z0-9]+\[.*?)\s*([a-z][\w\-]*)\(")
_TYPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_PARAM_RE = re.compile(r"%?([\w\.\-]+):\s*(\(?[a-z0-9]+\[[^)]*?\]?\)?)(?:,|$)")


def _type_bytes_and_shapes(type_str: str) -> tuple[float, list[tuple[str, list[int]]]]:
    shapes = []
    total = 0.0
    for dt, dims in _TYPE_RE.findall(type_str):
        if dt not in DTYPE_BYTES:
            continue
        shape = [int(d) for d in dims.split(",") if d] if dims else []
        numel = 1
        for d in shape:
            numel *= d
        total += numel * DTYPE_BYTES[dt]
        shapes.append((dt, shape))
    return total, shapes


class Block:
    def __init__(self, name: str):
        self.name = name
        self.lines: list[str] = []
        self.defs: dict[str, str] = {}      # ssa name -> type string
        self.whiles: list[tuple[str, str]] = []  # (body, cond)
        self.calls: list[str] = []          # fusion/call targets
        self.dot_flops = 0.0
        self.bytes = 0.0
        self.collectives: dict[str, tuple[int, float]] = defaultdict(lambda: (0, 0.0))


def _parse_blocks(text: str) -> dict[str, Block]:
    blocks: dict[str, Block] = {}
    cur: Block | None = None
    for line in text.splitlines():
        if cur is None:
            m = _BLOCK_START.match(line)
            if m and "{" in line:
                cur = Block(m.group(1))
                for pname, ptype in _PARAM_RE.findall(m.group(2)):
                    cur.defs[pname] = ptype
            continue
        if line.strip() == "}" or line.rstrip().endswith("} // " + cur.name):
            blocks[cur.name] = cur
            cur = None
            continue
        if line.strip().startswith("}"):
            blocks[cur.name] = cur
            cur = None
            continue
        cur.lines.append(line)
    if cur is not None:
        blocks[cur.name] = cur
    return blocks


def _analyze_block(b: Block):
    for line in b.lines:
        m = _OP_LINE.match(line)
        if not m:
            continue
        name, type_str, op = m.groups()
        b.defs[name] = type_str
        out_bytes, out_shapes = _type_bytes_and_shapes(type_str)

        if op == "while":
            mb = re.search(r"body=%?([\w\.\-]+)", line)
            mc = re.search(r"condition=%?([\w\.\-]+)", line)
            mt = re.search(r'"known_trip_count":\{"n":"(\d+)"\}', line)
            if mb and mc:
                b.whiles.append((mb.group(1), mc.group(1),
                                 int(mt.group(1)) if mt else None))
            continue
        if op in ("fusion", "call", "conditional"):
            for target in re.findall(r"(?:calls|to_apply)=%?([\w\.\-]+)", line):
                b.calls.append(target)
            b.bytes += out_bytes
            continue
        if op in COLLECTIVES or op.rstrip("-start") in COLLECTIVES:
            kind = op.replace("-start", "")
            if kind in COLLECTIVES:
                cnt, byt = b.collectives[kind]
                b.collectives[kind] = (cnt + 1, byt + out_bytes)
            continue
        if op == "dot":
            ops_m = re.search(r"dot\(([^)]*)\)", line)
            contract = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", line)
            if ops_m:
                operands = [o.strip().lstrip("%") for o in ops_m.group(1).split(",")]
                lhs_type = b.defs.get(operands[0], "")
                rhs_type = b.defs.get(operands[1], "") if len(operands) > 1 else ""
                lhs_bytes, lhs_shapes = _type_bytes_and_shapes(lhs_type)
                rhs_bytes, _ = _type_bytes_and_shapes(rhs_type)
                k = 1
                if lhs_shapes and contract:
                    lshape = lhs_shapes[0][1]
                    for ci in contract.group(1).split(","):
                        if ci:
                            idx = int(ci)
                            if idx < len(lshape):
                                k *= lshape[idx]
                out_numel = 1
                for _, shp in out_shapes:
                    for d in shp:
                        out_numel *= d
                b.dot_flops += 2.0 * out_numel * k
                b.bytes += lhs_bytes + rhs_bytes  # both operands stream from HBM
            b.bytes += out_bytes
            continue
        if op == "convolution":
            # rough: 2 * out_numel * (kernel elems) — rare in these programs
            b.dot_flops += 2.0 * out_bytes  # conservative placeholder
            b.bytes += out_bytes
            continue
        if op in ("parameter", "constant", "tuple", "get-tuple-element",
                  "bitcast", "after-all", "partition-id") or op in ELEMENTWISE:
            continue
        if op in ("dynamic-update-slice", "scatter"):
            # in-place update: traffic = the written slice, not the buffer
            # (XLA aliases the operand; counting the output would charge the
            # whole buffer per update)
            ops_m = re.search(r"\(([^)]*)\)", line)
            if ops_m:
                operands = [o.strip().lstrip("%") for o in ops_m.group(1).split(",")]
                if len(operands) > 1:
                    upd_bytes, _ = _type_bytes_and_shapes(b.defs.get(operands[1], ""))
                    b.bytes += upd_bytes
                    continue
        b.bytes += out_bytes


def _trip_count(cond: Block) -> int:
    """Loop bound from the condition block: the largest s32 constant."""
    best = 1
    for line in cond.lines:
        for m in re.finditer(r"constant\((\d+)\)", line):
            best = max(best, int(m.group(1)))
    return best


def analyze(text: str, entry_hint: str = "main") -> dict:
    blocks = _parse_blocks(text)
    for b in blocks.values():
        _analyze_block(b)

    entry_name = None
    for name in blocks:
        if name.startswith(entry_hint):
            entry_name = name
    if entry_name is None:  # fall back: the block with most whiles/lines
        entry_name = max(blocks, key=lambda n: len(blocks[n].lines))

    # execution multiplier = sum over call paths of the product of loop trip
    # counts along the path (the call graph is a DAG; memoized recursion)
    parents: dict[str, list[tuple[str, float]]] = defaultdict(list)
    for name, b in blocks.items():
        for body, cond, known in b.whiles:
            trips = known if known is not None else (
                _trip_count(blocks[cond]) if cond in blocks else 1)
            parents[body].append((name, float(trips)))
            parents[cond].append((name, float(trips) + 1.0))
        for callee in b.calls:
            parents[callee].append((name, 1.0))

    memo: dict[str, float] = {}

    def mult_of(name: str, _depth=0) -> float:
        if name == entry_name:
            return 1.0
        if name in memo:
            return memo[name]
        if _depth > len(blocks) + 2:  # cycle guard
            return 0.0
        memo[name] = 0.0  # break accidental cycles
        memo[name] = sum(mult_of(p, _depth + 1) * w for p, w in parents[name])
        return memo[name]

    mult = {name: mult_of(name) for name in blocks}

    total = {"dot_flops": 0.0, "bytes": 0.0,
             "collectives": defaultdict(lambda: {"count": 0.0, "bytes": 0.0})}
    for name, b in blocks.items():
        m = mult.get(name, 0.0)
        if m == 0.0:
            continue
        total["dot_flops"] += m * b.dot_flops
        total["bytes"] += m * b.bytes
        for kind, (cnt, byt) in b.collectives.items():
            total["collectives"][kind]["count"] += m * cnt
            total["collectives"][kind]["bytes"] += m * byt

    wire = 0.0
    for kind, rec in total["collectives"].items():
        factor = 2.0 if kind == "all-reduce" else 1.0
        rec["wire_bytes"] = rec["bytes"] * factor
        wire += rec["wire_bytes"]
    return {
        "dot_flops": total["dot_flops"],
        "bytes_proxy": total["bytes"],
        "collectives": {k: dict(v) for k, v in total["collectives"].items()},
        "wire_bytes_total": wire,
    }


#: jaxpr primitives that are elementwise or pure layout — free under the
#: same fusion assumption :data:`ELEMENTWISE` makes for HLO ops.
_FREE_PRIMS = frozenset({
    "add", "sub", "mul", "div", "rem", "neg", "sign", "abs", "max", "min",
    "and", "or", "xor", "not", "shift_left", "shift_right_logical",
    "shift_right_arithmetic", "eq", "ne", "lt", "le", "gt", "ge",
    "select_n", "convert_element_type", "integer_pow", "exp", "log",
    "sqrt", "rsqrt", "floor", "ceil", "round", "stop_gradient", "copy",
    "reshape", "squeeze", "broadcast_in_dim", "slice", "concatenate",
    "pad", "iota", "transpose",
})


def _out_bytes(eqn) -> int:
    return sum(int(np.prod(v.aval.shape)) * v.aval.dtype.itemsize
               for v in eqn.outvars)


def jaxpr_bytes(jaxpr) -> int:
    """HBM-bytes proxy of a (native-lowering) jaxpr: summed output bytes of
    every non-elementwise equation, recursing through call wrappers.

    The counterpart of :func:`analyze` for programs containing
    ``pallas_call`` equations, which cannot be measured from compiled HLO
    on CPU: interpret mode emulates the grid with per-step dynamic-slice /
    full-array dynamic-update-slice pairs whose HLO bytes are pure
    emulation artifact (24-34x the real kernel I/O).  A pallas_call counts
    its *outputs* only — its operands are the outputs of counted producers
    (the payload word gather, halo gathers) or program arguments, exactly
    as HLO op outputs chain in the proxy.
    """
    total = 0
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "pallas_call":
            total += _out_bytes(eqn)
            continue
        subs = []
        for v in eqn.params.values():
            for s in (v if isinstance(v, (tuple, list)) else (v,)):
                inner = getattr(s, "jaxpr", s)
                if hasattr(inner, "eqns"):
                    subs.append(inner)
        if subs:
            total += sum(jaxpr_bytes(s) for s in subs)
        elif name not in _FREE_PRIMS:
            total += _out_bytes(eqn)
    return total
