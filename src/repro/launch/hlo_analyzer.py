"""Structural HLO analyzer: walks the compiled module's computation
graph, multiplying `while`-loop bodies by their trip counts, to produce
loop-aware per-device FLOP and collective-byte totals.

Why: XLA's `cost_analysis()` counts a while body ONCE regardless of trip
count, so a 60-layer scanned transformer reports ~1/60th of its FLOPs
(verified in tests/test_hlo_analyzer.py). The dry-run's roofline terms
would be garbage without this correction.

Trip-count heuristic: jax.lax.scan lowers to while(tuple(...)) whose
induction bound enters the init tuple as a scalar s32/u32 constant; we
take the max scalar integer constant feeding the init tuple. Verified
against known-depth scans in the tests.
"""
from __future__ import annotations

import math
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1, "s4": 1, "u4": 1,
}
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "all-to-all", "collective-permute")
_TRAFFIC_MULT = {"all-reduce": 2.0, "all-gather": 1.0,
                 "reduce-scatter": 1.0, "all-to-all": 1.0,
                 "collective-permute": 1.0}
# Type may be a tuple containing /*index=N*/ comments (which contain
# '='), so match lazily and anchor on "opcode(" following the type.
_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w\.\-]+)\s*=\s*(.+?)\s+"
    r"([a-z][\w\-]*)\((.*)$")


def xla_cost_analysis(compiled) -> Dict[str, float]:
    """`compiled.cost_analysis()` as a plain dict."""
    return dict(compiled.cost_analysis())


def _shapes(type_str: str) -> List[Tuple[str, Tuple[int, ...]]]:
    out = []
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt in _DTYPE_BYTES:
            out.append((dt, tuple(int(d) for d in dims.split(",") if d)))
    return out


def _type_bytes(type_str: str) -> float:
    return sum(math.prod(dims) * _DTYPE_BYTES[dt]
               for dt, dims in _shapes(type_str))


@dataclass
class Op:
    name: str
    type_str: str
    opcode: str
    rest: str                      # operands + attributes text


@dataclass
class Computation:
    name: str
    ops: List[Op] = field(default_factory=list)
    types: Dict[str, str] = field(default_factory=dict)


@dataclass
class Analysis:
    dot_flops: float = 0.0
    collective_bytes: float = 0.0
    # TPU-equivalent traffic: XLA:CPU computes bf16 dots in f32, so
    # dot-adjacent collectives (operands produced by convert fusions)
    # move 2x the bytes a bf16-native backend would; this field halves
    # those (heuristic: producer op name contains "convert").
    collective_bytes_bf16eq: float = 0.0
    per_collective: Dict[str, Dict[str, float]] = field(
        default_factory=lambda: {k: {"count": 0.0, "bytes": 0.0}
                                 for k in COLLECTIVES})
    while_trips: List[int] = field(default_factory=list)


def parse_module(text: str) -> Tuple[Dict[str, Computation], str]:
    comps: Dict[str, Computation] = {}
    cur: Optional[Computation] = None
    entry = ""
    # params may be tuple-typed (nested parens) -> greedy group
    header = re.compile(r"^\s*(ENTRY\s+)?%?([\w\.\-]+)\s*"
                        r"\(.*\)\s*->\s*.+\{\s*$")
    for line in text.splitlines():
        h = header.match(line)
        if h:
            name = h.group(2)
            cur = Computation(name)
            comps[name] = cur
            if h.group(1):
                entry = name
            continue
        if line.strip() == "}":
            cur = None
            continue
        if cur is None:
            continue
        m = _OP_RE.match(line)
        if m:
            op = Op(m.group(1), m.group(2), m.group(3), m.group(4))
            cur.ops.append(op)
            cur.types[op.name] = op.type_str
    return comps, entry


def _find_attr(rest: str, key: str) -> Optional[str]:
    m = re.search(rf"{key}=%?([\w\.\-]+)", rest)
    return m.group(1) if m else None


def _contracting_sizes(op: Op, comp: Computation) -> float:
    """Product of lhs contracting-dim sizes for a dot."""
    m = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", op.rest)
    operands = re.findall(r"%([\w\.\-]+)", op.rest.split("),")[0])
    if not operands:
        return 1.0
    lhs_t = comp.types.get(operands[0], "")
    sh = _shapes(lhs_t)
    if not sh:
        return 1.0
    dims = sh[0][1]
    if not m:
        return dims[-1] if dims else 1.0
    idxs = [int(i) for i in m.group(1).split(",") if i]
    return math.prod(dims[i] for i in idxs) if idxs else 1.0


def _trip_count(init_tuple_op: Optional[Op], comp: Computation,
                while_op: Op) -> int:
    """Trip count of a while loop. Primary source: XLA's
    backend_config known_trip_count annotation; fallback: max scalar
    int constant feeding the init tuple (following one copy hop)."""
    m = re.search(r"known_trip_count[^0-9]*(\d+)", while_op.rest)
    if m:
        return int(m.group(1))
    cands = []
    ops_to_scan = []
    by_name = {o.name: o for o in comp.ops}
    if init_tuple_op is not None:
        names = re.findall(r"%([\w\.\-]+)", init_tuple_op.rest)
        resolved = []
        for n in names:
            o = by_name.get(n)
            if o is not None and o.opcode == "copy":
                src = re.findall(r"%([\w\.\-]+)", o.rest)
                o = by_name.get(src[0]) if src else None
            if o is not None:
                resolved.append(o)
        ops_to_scan = resolved
    for o in ops_to_scan:
        if o.opcode == "constant" and re.fullmatch(
                r"[su]\d+\[\]", o.type_str):
            m = re.match(r"(\-?\d+)", o.rest.rstrip(") "))
            if m:
                cands.append(abs(int(m.group(1))))
    return max(cands) if cands else 1


def analyze(text: str) -> Analysis:
    comps, entry = parse_module(text)
    res = Analysis()
    if not entry:
        entry = next(iter(comps), "")

    def walk(comp_name: str, mult: float, depth: int = 0) -> None:
        if depth > 12 or comp_name not in comps:
            return
        comp = comps[comp_name]
        by_name = {o.name: o for o in comp.ops}
        for op in comp.ops:
            code = op.opcode
            base = code[:-6] if code.endswith("-start") else code
            if base in COLLECTIVES and not code.endswith("-done"):
                b = _type_bytes(op.type_str)
                if base == "all-reduce" and code.endswith("-start"):
                    # start op result may be a (operand, result) tuple
                    b = b / 2 if op.type_str.startswith("(") else b
                traffic = b * _TRAFFIC_MULT[base]
                res.collective_bytes += traffic * mult
                res.per_collective[base]["count"] += mult
                res.per_collective[base]["bytes"] += traffic * mult
                operands = re.findall(r"%([\w\.\-]+)", op.rest)
                upcast = ("f32[" in op.type_str and operands
                          and "convert" in operands[0])
                res.collective_bytes_bf16eq += traffic * mult * \
                    (0.5 if upcast else 1.0)
            elif code == "dot":
                flops = 2.0 * _type_bytes(op.type_str) / max(
                    _DTYPE_BYTES.get(_shapes(op.type_str)[0][0], 4), 1) \
                    * _contracting_sizes(op, comp)
                res.dot_flops += flops * mult
            elif code == "while":
                body = _find_attr(op.rest, "body")
                operands = re.findall(r"%([\w\.\-]+)", op.rest.split(")")[0])
                init = by_name.get(operands[0]) if operands else None
                trips = _trip_count(init, comp, op)
                res.while_trips.append(trips)
                if body:
                    walk(body, mult * trips, depth + 1)
            elif code in ("fusion", "call", "async-start"):
                callee = _find_attr(op.rest, "calls") or \
                    _find_attr(op.rest, "to_apply")
                if callee:
                    walk(callee, mult, depth + 1)
            elif code == "conditional":
                for branch in re.findall(
                        r"(?:branch_computations=\{([^}]*)\}|"
                        r"(?:true|false)_computation=%?([\w\.\-]+))",
                        op.rest):
                    for b in branch:
                        for nm in re.findall(r"%?([\w\.\-]+)", b or ""):
                            walk(nm, mult, depth + 1)

    walk(entry, 1.0)
    return res
