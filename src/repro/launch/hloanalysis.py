"""HLO-text cost analyzer with while-loop trip-count accounting.

``compiled.cost_analysis()`` visits each computation ONCE, so a
``lax.scan`` over 20 superblocks reports 1/20th of the real FLOPs
(verified in tests/test_roofline.py). This analyzer re-derives

    flops            — dot ops exact (2 * out_elems * contracted_elems),
                       elementwise/reduce ops at 1 flop/output element
    memory bytes     — operands + outputs at fusion boundaries
                       (same convention as XLA's bytes_accessed)
    collective bytes — per-device ICI traffic with ring multipliers
                       (see launch/roofline.py)

from the optimized per-device HLO text, multiplying every computation by
its call multiplicity: fusions x1, while bodies x known_trip_count
(present as backend_config on scheduled while ops).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple


def cost_analysis_dict(compiled) -> Dict[str, float]:
    """`compiled.cost_analysis()` as a plain dict (empty when XLA reports
    nothing for a trivial program)."""
    return dict(compiled.cost_analysis() or {})

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "f16": 2, "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
    "f64": 8, "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1, "token": 0,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
# "%name = <shape-or-tuple> opcode(" ; shape may be a flat tuple
# "(f32[..], /*index=5*/ bf16[..], ...)" — comments contain '=' but no parens.
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*((?:\([^()]*\))|(?:\w+\[[\d,]*\]\S*))\s*"
    r"([\w\-]+)\(")
_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\((.*)\)\s*->")
_PARAM_RE = re.compile(r"([\w.\-]+):\s*((?:\([^)]*\))|(?:\w+\[[\d,]*\]))")
_TRIP_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}|known_trip_count=\{n=(\d+)\}')
_CALLS_RE = re.compile(r"calls=%?([\w.\-]+)")
_BODY_RE = re.compile(r"body=%?([\w.\-]+)")
_COND_RE = re.compile(r"condition=%?([\w.\-]+)")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")
_GROUPS_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")
_GROUPS2_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_CDIMS_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_DIMS_NUM_RE = re.compile(r"\d+")

_ELEMENTWISE = {
    "add", "subtract", "multiply", "divide", "maximum", "minimum", "power",
    "exponential", "exponential-minus-one", "tanh", "log", "log-plus-one",
    "rsqrt", "sqrt", "negate", "abs", "floor", "ceil", "cosine", "sine",
    "logistic", "select", "compare", "and", "or", "xor", "not", "remainder",
    "clamp", "sign", "atan2", "cbrt", "round-nearest-afz",
    "round-nearest-even", "erf",
}
_REDUCE_LIKE = {"reduce", "reduce-window"}
_NO_BYTES = {
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "while", "conditional", "call", "after-all", "iota", "partition-id",
    "replica-id", "bitcast-convert",
}
_COLLECTIVES = {"all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute", "ragged-all-to-all",
                "all-gather-start", "all-reduce-start",
                "collective-permute-start", "all-to-all-start"}


def _shape_elems_bytes(shape_str: str) -> Tuple[int, int]:
    elems = 0
    byts = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        elems += n
        byts += n * _DTYPE_BYTES[dt]
    return elems, byts


@dataclasses.dataclass
class _Op:
    name: str
    shape: str
    opcode: str
    line: str
    is_root: bool = False


@dataclasses.dataclass
class _Computation:
    name: str
    ops: List[_Op]
    symbols: Dict[str, str]           # %name -> shape str
    param_order: List[str] = dataclasses.field(default_factory=list)

    def root(self) -> Optional[_Op]:
        for op in self.ops:
            if op.is_root:
                return op
        return self.ops[-1] if self.ops else None

    def effective_param_bytes(self, idx: int) -> Optional[int]:
        """Bytes actually read from parameter #idx, or None for 'all of it'.
        A parameter consumed only through dynamic-slice/gather reads just the
        sliced region — crucial for scan-stacked weights and decode caches."""
        if idx >= len(self.param_order):
            return None
        pname = self.param_order[idx]
        pat = re.compile(r"%" + re.escape(pname) + r"\b")
        total = 0
        for op in self.ops:
            if not pat.search(op.line.split(" = ", 1)[-1]):
                continue
            if op.opcode in ("dynamic-slice", "gather"):
                total += _shape_elems_bytes(op.shape)[1]
            elif op.opcode == "dynamic-update-slice":
                # reads only the region it overwrites
                total += _second_operand_bytes(op, self.symbols)
            elif op.opcode in ("bitcast", "get-tuple-element"):
                return None           # aliases the param: be conservative
            else:
                return None
        return total


def _parse(text: str) -> Dict[str, _Computation]:
    comps: Dict[str, _Computation] = {}
    cur: Optional[_Computation] = None
    for raw in text.splitlines():
        line = raw.rstrip()
        m = _COMP_RE.match(line)
        if m and (line.endswith("{") or "->" in line):
            cur = _Computation(m.group(1), [], {})
            comps[cur.name] = cur
            for pname, pshape in _PARAM_RE.findall(m.group(2)):
                cur.symbols[pname] = pshape
                cur.param_order.append(pname)
            continue
        if cur is None:
            continue
        mi = _INSTR_RE.match(line)
        if mi:
            name, shape, opcode = mi.groups()
            cur.symbols[name] = shape
            cur.ops.append(_Op(name, shape, opcode, line,
                               is_root=line.lstrip().startswith("ROOT")))
    return comps


def _dot_flops(op: _Op, sym: Dict[str, str]) -> float:
    out_elems, _ = _shape_elems_bytes(op.shape)
    # contracted size from lhs operand shape + lhs_contracting_dims
    paren = op.line.split("(", 1)[1]
    operands = _OPERAND_RE.findall(paren.split(")", 1)[0])
    c = 1
    m = _CDIMS_RE.search(op.line)
    if m and operands:
        lhs_shape = sym.get(operands[0], "")
        sm = _SHAPE_RE.search(lhs_shape)
        if sm and sm.group(2):
            dims = [int(d) for d in sm.group(2).split(",")]
            for idx in _DIMS_NUM_RE.findall(m.group(1)):
                i = int(idx)
                if i < len(dims):
                    c *= dims[i]
    return 2.0 * out_elems * c


def _operand_bytes(op: _Op, sym: Dict[str, str]) -> int:
    paren = op.line.split("(", 1)[1]
    # operands before any named attribute section
    arglist = paren.split("), ")[0]
    total = 0
    for name in _OPERAND_RE.findall(arglist):
        if name in sym:
            total += _shape_elems_bytes(sym[name])[1]
    return total


def _second_operand_bytes(op: _Op, sym: Dict[str, str]) -> int:
    paren = op.line.split("(", 1)[1]
    arglist = paren.split("), ")[0]
    names = _OPERAND_RE.findall(arglist)
    if len(names) > 1 and names[1] in sym:
        return _shape_elems_bytes(sym[names[1]])[1]
    return 0


def _fusion_bytes(op: _Op, sym: Dict[str, str], called) -> float:
    """Boundary bytes of a fusion: output + effective per-operand reads."""
    paren = op.line.split("(", 1)[1]
    arglist = paren.split("), ")[0]
    names = _OPERAND_RE.findall(arglist)
    _, out_b = _shape_elems_bytes(op.shape)
    # in-place DUS fusions: output aliases the buffer; traffic ~ update only
    if called is not None:
        r = called.root()
        if r is not None and r.opcode == "dynamic-update-slice":
            out_b = _second_operand_bytes(r, called.symbols) * 2
    total = float(out_b)
    for i, nm in enumerate(names):
        full = _shape_elems_bytes(sym.get(nm, ""))[1]
        eff = called.effective_param_bytes(i) if called is not None else None
        total += full if eff is None else min(eff, full)
    return total


def _group_size(line: str) -> int:
    m = _GROUPS_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    m = _GROUPS2_RE.search(line)
    if m:
        return int(m.group(2))
    return 2


def _collective_traffic(op: _Op, sym: Dict[str, str]) -> Tuple[str, float]:
    kind = op.opcode.replace("-start", "")
    g = _group_size(op.line)
    if op.opcode.endswith("-start"):
        # start ops return (in, out [, scratch]) tuples; take the LAST array
        shapes = _SHAPE_RE.findall(op.shape)
        arrays = [f"{dt}[{dims}]" for dt, dims in shapes if dt in _DTYPE_BYTES]
        b = _shape_elems_bytes(arrays[-1])[1] if arrays else 0
    else:
        b = _shape_elems_bytes(op.shape)[1]
    if kind == "all-gather":
        return kind, b * (g - 1) / g
    if kind == "all-reduce":
        return kind, 2 * b * (g - 1) / g
    if kind == "reduce-scatter":
        return kind, b * (g - 1)
    if kind == "all-to-all":
        return kind, b * (g - 1) / g
    return "collective-permute", float(b)


@dataclasses.dataclass
class HloCost:
    flops: float = 0.0
    bytes: float = 0.0
    collectives: Dict[str, float] = dataclasses.field(default_factory=dict)

    @property
    def coll_total(self) -> float:
        return sum(self.collectives.values())


def analyze(text: str, entry: Optional[str] = None) -> HloCost:
    comps = _parse(text)
    if entry is None:
        m = re.search(r"^ENTRY\s+%?([\w.\-]+)", text, re.M)
        entry = m.group(1) if m else next(iter(comps))

    cache: Dict[str, HloCost] = {}

    def comp_cost(name: str) -> HloCost:
        if name in cache:
            return cache[name]
        cost = HloCost(collectives={})
        cache[name] = cost                      # cycle guard
        comp = comps.get(name)
        if comp is None:
            return cost
        for op in comp.ops:
            oc = op.opcode
            if oc == "while":
                trip = 1
                mt = _TRIP_RE.search(op.line)
                if mt:
                    trip = int(mt.group(1) or mt.group(2))
                mb, mc = _BODY_RE.search(op.line), _COND_RE.search(op.line)
                for sub, mult in ((mb, trip), (mc, trip)):
                    if sub:
                        c = comp_cost(sub.group(1))
                        cost.flops += c.flops * mult
                        cost.bytes += c.bytes * mult
                        for k, v in c.collectives.items():
                            cost.collectives[k] = cost.collectives.get(k, 0) + v * mult
                continue
            if oc in ("fusion", "call", "conditional"):
                called = None
                for mcall in _CALLS_RE.finditer(op.line):
                    called = comps.get(mcall.group(1))
                    c = comp_cost(mcall.group(1))
                    cost.flops += c.flops
                    # bytes inside fusions are NOT HBM traffic; boundary only
                    for k, v in c.collectives.items():
                        cost.collectives[k] = cost.collectives.get(k, 0) + v
                if oc == "fusion":
                    cost.bytes += _fusion_bytes(op, comp.symbols, called)
                continue
            if oc in _COLLECTIVES:
                kind, traffic = _collective_traffic(op, comp.symbols)
                cost.collectives[kind] = cost.collectives.get(kind, 0) + traffic
                _, ob = _shape_elems_bytes(op.shape)
                cost.bytes += ob + _operand_bytes(op, comp.symbols)
                continue
            if oc in _NO_BYTES:
                continue
            elems, ob = _shape_elems_bytes(op.shape)
            if oc == "dot":
                cost.flops += _dot_flops(op, comp.symbols)
            elif oc in _ELEMENTWISE:
                cost.flops += elems
            elif oc in _REDUCE_LIKE:
                cost.flops += _operand_bytes(op, comp.symbols) / 4.0
            if oc in ("dynamic-slice", "gather"):
                # reads only the sliced region, not the whole operand
                cost.bytes += 2 * ob
            elif oc == "dynamic-update-slice":
                # read-modify-write of the update region only
                upd = _second_operand_bytes(op, comp.symbols)
                cost.bytes += 3 * upd
            elif oc == "scatter":
                cost.bytes += 3 * _second_operand_bytes(op, comp.symbols) + ob
            else:
                cost.bytes += ob + _operand_bytes(op, comp.symbols)
        # inline-fused computations called only via calls= already handled;
        return cost

    return comp_cost(entry)
