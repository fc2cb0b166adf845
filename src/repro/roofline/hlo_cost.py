"""While-aware, fusion-aware cost model over optimized HLO text.

XLA's HloCostAnalysis counts a while-loop body ONCE regardless of trip
count (verified empirically — a 16-iteration scan reports the same FLOPs
as a 1-iteration scan). Every model here scans over layers, so both
FLOPs and collective bytes would be undercounted by ~n_layers. This
module parses the post-optimization HLO text and computes:

  * flops   — dot/convolution/elementwise, with while bodies multiplied
              by their statically-derived trip count and fusion ops
              attributed the cost of their called computation;
  * bytes   — memory traffic at fusion boundaries only (operands+result
              of executed ops; ops inside fusion computations are not
              double-counted);
  * collectives — per-op operand/result/wire bytes, trip-count-expanded.

Shapes are post-SPMD (per-device), so every number is per device.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s4": 1, "u4": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1, "f8e5m2fnuz": 1,
}

_ELEMWISE = {
    "add", "subtract", "multiply", "divide", "power", "maximum", "minimum",
    "exponential", "exponential-minus-one", "log", "log-plus-one", "tanh",
    "logistic", "rsqrt", "sqrt", "cbrt", "sine", "cosine", "negate", "abs",
    "sign", "floor", "ceil", "round-nearest-afz", "round-nearest-even",
    "atan2", "remainder", "erf",
}
_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute", "ragged-all-to-all")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COMP_HDR_RE = re.compile(r"^(?:ENTRY\s+)?(%?[\w.\-]+)\s+\(.*\)\s*->")
_TRIP_RE = re.compile(r'known_trip_count[^}]*"n":"(\d+)"')
_CALLS_RE = re.compile(r"calls=(%?[\w.\-]+)")
_COND_RE = re.compile(r"condition=(%?[\w.\-]+)")
_BODY_RE = re.compile(r"body=(%?[\w.\-]+)")
_CONST_RE = re.compile(r"constant\((\d+)\)")
_DIMS_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_OPERANDS_RE = re.compile(r"(%?[\w.\-]+)")


def _parse_shape(type_str: str) -> List[Tuple[str, Tuple[int, ...]]]:
    """'(f32[2,3], bf16[4])' -> [('f32', (2,3)), ('bf16', (4,))]."""
    out = []
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in DTYPE_BYTES:
            continue
        shape = tuple(int(d) for d in dims.split(",") if d)
        out.append((dt, shape))
    return out


def _nbytes(type_str: str) -> int:
    total = 0
    for dt, shape in _parse_shape(type_str):
        n = 1
        for d in shape:
            n *= d
        total += n * DTYPE_BYTES[dt]
    return total


def _nelems(type_str: str) -> int:
    total = 0
    for _, shape in _parse_shape(type_str):
        n = 1
        for d in shape:
            n *= d
        total += n
    return total


@dataclass
class Instr:
    name: str
    type_str: str
    opcode: str
    rest: str          # operand list + attrs


@dataclass
class Computation:
    name: str
    instrs: List[Instr] = field(default_factory=list)
    symbols: Dict[str, str] = field(default_factory=dict)   # name -> type


@dataclass
class CostTotals:
    flops: float = 0.0
    bytes: float = 0.0
    cast_bytes: float = 0.0      # CPU-backend bf16<->f32 cast artifacts,
    #                              excluded from the roofline memory term
    coll_counts: Dict[str, float] = field(default_factory=dict)
    coll_operand: Dict[str, float] = field(default_factory=dict)
    coll_result: Dict[str, float] = field(default_factory=dict)
    coll_wire: Dict[str, float] = field(default_factory=dict)

    def add(self, other: "CostTotals", mult: float = 1.0):
        self.flops += other.flops * mult
        self.bytes += other.bytes * mult
        self.cast_bytes += other.cast_bytes * mult
        for d_self, d_o in ((self.coll_counts, other.coll_counts),
                            (self.coll_operand, other.coll_operand),
                            (self.coll_result, other.coll_result),
                            (self.coll_wire, other.coll_wire)):
            for k, v in d_o.items():
                d_self[k] = d_self.get(k, 0.0) + v * mult

    @property
    def total_wire(self) -> float:
        return sum(self.coll_wire.values())

    def to_dict(self):
        return {"flops": self.flops, "bytes": self.bytes,
                "cast_bytes": self.cast_bytes,
                "coll_counts": self.coll_counts,
                "coll_operand": self.coll_operand,
                "coll_result": self.coll_result,
                "coll_wire": self.coll_wire,
                "total_wire": self.total_wire}


_OPCODE_RE = re.compile(r"([\w\-]+)\(")


def _parse_instr(line: str) -> Optional[Instr]:
    line = line.strip()
    if line.startswith("ROOT "):
        line = line[5:]
    if not line.startswith("%"):
        return None
    eq = line.find(" = ")
    if eq < 0:
        return None
    name = line[:eq].lstrip("%")
    rest = line[eq + 3:]
    if rest.startswith("("):                      # tuple type
        depth = 0
        i = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        type_str, rem = rest[:i + 1], rest[i + 1:]
    else:
        sp = rest.find(" ")
        if sp < 0:
            return None
        type_str, rem = rest[:sp], rest[sp:]
    m = _OPCODE_RE.match(rem.strip())
    if not m:
        return None
    opcode = m.group(1)
    return Instr(name, type_str, opcode, rem.strip()[m.end():])


def parse_module(text: str) -> Dict[str, Computation]:
    comps: Dict[str, Computation] = {}
    cur: Optional[Computation] = None
    for raw in text.splitlines():
        line = raw.rstrip()
        if not line:
            continue
        if not line.startswith(" ") and "{" in line:
            m = _COMP_HDR_RE.match(line.strip())
            if m:
                cur = Computation(m.group(1).lstrip("%"))
                comps[cur.name] = cur
                continue
        if line.strip() == "}":
            continue
        if cur is None:
            continue
        instr = _parse_instr(line)
        if instr is None:
            continue
        cur.instrs.append(instr)
        cur.symbols[instr.name] = instr.type_str
    return comps


def _trip_count(cond: Computation) -> int:
    """Largest constant compared against in the condition (scan bound)."""
    best = 0
    for ins in cond.instrs:
        if ins.opcode == "constant":
            m = re.match(r"(\d+)", ins.rest)
            if m:
                best = max(best, int(m.group(1)))
        m = re.search(r"constant\((\d+)\)", ins.rest)
        if m:
            best = max(best, int(m.group(1)))
    return best if best > 0 else 1


def _dot_flops(ins: Instr, comp: Computation) -> float:
    res_elems = _nelems(ins.type_str)
    m = _DIMS_RE.search(ins.rest)
    k = 1
    if m:
        dims = [int(d) for d in m.group(1).split(",") if d]
        ops = _OPERANDS_RE.findall(ins.rest.split(")")[0])
        lhs = next((o.lstrip("%") for o in ops if o.lstrip("%") in comp.symbols),
                   None)
        if lhs is not None:
            shapes = _parse_shape(comp.symbols[lhs])
            if shapes:
                shape = shapes[0][1]
                for d in dims:
                    if d < len(shape):
                        k *= shape[d]
    return 2.0 * res_elems * k


def _conv_flops(ins: Instr, comp: Computation) -> float:
    # approximation: 2 * out_elems * prod(kernel dims != batch/feature)
    res_elems = _nelems(ins.type_str)
    ops = _OPERANDS_RE.findall(ins.rest.split(")")[0])
    named = [o.lstrip("%") for o in ops if o.lstrip("%") in comp.symbols]
    if len(named) >= 2:
        ksh = _parse_shape(comp.symbols[named[1]])
        if ksh:
            n = 1
            for d in ksh[0][1]:
                n *= d
            # divide by output feature dim to get per-output-element work
            out_feat = max(_parse_shape(ins.type_str)[0][1][-1], 1) \
                if _parse_shape(ins.type_str) else 1
            return 2.0 * res_elems * max(n // max(out_feat, 1), 1)
    return 2.0 * res_elems


def _group_size(rest: str, default: int) -> int:
    m = re.search(r"replica_groups=\{\{([\d,]+)\}", rest)
    if m:
        return len(m.group(1).split(","))
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]", rest)
    if m:
        return int(m.group(2))
    return default


class HloCost:
    def __init__(self, text: str, default_group: int = 1):
        self.comps = parse_module(text)
        self.default_group = default_group
        self._memo: Dict[str, CostTotals] = {}
        self._inplace_memo: Dict[str, bool] = {}
        entry = None
        for name, c in self.comps.items():
            if name.endswith("main") or name.startswith("main") or entry is None:
                if entry is None or "main" in name:
                    entry = name
        self.entry = entry

    def total(self) -> CostTotals:
        return self._comp_cost(self.entry)

    def _fusion_alias(self, comp_name: str) -> Optional[str]:
        """'write' for DUS/scatter-rooted fusions (in-place update),
        'read' for fusions that dynamic-slice a big buffer, else None."""
        comp_name = comp_name.lstrip("%")
        if comp_name in self._inplace_memo:
            return self._inplace_memo[comp_name]
        comp = self.comps.get(comp_name)
        out = None
        if comp and comp.instrs:
            if any(i.opcode in ("dynamic-update-slice", "scatter")
                   for i in comp.instrs):
                out = "write"
            elif any(i.opcode in ("dynamic-slice", "gather", "slice")
                     for i in comp.instrs):
                out = "read"
        self._inplace_memo[comp_name] = out
        return out

    _CAST_ONLY = {"parameter", "constant", "convert", "bitcast", "copy",
                  "tuple", "get-tuple-element"}

    def _cast_only(self, comp_name: str) -> bool:
        """True if the fused computation is pure dtype-cast/copy plumbing
        (XLA:CPU upcasts bf16 dot operands to f32 and copies loop carries;
        a TPU with donated bf16 buffers would not)."""
        comp = self.comps.get(comp_name.lstrip("%"))
        if comp is None or not comp.instrs:
            return False
        return all(i.opcode in self._CAST_ONLY for i in comp.instrs)

    def _comp_cost(self, name: str) -> CostTotals:
        name = name.lstrip("%")
        if name in self._memo:
            return self._memo[name]
        comp = self.comps.get(name)
        tot = CostTotals()
        self._memo[name] = tot
        if comp is None:
            return tot
        for ins in comp.instrs:
            op = ins.opcode
            base = op.replace("-start", "")
            if op.endswith("-done"):
                continue
            if base in _COLLECTIVES:
                opnd_t = _operand_bytes(ins, comp)
                res = _nbytes(ins.type_str)
                n = _group_size(ins.rest, self.default_group)
                if base == "all-gather":
                    opnd = opnd_t if opnd_t else res // max(n, 1)
                    wire = max(res - opnd, 0)
                elif base == "all-reduce":
                    opnd = opnd_t if opnd_t else res
                    wire = 2 * opnd * (n - 1) / max(n, 1)
                elif base == "reduce-scatter":
                    opnd = opnd_t if opnd_t else res * n
                    wire = max(opnd - res, 0)
                elif base in ("all-to-all", "ragged-all-to-all"):
                    opnd = opnd_t if opnd_t else res
                    wire = opnd * (n - 1) / max(n, 1)
                else:  # collective-permute
                    opnd = opnd_t if opnd_t else res
                    wire = opnd
                tot.coll_counts[base] = tot.coll_counts.get(base, 0) + 1
                tot.coll_operand[base] = tot.coll_operand.get(base, 0) + opnd
                tot.coll_result[base] = tot.coll_result.get(base, 0) + res
                tot.coll_wire[base] = tot.coll_wire.get(base, 0) + wire
                tot.bytes += res + (opnd or res)
                continue
            if op == "while":
                body = _BODY_RE.search(ins.rest)
                tm = _TRIP_RE.search(ins.rest)
                if tm:
                    trips = int(tm.group(1))
                else:
                    cond = _COND_RE.search(ins.rest)
                    trips = 1
                    if cond:
                        ccomp = self.comps.get(cond.group(1).lstrip("%"))
                        if ccomp:
                            trips = _trip_count(ccomp)
                if body:
                    tot.add(self._comp_cost(body.group(1)), mult=trips)
                continue
            if op in ("fusion", "call", "custom-call", "async-start"):
                m = _CALLS_RE.search(ins.rest)
                alias = self._fusion_alias(m.group(1)) if (
                    op == "fusion" and m) else None
                if m:
                    sub = self._comp_cost(m.group(1))
                    # fusion: inner flops count, inner bytes do NOT
                    tot.flops += sub.flops
                    for k, v in sub.coll_wire.items():
                        tot.coll_wire[k] = tot.coll_wire.get(k, 0) + v
                    for k, v in sub.coll_counts.items():
                        tot.coll_counts[k] = tot.coll_counts.get(k, 0) + v
                    for k, v in sub.coll_operand.items():
                        tot.coll_operand[k] = tot.coll_operand.get(k, 0) + v
                    for k, v in sub.coll_result.items():
                        tot.coll_result[k] = tot.coll_result.get(k, 0) + v
                res_b = _nbytes(ins.type_str)
                opnd_b = _operand_bytes(ins, comp)
                if alias == "write":
                    # in-place update (DUS/scatter): result aliases the big
                    # buffer; traffic is the update slice only
                    big = _max_operand_bytes(ins, comp)
                    res_b = 0
                    opnd_b = max(opnd_b - big, 0)
                elif alias == "read":
                    # dynamic-slice inside: only the slice is read
                    big = _max_operand_bytes(ins, comp)
                    opnd_b = max(opnd_b - big, 0) + res_b
                if op == "fusion" and m and self._cast_only(m.group(1)):
                    tot.cast_bytes += res_b + opnd_b
                else:
                    tot.bytes += res_b + opnd_b
                continue
            if op in ("dynamic-update-slice", "scatter"):
                big = _max_operand_bytes(ins, comp)
                tot.bytes += max(_nbytes(ins.type_str) - big, 0) \
                    + max(_operand_bytes(ins, comp) - big, 0)
                continue
            if op in ("dynamic-slice", "slice", "gather"):
                big = _max_operand_bytes(ins, comp)
                tot.bytes += _nbytes(ins.type_str) \
                    + max(_operand_bytes(ins, comp) - big, 0) \
                    + min(_nbytes(ins.type_str), big)
                continue
            if op in ("convert", "copy"):
                tot.cast_bytes += _nbytes(ins.type_str) \
                    + _operand_bytes(ins, comp)
                continue
            if op == "conditional":
                # take the max branch cost (upper bound)
                branches = re.findall(r"(?:branch_computations=\{([^}]*)\}|"
                                      r"(?:true|false)_computation=(%?[\w.\-]+))",
                                      ins.rest)
                names = []
                for a, b in branches:
                    if a:
                        names += [x.strip() for x in a.split(",")]
                    if b:
                        names.append(b)
                if names:
                    subs = [self._comp_cost(n) for n in names]
                    best = max(subs, key=lambda s: s.flops)
                    tot.add(best)
                continue
            if op == "dot":
                tot.flops += _dot_flops(ins, comp)
                tot.bytes += _nbytes(ins.type_str) + _operand_bytes(ins, comp)
                continue
            if op == "convolution":
                tot.flops += _conv_flops(ins, comp)
                tot.bytes += _nbytes(ins.type_str) + _operand_bytes(ins, comp)
                continue
            if op in _ELEMWISE:
                tot.flops += _nelems(ins.type_str)
                tot.bytes += _nbytes(ins.type_str) + _operand_bytes(ins, comp)
                continue
            if op in ("parameter", "constant", "get-tuple-element", "tuple",
                      "bitcast", "copy-start", "copy-done", "after-all",
                      "partition-id", "replica-id", "iota"):
                continue
            # remaining data-movement ops (reshape/transpose/scatter/...)
            tot.bytes += _nbytes(ins.type_str) + _operand_bytes(ins, comp)
        return tot


def _max_operand_bytes(ins: Instr, comp: Computation) -> int:
    best = 0
    oplist = ins.rest.split(")")[0]
    for name in _OPERANDS_RE.findall(oplist):
        t = comp.symbols.get(name.lstrip("%"))
        if t:
            best = max(best, _nbytes(t))
    return best


def _operand_bytes(ins: Instr, comp: Computation) -> int:
    total = 0
    oplist = ins.rest.split(")")[0]
    for name in _OPERANDS_RE.findall(oplist):
        t = comp.symbols.get(name.lstrip("%"))
        if t:
            total += _nbytes(t)
    return total


def analyze(text: str, default_group: int = 1) -> CostTotals:
    return HloCost(text, default_group).total()


def xla_cost_analysis(compiled) -> dict:
    """``Compiled.cost_analysis()`` as a (possibly empty) dict."""
    cost = compiled.cost_analysis()
    return dict(cost) if cost is not None else {}
