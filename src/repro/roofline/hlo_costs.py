"""Trip-count-aware HLO cost walker.

``compiled.cost_analysis()`` counts every while-loop body ONCE (verified in
this container: a scan of 8 matmuls reports the flops of 1) — useless for a
scanned-layers training step whose inner loop runs accum×num_layers times.
The same defect hits any naive collective-bytes grep.

This walker parses the post-partitioning HLO text into a call graph
(computations, while/fusion/call/conditional edges), extracts loop trip
counts from scan-shaped conditions (`compare(iter, constant(N)), LT`), and
accumulates per-chip:

    flops             — dot/convolution, 2·prod(result)·prod(contracted)
    bytes             — Σ result bytes of top-level ops (HBM-traffic proxy:
                        fusion internals stay in registers/VMEM)
    collectives[kind] — Σ result bytes of all-reduce/all-gather/
                        reduce-scatter/all-to-all/collective-permute

Each multiplied by the product of enclosing trip counts. Dynamic-bound
loops (none in this codebase's jit graphs) fall back to ×1 and are flagged.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
    "f64": 8, "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")

_DEF_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%([\w\.\-]+)\s*=\s*([a-z0-9]+)\[([0-9,]*)\][^\s]*\s+"
    r"([a-z0-9\-]+)\(")
_TUPLE_DEF_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%([\w\.\-]+)\s*=\s*\(.*\)\s+([a-z0-9\-]+)\(")
_COMP_HDR_RE = re.compile(r"^\s*(ENTRY\s+)?%?([\w\.\-]+)\s+\(.*\)\s*->")
_CALLS_RE = re.compile(r"calls=%?([\w\.\-]+)")
_COND_BODY_RE = re.compile(r"condition=%?([\w\.\-]+),\s*body=%?([\w\.\-]+)")
_BRANCHES_RE = re.compile(r"(?:true_computation=%?([\w\.\-]+).*?"
                          r"false_computation=%?([\w\.\-]+)|"
                          r"branch_computations=\{([^}]*)\})")
_CONST_RE = re.compile(r"constant\((\d+)\)")
_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")
_OPERAND_RE = re.compile(r"%([\w\.\-]+)")
_WINDOW_SIZE_RE = re.compile(r"size=([0-9x]+)")


@dataclass
class Op:
    name: str
    kind: str
    dtype: str
    dims: Tuple[int, ...]
    line: str


@dataclass
class Computation:
    name: str
    is_entry: bool = False
    ops: List[Op] = field(default_factory=list)
    shapes: Dict[str, Tuple[str, Tuple[int, ...]]] = field(default_factory=dict)


def _parse_dims(s: str) -> Tuple[int, ...]:
    return tuple(int(d) for d in s.split(",") if d) if s else ()


def parse_module(text: str) -> Dict[str, Computation]:
    comps: Dict[str, Computation] = {}
    cur: Optional[Computation] = None
    for line in text.splitlines():
        hdr = _COMP_HDR_RE.match(line)
        if hdr and "{" in line and "=" not in line.split("(")[0]:
            cur = Computation(hdr.group(2), is_entry=bool(hdr.group(1)))
            comps[cur.name] = cur
            continue
        if cur is None:
            continue
        if line.strip() == "}":
            cur = None
            continue
        m = _DEF_RE.match(line)
        if m:
            name, dtype, dims_s, kind = m.groups()
            dims = _parse_dims(dims_s)
            cur.shapes[name] = (dtype, dims)
            cur.ops.append(Op(name, kind, dtype, dims, line.strip()))
            continue
        mt = _TUPLE_DEF_RE.match(line)
        if mt:
            name, kind = mt.groups()
            # tuple-shaped op (while/fusion returning tuples): record shapes
            # of tuple elements for byte counting of collectives if needed
            cur.shapes[name] = ("tuple", ())
            cur.ops.append(Op(name, kind, "tuple", (), line.strip()))
        # parameters: "%p = f32[...] parameter(0)" matched by _DEF_RE above
    return comps


def _prod(dims) -> int:
    n = 1
    for d in dims:
        n *= d
    return n


def _dot_flops(op: Op, comp: Computation) -> float:
    mc = _CONTRACT_RE.search(op.line)
    inside = op.line[op.line.index("(") + 1:]
    operands = _OPERAND_RE.findall(inside.split(")")[0])
    lhs = comp.shapes.get(operands[0]) if operands else None
    contracted = 1
    if mc and lhs:
        for d in _parse_dims(mc.group(1)):
            if d < len(lhs[1]):
                contracted *= lhs[1][d]
    return 2.0 * _prod(op.dims) * contracted


def _conv_flops(op: Op, comp: Computation) -> float:
    inside = op.line[op.line.index("(") + 1:]
    operands = _OPERAND_RE.findall(inside.split(")")[0])
    if len(operands) < 2:
        return 0.0
    rhs = comp.shapes.get(operands[1])
    if rhs is None:
        return 0.0
    # kernel: spatial dims × input features (HWIO-ish); output features is
    # in the result shape, so multiply result elements by prod(kernel)/O
    kdims = _prod(rhs[1])
    ofeat = rhs[1][-1] if rhs[1] else 1
    per_out = kdims / max(ofeat, 1)
    return 2.0 * _prod(op.dims) * per_out


def _trip_count(cond: Computation) -> Tuple[float, bool]:
    consts = [int(c) for op in cond.ops for c in _CONST_RE.findall(op.line)]
    big = [c for c in consts if c > 0]
    if big:
        return float(max(big)), True
    return 1.0, False


def _op_bytes(op: Op) -> float:
    return float(_prod(op.dims)) * _DTYPE_BYTES.get(op.dtype, 4)


def _collective_payload_bytes(op: Op, comp: Computation,
                              comps: Dict[str, Computation]) -> float:
    """Wire bytes of a collective, seeing through the CPU backend's
    promotion pass: XLA-CPU cannot reduce/gather bf16/int8, so it emits
    convert-up → collective(f32) → convert-down. On the TPU target the
    payload stays narrow. If the collective's operand is produced by a
    convert (or a fusion whose same-shaped parameter is narrower), count
    the narrow dtype; genuinely-f32 payloads are unaffected (their
    producers' same-shape inputs are f32 too)."""
    result = _op_bytes(op)
    inside = op.line[op.line.index("(") + 1:]
    operands = _OPERAND_RE.findall(inside.split(")")[0])
    if not operands:
        return result
    src = next((o for o in comp.ops if o.name == operands[0]), None)
    if src is None:
        return result
    width = _DTYPE_BYTES.get(op.dtype, 4)
    narrow = width
    if src.kind == "convert":
        ins = _OPERAND_RE.findall(src.line[src.line.index("(") + 1:])
        if ins and ins[0] in comp.shapes:
            narrow = _DTYPE_BYTES.get(comp.shapes[ins[0]][0], width)
    elif src.kind == "fusion":
        m = _CALLS_RE.search(src.line)
        body = comps.get(m.group(1)) if m else None
        if body is not None:
            n_elem = _prod(src.dims)
            # (a) a same-sized parameter that is already narrow
            for o in body.ops:
                if o.kind == "parameter" and o.dims != () and \
                        _prod(o.dims) == n_elem:
                    narrow = min(narrow, _DTYPE_BYTES.get(o.dtype, width))
            # (b) a narrow→wide convert round-trip feeding the result (the
            # promotion pass materializes convert(bf16→f32) right before
            # the wire) — the convert INPUT dtype is the true payload
            for o in body.ops:
                if o.kind != "convert" or _prod(o.dims) != n_elem:
                    continue
                ins = _OPERAND_RE.findall(o.line[o.line.index("(") + 1:])
                if ins and ins[0] in body.shapes:
                    w_in = _DTYPE_BYTES.get(body.shapes[ins[0]][0], width)
                    if w_in < _DTYPE_BYTES.get(o.dtype, width):
                        narrow = min(narrow, w_in)
    if narrow < width:
        return result * narrow / width
    return result


class Walker:
    def __init__(self, comps: Dict[str, Computation]):
        self.comps = comps
        self.memo: Dict[str, Dict] = {}
        self.dynamic_loops = 0
        # computations called as fusion bodies: their op "bytes" are
        # register/VMEM-internal, skip byte counting there
        self.fusion_bodies = set()
        for c in comps.values():
            for op in c.ops:
                if op.kind == "fusion":
                    m = _CALLS_RE.search(op.line)
                    if m:
                        self.fusion_bodies.add(m.group(1))

    def costs(self, name: str) -> Dict:
        if name in self.memo:
            return self.memo[name]
        comp = self.comps.get(name)
        if comp is None:
            return {"flops": 0.0, "bytes": 0.0, "coll": {}}
        total = {"flops": 0.0, "bytes": 0.0, "coll": {}}
        in_fusion = name in self.fusion_bodies
        for op in comp.ops:
            if op.kind == "dot":
                total["flops"] += _dot_flops(op, comp)
            elif op.kind == "convolution":
                total["flops"] += _conv_flops(op, comp)
            if not in_fusion and op.kind not in ("parameter", "constant",
                                                 "get-tuple-element", "tuple"):
                total["bytes"] += _op_bytes(op)
            if op.kind in COLLECTIVES or any(
                    op.kind == k + "-start" for k in COLLECTIVES):
                kind = op.kind.replace("-start", "")
                total["coll"][kind] = total["coll"].get(kind, 0.0) \
                    + _collective_payload_bytes(op, comp, self.comps)
            if op.kind == "while":
                m = _COND_BODY_RE.search(op.line)
                if m:
                    cond_name, body_name = m.groups()
                    trips, static = _trip_count(self.comps.get(
                        cond_name, Computation(cond_name)))
                    if not static:
                        self.dynamic_loops += 1
                    self._add(total, self.costs(body_name), trips)
                    self._add(total, self.costs(cond_name), trips)
            elif op.kind in ("fusion", "call", "custom-call", "map",
                             "reduce", "reduce-window", "sort", "scatter"):
                m = _CALLS_RE.search(op.line)
                if m:
                    self._add(total, self.costs(m.group(1)), 1.0)
            elif op.kind == "conditional":
                m = _BRANCHES_RE.search(op.line)
                if m:
                    branches = [b for b in (m.group(1), m.group(2)) if b]
                    if m.group(3):
                        branches = _OPERAND_RE.findall(m.group(3)) or \
                            [s.strip().lstrip("%") for s in
                             m.group(3).split(",")]
                    if branches:
                        subs = [self.costs(b) for b in branches]
                        worst = max(subs, key=lambda s: s["flops"] + s["bytes"])
                        self._add(total, worst, 1.0)
        self.memo[name] = total
        return total

    @staticmethod
    def _add(total: Dict, sub: Dict, mult: float):
        total["flops"] += sub["flops"] * mult
        total["bytes"] += sub["bytes"] * mult
        for k, v in sub["coll"].items():
            total["coll"][k] = total["coll"].get(k, 0.0) + v * mult


def module_costs(hlo_text: str) -> Dict:
    """Per-chip {flops, bytes, collectives{kind: bytes}, dynamic_loops}."""
    comps = parse_module(hlo_text)
    entry = next((c.name for c in comps.values() if c.is_entry), None)
    if entry is None:  # fall back: biggest computation
        entry = max(comps, key=lambda n: len(comps[n].ops)) if comps else None
    w = Walker(comps)
    out = w.costs(entry) if entry else {"flops": 0.0, "bytes": 0.0, "coll": {}}
    coll = dict(out["coll"])
    coll["total"] = sum(coll.values())
    return {"flops": out["flops"], "bytes": out["bytes"],
            "collectives": coll, "dynamic_loops": w.dynamic_loops}
