"""Trip-count-weighted cost analysis of optimized HLO text.

XLA's built-in ``compiled.cost_analysis()`` counts every ``while`` body
exactly once — for scan-over-layers / microbatch-accumulation programs
that under-reports FLOPs, bytes and collectives by the product of trip
counts (~350x for a 64-layer, 16-microbatch train step).  Post-
optimization HLO carries ``backend_config={"known_trip_count":{"n":..}}``
on while ops, so an exact weighting is recoverable from the text.

This module parses the module into computations, walks the call graph
from ENTRY multiplying by trip counts, and accumulates:

  * flops            — 2 * prod(lhs_shape) * prod(rhs_free) per dot
                       (plus convolutions), weighted by trips;
  * coll_wire_bytes  — per-chip wire traffic per collective kind, using
                       the same ring-cost model as roofline.py;
  * hbm_bytes        — HBM traffic proxy: every walked instruction
                       contributes its result bytes (one write) plus its
                       operand bytes (one read per consumer).  Fusion
                       internals are excluded (they live in registers /
                       VMEM); fusion parameters/results are the buffer
                       edges that actually hit memory.

Everything is per-chip: the post-SPMD module is the per-partition
program.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
    "f64": 8, "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

_SHAPE_RE = re.compile(r"(pred|bf16|f16|f32|f64|s4|u4|s8|u8|s16|u16|s32|u32|s64|u64|c64|c128|f8e4m3fn|f8e5m2)\[([0-9,]*)\]")
_INSTR_RE = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OPCODE_RE = re.compile(r"([a-z][\w\-]*)\(")
_CALLED_RE = re.compile(r"(?:calls|body|to_apply)=%?([\w.\-]+)")
_COND_BRANCHES_RE = re.compile(r"branch_computations=\{([^}]*)\}")
_TRUE_FALSE_RE = re.compile(r"(?:true_computation|false_computation)=%?([\w.\-]+)")
_TRIP_RE = re.compile(r'known_trip_count[^0-9]*(\d+)')
_OPERANDS_RE = re.compile(r"\(([^()]*(?:\([^()]*\)[^()]*)*)\)")
_GROUPS_RE = re.compile(r"replica_groups=\{\{([0-9,]+)\}")
_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=")
_CDIMS_RE = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")
_BDIMS_RE = re.compile(r"lhs_batch_dims=\{([0-9,]*)\}")
_RCDIMS_RE = re.compile(r"rhs_contracting_dims=\{([0-9,]*)\}")
_RBDIMS_RE = re.compile(r"rhs_batch_dims=\{([0-9,]*)\}")

_SKIP_BYTES = {
    "parameter", "get-tuple-element", "tuple", "bitcast", "constant",
    "iota", "after-all", "partition-id", "replica-id",
}

COLLECTIVES = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute",
)


def _shape_elems(dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n


def _first_shapes_bytes(text: str) -> int:
    return sum(
        _shape_elems(dims) * _DTYPE_BYTES[dt] for dt, dims in _SHAPE_RE.findall(text)
    )


@dataclass
class Instr:
    name: str
    opcode: str
    result_text: str
    operands: list[str]
    line: str
    is_root: bool = False
    param_idx: int = -1


@dataclass
class Computation:
    name: str
    instrs: list[Instr] = field(default_factory=list)
    table: dict = field(default_factory=dict)  # var -> result_text


def parse_module(hlo: str) -> dict[str, Computation]:
    comps: dict[str, Computation] = {}
    cur: Computation | None = None
    for raw in hlo.splitlines():
        line = raw.rstrip()
        s = line.strip()
        if not s or s.startswith("//"):
            continue
        if s.startswith(("HloModule",)):
            continue
        if (s.startswith("%") or s.startswith("ENTRY")) and s.endswith("{"):
            m = re.match(r"(?:ENTRY\s+)?%?([\w.\-]+)", s)
            cur = Computation(m.group(1))
            comps[cur.name] = cur
            if s.startswith("ENTRY"):
                comps["__entry__"] = cur
            continue
        if s == "}" or s.startswith("}"):
            continue
        if cur is None:
            continue
        m = _INSTR_RE.match(line)
        if not m:
            continue
        rhs = m.group(3)
        op_m = _OPCODE_RE.search(rhs)
        if not op_m:
            continue
        opcode = op_m.group(1)
        result_text = rhs[: op_m.start()]
        # operands: first (...) group after the opcode
        rest = rhs[op_m.end() - 1 :]
        ops_m = _OPERANDS_RE.match(rest)
        # XLA prints operands as "%name" ("add(%a, %b)")
        operands = re.findall(r"%([\w.\-]+)", ops_m.group(1)) if ops_m else []
        name = m.group(2)
        instr = Instr(name, opcode, result_text, operands, s, is_root=bool(m.group(1)))
        if opcode == "parameter":
            pm = re.search(r"parameter\((\d+)\)", s)
            if pm:
                instr.param_idx = int(pm.group(1))
        cur.instrs.append(instr)
        cur.table[name] = result_text
    return comps


@dataclass
class Costs:
    flops: float = 0.0
    hbm_bytes: float = 0.0
    coll_wire_bytes: float = 0.0
    coll_by_kind: dict = field(default_factory=dict)
    n_collectives: int = 0

    def add_coll(self, kind: str, b: float, mult: float):
        self.coll_wire_bytes += b * mult
        self.coll_by_kind[kind] = self.coll_by_kind.get(kind, 0.0) + b * mult
        self.n_collectives += 1


def _dot_flops(instr: Instr, comp: Computation) -> float:
    if not instr.operands:
        return 0.0
    lhs = comp.table.get(instr.operands[0], "")
    rhs = comp.table.get(instr.operands[1], "") if len(instr.operands) > 1 else ""
    lhs_shapes = _SHAPE_RE.findall(lhs)
    rhs_shapes = _SHAPE_RE.findall(rhs)
    if not lhs_shapes or not rhs_shapes:
        return 0.0
    lhs_dims = [int(d) for d in lhs_shapes[0][1].split(",") if d]
    rhs_dims = [int(d) for d in rhs_shapes[0][1].split(",") if d]
    cd = {int(x) for x in _RCDIMS_RE.search(instr.line).group(1).split(",") if x} if _RCDIMS_RE.search(instr.line) else set()
    bd = {int(x) for x in _RBDIMS_RE.search(instr.line).group(1).split(",") if x} if _RBDIMS_RE.search(instr.line) else set()
    lhs_total = 1
    for d in lhs_dims:
        lhs_total *= d
    rhs_free = 1
    for i, d in enumerate(rhs_dims):
        if i not in cd and i not in bd:
            rhs_free *= d
    return 2.0 * lhs_total * rhs_free


def _conv_flops(instr: Instr, comp: Computation) -> float:
    # 2 * output_elems * (kernel spatial x in_channels) — approximate via
    # operand/result shapes: flops = 2 * out_elems * prod(kernel)/out_feat
    if len(instr.operands) < 2:
        return 0.0
    ker = comp.table.get(instr.operands[1], "")
    ker_shapes = _SHAPE_RE.findall(ker)
    if not ker_shapes:
        return 0.0
    ker_elems = _shape_elems(ker_shapes[0][1])
    out_shapes = _SHAPE_RE.findall(instr.result_text)
    out_elems = _shape_elems(out_shapes[0][1]) if out_shapes else 0
    # assume last kernel dim is out-features
    ker_dims = [int(d) for d in ker_shapes[0][1].split(",") if d]
    out_feat = ker_dims[-1] if ker_dims else 1
    return 2.0 * out_elems * (ker_elems / max(out_feat, 1))


def _fusion_bytes(ins: Instr, comp: Computation, fc: Computation | None) -> float:
    """HBM traffic of one fusion: result write + operand reads, with
    window-access repair — an operand whose only internal consumers are
    (dynamic-)slice/gather ops is read only through those windows, and a
    root dynamic-update-slice writes only its update window (the rest of
    the buffer aliases in place)."""
    result_b = _first_shapes_bytes(ins.result_text)
    if fc is None:
        return result_b + sum(
            _first_shapes_bytes(comp.table.get(o, "")) for o in ins.operands
        )
    params = {i.param_idx: i.name for i in fc.instrs if i.opcode == "parameter"}
    consumers: dict[str, list[Instr]] = {}
    for fi in fc.instrs:
        for o in fi.operands:
            consumers.setdefault(o, []).append(fi)
    root = next((i for i in fc.instrs if i.is_root), None)

    total = 0.0
    for idx, oname in enumerate(ins.operands):
        full = _first_shapes_bytes(comp.table.get(oname, ""))
        pname = params.get(idx)
        cons = consumers.get(pname, []) if pname else []
        if not cons:
            total += full
            continue
        # per-consumer window accounting: (dynamic-)slice/gather reads only
        # its window; a root dynamic-update-slice destination aliases in
        # place (loop-carried caches) and costs nothing beyond the update
        # write; any other consumer reads the full buffer.
        acc = 0.0
        for c in cons:
            if c.opcode in ("dynamic-slice", "slice", "gather"):
                acc += _first_shapes_bytes(c.result_text)
            elif (
                c is root
                and root.opcode == "dynamic-update-slice"
                and root.operands
                and root.operands[0] == pname
            ):
                pass
            else:
                acc = full
                break
        total += min(acc, full)
    if root is not None and root.opcode == "dynamic-update-slice" and len(root.operands) > 1:
        total += _first_shapes_bytes(fc.table.get(root.operands[1], ""))
    else:
        total += result_b
    return total


def _group_size(line: str, default: int) -> int:
    m = _IOTA_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return default


def _walk(comps, name: str, mult: float, costs: Costs, n_devices: int, flops_only: bool):
    comp = comps.get(name)
    if comp is None:
        return
    for ins in comp.instrs:
        op = ins.opcode
        if op == "while":
            trips = 1
            m = _TRIP_RE.search(ins.line)
            if m:
                trips = int(m.group(1))
            body = None
            bm = re.search(r"body=%?([\w.\-]+)", ins.line)
            if bm:
                body = bm.group(1)
            if body:
                _walk(comps, body, mult * trips, costs, n_devices, flops_only)
            continue
        if op == "conditional":
            branches = _COND_BRANCHES_RE.search(ins.line)
            names = []
            if branches:
                names = [b.strip().lstrip("%") for b in branches.group(1).split(",")]
            else:
                names = _TRUE_FALSE_RE.findall(ins.line)
            for b in names:
                _walk(comps, b, mult, costs, n_devices, flops_only)
            continue
        if op in ("call", "async-start"):
            m = _CALLED_RE.search(ins.line)
            if m:
                _walk(comps, m.group(1), mult, costs, n_devices, flops_only)

        if op == "fusion":
            m = _CALLED_RE.search(ins.line)
            if m:
                # fusion internals: flops only (buffers stay on-chip)
                _walk(comps, m.group(1), mult, costs, n_devices, True)
        elif op == "dot":
            costs.flops += _dot_flops(ins, comp) * mult
        elif op == "convolution":
            costs.flops += _conv_flops(ins, comp) * mult

        is_coll = None
        for c in COLLECTIVES:
            if op == c or op == c + "-start":
                is_coll = c
                break
        if is_coll and not flops_only:
            rb = _first_shapes_bytes(ins.result_text)
            g = _group_size(ins.line, n_devices)
            frac = (g - 1) / g if g > 1 else 0.0
            if is_coll == "all-reduce":
                costs.add_coll(is_coll, 2.0 * rb * frac, mult)
            elif is_coll == "all-gather":
                costs.add_coll(is_coll, rb * frac, mult)
            elif is_coll == "reduce-scatter":
                costs.add_coll(is_coll, rb * (g - 1), mult)
            elif is_coll == "all-to-all":
                costs.add_coll(is_coll, rb * frac, mult)
            else:
                costs.add_coll(is_coll, float(rb), mult)

        if not flops_only and op not in _SKIP_BYTES and not op.endswith("-done"):
            rb = _first_shapes_bytes(ins.result_text)
            if op == "fusion":
                m = _CALLED_RE.search(ins.line)
                fc = comps.get(m.group(1)) if m else None
                costs.hbm_bytes += _fusion_bytes(ins, comp, fc) * mult
            elif op == "dynamic-update-slice":
                # in-place: traffic = read + write of the update window only
                upd = _first_shapes_bytes(comp.table.get(ins.operands[1], "")) if len(ins.operands) > 1 else 0
                costs.hbm_bytes += 2.0 * upd * mult
            elif op in ("dynamic-slice", "slice", "gather", "broadcast", "reshape",
                        "transpose", "copy", "reverse", "concatenate", "pad"):
                # data-movement ops: read + write of the (smaller) result
                costs.hbm_bytes += 2.0 * rb * mult
            elif op == "scatter":
                upd = _first_shapes_bytes(comp.table.get(ins.operands[-1], "")) if ins.operands else 0
                costs.hbm_bytes += (2.0 * upd + rb * 0) * mult
            else:
                ob = sum(
                    _first_shapes_bytes(comp.table.get(o, "")) for o in ins.operands
                )
                costs.hbm_bytes += (rb + ob) * mult


def analyze(hlo_text: str, n_devices: int) -> Costs:
    comps = parse_module(hlo_text)
    costs = Costs()
    entry = comps.get("__entry__")
    if entry is None:
        return costs
    _walk(comps, entry.name, 1.0, costs, n_devices, False)
    return costs
