"""Trip-count-aware HLO cost model.

``compiled.cost_analysis()`` visits each while-loop body ONCE, ignoring trip
counts (verified empirically: a scan of 8 matmuls reports the FLOPs of 1), so
it wildly undercounts scanned layer stacks.  This module re-derives the three
roofline inputs directly from the optimized HLO text:

  * FLOPs       — 2 * numel(result) * contraction for every ``dot`` (einsums
                  lower to dots; elementwise FLOPs are bandwidth-bound and
                  attributed to the memory term),
  * HBM bytes   — operands + result of every top-level (post-fusion)
                  instruction, i.e. one read per operand and one write per
                  result, the standard post-fusion traffic model,
  * collectives — operand bytes per all-gather / all-reduce / reduce-scatter /
                  all-to-all / collective-permute, split per op kind,

each multiplied by the product of enclosing while trip counts (extracted from
the loop-condition constant).  Shapes in the SPMD module are per-device
shards, so all totals are per-device.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1, "token": 0,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COMP_HDR = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s+\(")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*((?:\([^()]*\)|[\w\[\],{}]+))\s+([\w\-]+)\(")
_ATTR = re.compile(r"(\w+)=%?([\w.\-]+)")
_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")
_FREE_OPS = {
    "parameter", "constant", "get-tuple-element", "tuple", "bitcast",
    "after-all", "partition-id", "replica-id", "iota",
}


def _shape_dims(shape_str: str) -> list[tuple[str, list[int]]]:
    out = []
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt in _DTYPE_BYTES:
            out.append((dt, [int(d) for d in dims.split(",")] if dims else []))
    return out


def shape_bytes(shape_str: str) -> int:
    total = 0
    for dt, dims in _shape_dims(shape_str):
        n = 1
        for d in dims:
            n *= d
        total += n * _DTYPE_BYTES[dt]
    return total


def _numel(shape_str: str) -> int:
    n_total = 0
    for _, dims in _shape_dims(shape_str):
        n = 1
        for d in dims:
            n *= d
        n_total += n
    return n_total


@dataclasses.dataclass
class Instr:
    name: str
    shape: str
    op: str
    line: str


def parse_module(text: str) -> tuple[dict[str, list[Instr]], dict[str, dict[str, str]], str]:
    """Returns (computations, per-comp symbol tables, entry name)."""
    comps: dict[str, list[Instr]] = {}
    symtab: dict[str, dict[str, str]] = {}
    entry = ""
    cur: str | None = None
    for raw in text.splitlines():
        line = raw.rstrip()
        if cur is None:
            m = _COMP_HDR.match(line)
            if m and line.endswith("{") and "->" in line:
                cur = m.group(2)
                comps[cur] = []
                symtab[cur] = {}
                if m.group(1):
                    entry = cur
                # parameters carry shapes in the signature (balanced parens)
                lo = line.find("(")
                depth, hi = 0, -1
                for i in range(lo, len(line)):
                    if line[i] == "(":
                        depth += 1
                    elif line[i] == ")":
                        depth -= 1
                        if depth == 0:
                            hi = i
                            break
                sig = line[lo + 1 : hi] if hi > lo else ""
                # split top-level commas
                parts, d, start = [], 0, 0
                for i, c in enumerate(sig):
                    if c == "(":
                        d += 1
                    elif c == ")":
                        d -= 1
                    elif c == "," and d == 0:
                        parts.append(sig[start:i])
                        start = i + 1
                parts.append(sig[start:])
                for p in parts:
                    if ":" in p:
                        nm, sh = p.split(":", 1)
                        symtab[cur][nm.strip().lstrip("%")] = sh.strip()
                continue
        else:
            if line.strip() == "}":
                cur = None
                continue
            im = _INSTR.match(line)
            if im:
                name, shape, op = im.group(1), im.group(2), im.group(3)
                comps[cur].append(Instr(name, shape, op, line))
                symtab[cur][name] = shape
    return comps, symtab, entry


def _operands(line: str, op: str) -> list[str]:
    idx = line.find(op + "(")
    if idx < 0:
        return []
    depth = 0
    start = idx + len(op)
    buf = []
    for i in range(start, len(line)):
        c = line[i]
        if c == "(":
            depth += 1
            if depth == 1:
                continue
        elif c == ")":
            depth -= 1
            if depth == 0:
                buf.append(line[start + 1 : i])
                break
    if not buf:
        return []
    return re.findall(r"%([\w.\-]+)", buf[0])


def _attr(line: str, key: str) -> str | None:
    m = re.search(rf"{key}=%?([\w.\-]+)", line)
    return m.group(1) if m else None


def _trip_count(cond_comp: list[Instr]) -> int:
    """Max integer constant in the loop condition (counter starts at 0)."""
    best = 1
    for ins in cond_comp:
        for m in re.finditer(r"constant\((\d+)\)", ins.line):
            best = max(best, int(m.group(1)))
    return best


def _dot_flops(ins: Instr, syms: dict[str, str]) -> float:
    ops = _operands(ins.line, ins.op)
    if not ops:
        return 0.0
    lhs_shape = syms.get(ops[0])
    m = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", ins.line)
    if lhs_shape is None or m is None:
        # fallback: assume contraction == last dim of result's sibling
        return 2.0 * _numel(ins.shape)
    dims = _shape_dims(lhs_shape)
    if not dims:
        return 0.0
    lhs_dims = dims[0][1]
    contract = 1
    for d in (m.group(1).split(",") if m.group(1) else []):
        di = int(d)
        if di < len(lhs_dims):
            contract *= lhs_dims[di]
    return 2.0 * _numel(ins.shape) * contract


def _fusion_operand_bytes(ins: Instr, syms: dict[str, str], callee: str | None,
                          comps: dict[str, list[Instr]]) -> int:
    """Operand bytes of a fusion, charging dynamic-slice'd params at slice size.

    The scan weight-gather pattern (`dynamic-slice(stacked_params, i)`) would
    otherwise be charged the FULL stacked array per loop iteration — a
    ~n_groups x overcount of weight traffic.
    """
    ops = _operands(ins.line, ins.op)
    if not callee or callee not in comps:
        b = 0
        for o in ops:
            s = syms.get(o)
            if s:
                b += shape_bytes(s)
        return b
    # map parameter index -> bytes actually read (slice size if the only
    # consumer is a dynamic-slice)
    body = comps[callee]
    param_read: dict[int, int] = {}
    param_names: dict[str, int] = {}
    for bi in body:
        if bi.op == "parameter":
            m = re.search(r"parameter\((\d+)\)", bi.line)
            if m:
                param_names[bi.name] = int(m.group(1))
    consumers: dict[str, list[Instr]] = {}
    for bi in body:
        for o in _operands(bi.line, bi.op):
            consumers.setdefault(o, []).append(bi)
    for pname, pidx in param_names.items():
        cons = consumers.get(pname, [])
        if cons and all(c.op == "dynamic-slice" for c in cons):
            param_read[pidx] = sum(shape_bytes(c.shape) for c in cons)
    b = 0
    for i, o in enumerate(ops):
        if i in param_read:
            b += param_read[i]
        else:
            s = syms.get(o)
            if s:
                b += shape_bytes(s)
    return b


def analyze(text: str) -> dict[str, Any]:
    comps, symtab, entry = parse_module(text)
    totals = {
        "flops": 0.0,
        "bytes": 0.0,
        "collective_bytes": {c: 0.0 for c in _COLLECTIVES},
        "collective_counts": {c: 0 for c in _COLLECTIVES},
        "collective_shapes": {},
        "bytes_by": {},
        "dot_count": 0,
        "while_trips": [],
    }

    def add_bytes(ins: Instr, n: float, mult: float) -> None:
        totals["bytes"] += mult * n
        key = f"{ins.op} {ins.shape[:70]}"
        totals["bytes_by"][key] = totals["bytes_by"].get(key, 0.0) + mult * n

    def inst_operand_bytes(ins: Instr, syms) -> int:
        b = 0
        for o in _operands(ins.line, ins.op):
            s = syms.get(o)
            if s:
                b += shape_bytes(s)
        return b

    def visit(comp_name: str, mult: float, *, in_fusion: bool) -> None:
        syms = symtab.get(comp_name, {})
        for ins in comps.get(comp_name, []):
            op = ins.op
            if op == "while":
                cond = _attr(ins.line, "condition")
                body = _attr(ins.line, "body")
                trip = _trip_count(comps.get(cond, [])) if cond else 1
                totals["while_trips"].append(trip)
                if body:
                    visit(body, mult * trip, in_fusion=False)
                continue
            if op == "conditional":
                for br in re.findall(r"(?:branch_computations=\{([^}]*)\}|true_computation=%?([\w.\-]+)|false_computation=%?([\w.\-]+))", ins.line):
                    for name in br:
                        for c in filter(None, re.findall(r"%?([\w.\-]+)", name or "")):
                            if c in comps:
                                visit(c, mult, in_fusion=False)
                continue
            if op == "fusion":
                callee = _attr(ins.line, "calls")
                if not in_fusion:
                    add_bytes(ins, _fusion_operand_bytes(ins, syms, callee, comps)
                              + shape_bytes(ins.shape), mult)
                if callee:
                    visit(callee, mult, in_fusion=True)  # count dots inside only
                continue
            if op in ("call", "async-start", "async-done"):
                callee = _attr(ins.line, "calls") or _attr(ins.line, "to_apply")
                if callee and callee in comps:
                    visit(callee, mult, in_fusion=in_fusion)
                continue
            kind = next((c for c in _COLLECTIVES if op.startswith(c)), None)
            if kind:
                ob = inst_operand_bytes(ins, syms)
                if ob == 0:
                    ob = shape_bytes(ins.shape)
                totals["collective_bytes"][kind] += mult * ob
                totals["collective_counts"][kind] += int(mult)
                key = f"{kind} {ins.shape[:60]}"
                totals["collective_shapes"][key] = totals["collective_shapes"].get(key, 0.0) + mult * ob
                if not in_fusion:
                    add_bytes(ins, ob + shape_bytes(ins.shape), mult)
                continue
            if op == "dynamic-slice":
                # reads the slice, writes the slice — not the whole operand
                if not in_fusion:
                    add_bytes(ins, 2 * shape_bytes(ins.shape), mult)
                continue
            if op == "dynamic-update-slice":
                # in-place aliased update: read+write of the update region only
                ops_ = _operands(ins.line, ins.op)
                upd = syms.get(ops_[1]) if len(ops_) > 1 else None
                if not in_fusion:
                    add_bytes(ins, 2 * (shape_bytes(upd) if upd else shape_bytes(ins.shape)), mult)
                continue
            if op in ("dot", "convolution"):
                totals["flops"] += mult * _dot_flops(ins, syms)
                totals["dot_count"] += 1
                if not in_fusion:
                    add_bytes(ins, inst_operand_bytes(ins, syms) + shape_bytes(ins.shape), mult)
                continue
            if op == "custom-call" and ("matmul" in ins.line or "dot" in ins.line.lower()):
                totals["flops"] += mult * 2.0 * _numel(ins.shape) * 1  # unknown k
            if op in _FREE_OPS:
                continue
            if not in_fusion:
                add_bytes(ins, inst_operand_bytes(ins, syms) + shape_bytes(ins.shape), mult)

    visit(entry, 1.0, in_fusion=False)
    totals["collective_total_bytes"] = sum(totals["collective_bytes"].values())
    return totals
