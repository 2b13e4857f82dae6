"""Loop-aware post-SPMD HLO analysis for the three-term roofline.

Why not ``compiled.cost_analysis()`` alone?  XLA's cost analysis visits
each instruction ONCE — a ``lax.scan`` over 80 layers reports 1/80th of the
real FLOPs (verified empirically in this repo).  And it reports no
collective traffic at all.  This module re-derives all three roofline
inputs from ``compiled.as_text()`` with while-loop trip-count weighting:

  * dot_flops        2 * result_elems * contraction_size per dot,
                     weighted by enclosing loop trip counts.
  * bytes_accessed   operand+result bytes of every top-level instruction
                     in non-fusion computations (fusion internals touch no
                     HBM; the fusion call site is what counts), weighted.
  * collectives      per-kind ring wire bytes per chip, weighted:
                         all-reduce        2(n-1)/n * result
                         all-gather        (n-1)/n  * result
                         reduce-scatter    (n-1)    * result
                         all-to-all        (n-1)/n  * result
                         collective-permute            result

Trip counts come from each while's condition computation (max scalar-int
compare constant — exact for lax.scan-lowered loops).  Shapes in
``compiled.as_text()`` are per-partition, so everything here is per-chip.
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "f64": 8, "s64": 8, "u64": 8, "c64": 8, "c128": 16,
    "f32": 4, "s32": 4, "u32": 4,
    "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "s4": 1, "u4": 1,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1, "f8e5m2fnuz": 1, "f8e4m3fnuz": 1,
}

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")

_SHAPE_RE = re.compile(r"\b(" + "|".join(_DTYPE_BYTES) + r")\[([\d,]*)\]")
_COMP_RE = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w\.\-_]+)\s*\(.*\)\s*->\s*.+\{\s*$")
_NAME_RE = re.compile(r"^\s*(?:ROOT\s+)?%([\w\.\-_]+)\s*=\s*(.*)$")
_OPCODE_RE = re.compile(r"\s*([\w\-]+)\(")
_WHILE_RE = re.compile(r"condition=%?([\w\.\-_]+),\s*body=%?([\w\.\-_]+)")
_TRIP_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')
_CALLS_RE = re.compile(r"(?:calls|to_apply|condition|body)=%?([\w\.\-_]+)")
_CONST_RE = re.compile(r"[su]\d+\[\]\s*constant\((\d+)\)")
_IOTA_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_EXPL_GROUPS_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")
_OPERAND_RE = re.compile(r"%([\w\.\-_]+)")
_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")

# instructions that move no HBM data
_FREE_OPS = {"parameter", "tuple", "get-tuple-element", "bitcast", "constant",
             "after-all", "partition-id", "replica-id", "iota", "reshape",
             # control flow: carried state is aliased, not copied — counting
             # the full carry x trip-count would overcount by O(layers)
             "while", "conditional", "call"}

# ops that touch only the sliced/updated REGION of their big operand
# (XLA aliases the buffer): count 2x the touched bytes, not the operand.
#   dynamic-slice / gather: touched = result
#   dynamic-update-slice: touched = the update operand (index 1)
#   scatter: touched = the updates operand (index 2)
_REGION_OPS = {"dynamic-slice": None, "gather": None,
               "dynamic-update-slice": 1, "scatter": 2}


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def _shapes_in(text: str) -> List[Tuple[str, str]]:
    return _SHAPE_RE.findall(text)


def _group_size(line: str) -> int:
    m = _IOTA_GROUPS_RE.search(line)
    if m:
        return max(int(m.group(2)), 1)
    m = _EXPL_GROUPS_RE.search(line)
    if m:
        return max(len(m.group(1).split(",")), 1)
    return 1


def _wire_bytes(kind: str, result_bytes: float, n: int) -> float:
    if kind == "collective-permute":
        return float(result_bytes)
    if n <= 1:
        return 0.0
    if kind == "all-reduce":
        return 2.0 * (n - 1) / n * result_bytes
    if kind == "all-gather":
        return (n - 1) / n * result_bytes
    if kind == "reduce-scatter":
        return float(n - 1) * result_bytes
    return (n - 1) / n * result_bytes      # all-to-all


class _Instr:
    __slots__ = ("name", "opcode", "result_shapes", "operands", "line")

    def __init__(self, name, opcode, result_shapes, operands, line):
        self.name = name
        self.opcode = opcode
        self.result_shapes = result_shapes      # [(dtype, dims_str), ...]
        self.operands = operands                # [%names]
        self.line = line


def _split_instr(rhs: str):
    """rhs = everything after '%name = '.  Returns (result_txt, opcode,
    operand_txt, attrs) or None.  Handles tuple results containing
    '/*index=N*/' comments by matching the tuple's closing paren."""
    rhs = rhs.lstrip()
    if rhs.startswith("("):
        depth = 0
        end = -1
        for i, ch in enumerate(rhs):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    end = i
                    break
        if end < 0:
            return None
        result_txt, rest = rhs[: end + 1], rhs[end + 1:]
    else:
        sp = rhs.find(" ")
        if sp < 0:
            return None
        result_txt, rest = rhs[:sp], rhs[sp:]
    om = _OPCODE_RE.match(rest)
    if not om:
        return None
    opcode = om.group(1)
    call = rest[om.end():]
    depth = 1
    end = len(call)
    for i, ch in enumerate(call):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                end = i
                break
    return result_txt, opcode, call[:end], call[end:]


def _parse(hlo_text: str):
    comps: Dict[str, List[_Instr]] = defaultdict(list)
    calls: Dict[str, set] = defaultdict(set)
    fusion_children: set = set()
    while_edges: List[Tuple[str, str]] = []     # (cond, body)
    trip_counts: Dict[str, int] = {}            # body -> known trip count
    max_const: Dict[str, int] = defaultdict(int)
    comp = "__toplevel__"
    for line in hlo_text.splitlines():
        h = _COMP_RE.match(line)
        if h and "{" in line and "=" not in line.split("(")[0]:
            comp = h.group(1)
            continue
        for m in _CONST_RE.finditer(line):
            max_const[comp] = max(max_const[comp], int(m.group(1)))
        nm = _NAME_RE.match(line)
        if not nm:
            continue
        parts = _split_instr(nm.group(2))
        if parts is None:
            continue
        result_txt, opcode, operand_txt, attrs = parts
        operands = _OPERAND_RE.findall(operand_txt)
        comps[comp].append(_Instr(nm.group(1), opcode, _shapes_in(result_txt),
                                  operands, line))
        if opcode == "while":
            w = _WHILE_RE.search(attrs)
            if w:
                while_edges.append((w.group(1), w.group(2)))
                t = _TRIP_RE.search(attrs)
                if t:
                    trip_counts[w.group(2)] = int(t.group(1))
        for cm in _CALLS_RE.finditer(attrs):
            calls[comp].add(cm.group(1))
            if opcode == "fusion" or "to_apply" in attrs:
                fusion_children.add(cm.group(1))
    return comps, calls, fusion_children, while_edges, max_const, trip_counts


def _trip_count(cond: str, calls, max_const) -> int:
    """Fallback when backend_config lacks known_trip_count: max scalar-int
    constant over the condition computation's transitive call closure."""
    seen, stack, best = set(), [cond], 1
    while stack:
        c = stack.pop()
        if c in seen:
            continue
        seen.add(c)
        best = max(best, max_const.get(c, 1))
        stack.extend(calls.get(c, ()))
    return best


def _multiplicities(comps, calls, while_edges, max_const,
                    trip_counts) -> Dict[str, float]:
    body_trip = {body: trip_counts.get(body) or
                 _trip_count(cond, calls, max_const)
                 for cond, body in while_edges}
    mult: Dict[str, float] = defaultdict(float)
    called = set()
    for cs in calls.values():
        called |= cs
    entries = [c for c in comps if c not in called] or ["__toplevel__"]
    for e in entries:
        mult[e] = 1.0
    for _ in range(64):                         # nesting depth bound
        changed = False
        for parent, children in calls.items():
            if mult[parent] <= 0:
                continue
            for ch in children:
                m = mult[parent] * body_trip.get(ch, 1)
                if m > mult[ch]:
                    mult[ch] = m
                    changed = True
        if not changed:
            break
    return mult


def analyze(hlo_text: str) -> Dict:
    """Full loop-aware analysis of optimized HLO text (see module doc)."""
    comps, calls, fusion_children, while_edges, max_const, trips = _parse(hlo_text)
    mult = _multiplicities(comps, calls, while_edges, max_const, trips)

    # symbol table: instruction name -> result shapes (for dot operands)
    symtab: Dict[str, List[Tuple[str, str]]] = {}
    for instrs in comps.values():
        for ins in instrs:
            symtab[ins.name] = ins.result_shapes

    dot_flops = 0.0
    bytes_accessed = 0.0
    coll: Dict[str, Dict[str, float]] = defaultdict(
        lambda: dict(result_bytes=0.0, wire_bytes=0.0, count=0.0, max_group=1))

    body_trips = {body: trips.get(body) or _trip_count(cond, calls, max_const)
                  for cond, body in while_edges}

    def _trip_adjusted(shapes, trip: int) -> int:
        """Scan-over-layers pattern: a tensor whose LEADING dim equals the
        enclosing while's trip count is per-iteration-sliced/updated
        (stacked weights, stacked KV caches) — one iteration touches
        1/trip of it, and XLA aliases the buffer in place."""
        total = 0
        for d, dims in shapes:
            b = _shape_bytes(d, dims)
            if trip > 1 and dims:
                lead = int(dims.split(",")[0])
                if lead == trip:
                    b //= trip
            total += b
        return total

    def operand_bytes(op_name: str, trip: int) -> int:
        return _trip_adjusted(symtab.get(op_name, ()), trip)

    for comp, instrs in comps.items():
        if comp in fusion_children:
            continue                       # fusion internals touch no HBM
        m = max(mult.get(comp, 1.0), 1.0)
        trip = body_trips.get(comp, 1)
        for ins in instrs:
            rbytes = _trip_adjusted(ins.result_shapes, trip)
            if ins.opcode in _REGION_OPS:
                opnd_idx = _REGION_OPS[ins.opcode]
                if opnd_idx is None:
                    touched = rbytes
                else:
                    touched = 0
                    if opnd_idx < len(ins.operands):
                        touched = operand_bytes(ins.operands[opnd_idx], trip)
                bytes_accessed += m * 2 * touched
            elif ins.opcode not in _FREE_OPS:
                obytes = sum(operand_bytes(op, trip) for op in ins.operands)
                bytes_accessed += m * (rbytes + obytes)
            if ins.opcode == "dot":
                cm = _CONTRACT_RE.search(ins.line)
                contract = 1
                if cm and ins.operands:
                    lhs_shapes = symtab.get(ins.operands[0], ())
                    if lhs_shapes:
                        dims = lhs_shapes[0][1].split(",") if lhs_shapes[0][1] else []
                        for idx in (cm.group(1).split(",") if cm.group(1) else []):
                            i = int(idx)
                            if i < len(dims):
                                contract *= int(dims[i])
                relems = 1
                if ins.result_shapes:
                    d0 = ins.result_shapes[0][1]
                    for d in (d0.split(",") if d0 else []):
                        relems *= int(d)
                dot_flops += m * 2.0 * relems * contract
            elif ins.opcode in ("convolution",):
                # rare in this codebase (vision smoke only); approximate via
                # result elems * operand-1 elems / spatial — skip, warn big
                pass
            op_base = ins.opcode.replace("-start", "")
            if op_base in COLLECTIVES and not ins.opcode.endswith("-done"):
                n = _group_size(ins.line)
                rec = coll[op_base]
                rec["result_bytes"] += m * rbytes
                rec["wire_bytes"] += m * _wire_bytes(op_base, rbytes, n)
                rec["count"] += m
                rec["max_group"] = max(rec["max_group"], n)

    return dict(dot_flops=dot_flops, bytes_accessed=bytes_accessed,
                collectives=dict(coll))


def collective_bytes(hlo_text: str) -> Dict[str, Dict[str, float]]:
    """Back-compat wrapper: just the collectives part of analyze()."""
    return analyze(hlo_text)["collectives"]


def xla_cost_analysis(compiled) -> Dict:
    """Drift-tolerant ``compiled.cost_analysis()``.

    Across jax versions ``cost_analysis()`` has returned a plain dict, a
    per-device LIST of dicts, or ``None`` — the raw call un-crashed three
    separate callers before they learned to normalize it, each
    with its own copy of the fix.  This is the one shared shim: always a
    plain dict (device 0's entry on list-returning versions, ``{}`` when
    the analysis is absent).  Remember its numbers are loop-NAIVE (see
    module doc) — use :func:`analyze` for roofline inputs; this exists for
    cross-checks and MACs-style accounting.  The
    ``raw-cost-analysis`` lint rule rejects bare call sites outside this
    module."""
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    return dict(cost or {})


def collectives_report(compiled_or_text) -> Dict:
    """Per-step collective wire bytes of a compiled executable.

    Accepts a ``jax`` compiled object (anything with ``as_text()`` — the
    result of ``jit(f).lower(...).compile()``) or raw optimized-HLO text,
    and returns::

        {"per_kind": {kind: {result_bytes, wire_bytes, count, max_group}},
         "total_wire_bytes": float,       # sum over kinds, per chip
         "count": float}                  # total collective launches

    Wire bytes are ring-corrected per chip (see module doc), so
    ``total_wire_bytes / link_bandwidth`` is the step's collective
    time bound.  This is the same walk the dry-run records and the
    shard_map-vs-GSPMD wire-bytes regression guard assert on; the
    two-level DP engine's tests (tests/test_multihost.py) use it to
    account the cross-host gradient-reduction traffic per train step."""
    text = compiled_or_text if isinstance(compiled_or_text, str) \
        else compiled_or_text.as_text()
    per_kind = collective_bytes(text)
    return dict(
        per_kind=per_kind,
        total_wire_bytes=sum(r["wire_bytes"] for r in per_kind.values()),
        count=sum(r["count"] for r in per_kind.values()),
    )


def total_collective_seconds(per_kind: Dict[str, Dict[str, float]],
                             link_bw: float) -> float:
    """Wire bytes are already ring-corrected; just divide by link bandwidth."""
    return sum(rec["wire_bytes"] for rec in per_kind.values()) / link_bw
