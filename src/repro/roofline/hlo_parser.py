"""Structural HLO cost model with loop trip-count correction.

XLA's ``compiled.cost_analysis()`` counts each ``while`` body **once**,
regardless of trip count — useless for scan-over-layers models (an 80-layer
model reports ~1 layer of FLOPs).  This parser walks the post-partitioning
per-device HLO text and accumulates:

  * ``dot_flops``          — 2 × |result| × |contracted dims| per dot op
  * ``collective_bytes``   — result bytes of all-gather / all-reduce /
                             reduce-scatter / all-to-all / collective-permute
  * ``bytes_accessed``     — operand-read + result-write bytes of every
                             materializing instruction (fusion internals are
                             registers and excluded; aliasing ops excluded)

each multiplied by the product of enclosing while-loop trip counts.  Trip
counts are read from the loop condition computation (the largest s32
constant compared against the induction variable — an upper bound for
early-exit loops like Weiszfeld, which is the conservative direction).

Known approximations (documented in EXPERIMENTS.md §Roofline):
  * elementwise FLOPs are ignored (dot-dominated workloads);
  * ``bytes_accessed`` assumes every instruction result materializes in HBM
    once per execution — XLA may keep small results in registers/cache, so
    this is an upper bound on HBM traffic;
  * dynamic trip counts use their static upper bound.
"""

from __future__ import annotations

import dataclasses
import math
import re

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16, "token": 0,
    "u4": 1, "s4": 1,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"\b(" + "|".join(_DTYPE_BYTES) + r")\[([0-9,]*)\]")
_COMP_HEADER_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w\.\-]+)\s*\(")
_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%([\w\.\-]+)\s*=\s*(.*)$")
_OP_RE = re.compile(r"^((?:\([^)]*\)|[\w\[\],{}$ ])*?)\s*([\w\-]+)\(")
_OPERAND_RE = re.compile(r"%([\w\.\-]+)")
_CONST_RE = re.compile(r"\bs32\[\]\s+constant\((\d+)\)")
_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")
_CALL_RE = re.compile(r"(?:calls|body|condition|to_apply)=%?([\w\.\-]+)")

_ALIAS_OPS = {"parameter", "constant", "tuple", "get-tuple-element",
              "bitcast", "after-all", "partition-id", "replica-id",
              "custom-call"}  # custom-call bytes unknowable; usually tiny here


def _shape_elems_bytes(text: str):
    elems = 0
    nbytes = 0
    for dtype, dims in _SHAPE_RE.findall(text):
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        elems += n
        nbytes += n * _DTYPE_BYTES[dtype]
    return elems, nbytes


@dataclasses.dataclass
class Instr:
    name: str
    op: str
    result_text: str
    rest: str            # text after the op's opening paren (full tail)
    operands: list[str]


@dataclasses.dataclass
class Computation:
    name: str
    instrs: list[Instr]
    shapes: dict          # instr name -> result_text


def parse_computations(hlo: str) -> dict[str, Computation]:
    comps: dict[str, Computation] = {}
    current: Computation | None = None
    for raw in hlo.splitlines():
        line = raw.rstrip()
        if not line:
            continue
        if not line.startswith(" ") and ("->" in line) and line.endswith("{"):
            m = _COMP_HEADER_RE.match(line.strip())
            if m:
                current = Computation(name=m.group(1), instrs=[], shapes={})
                comps[current.name] = current
            continue
        if line.strip() == "}":
            continue
        if current is None:
            continue
        im = _INSTR_RE.match(line)
        if not im:
            continue
        name, body = im.group(1), im.group(2)
        om = _OP_RE.match(body)
        if not om:
            continue
        result_text, op = om.group(1), om.group(2)
        tail = body[om.end():]
        # operands live in the first balanced paren group
        depth, end = 1, len(tail)
        for i, ch in enumerate(tail):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    end = i
                    break
        operand_text = tail[:end]
        operands = _OPERAND_RE.findall(operand_text)
        ins = Instr(name=name, op=op, result_text=result_text,
                    rest=tail, operands=operands)
        current.instrs.append(ins)
        current.shapes[name] = result_text
    return comps


def _trip_count(cond: Computation) -> int:
    best = 1
    for ins in cond.instrs:
        for m in _CONST_RE.finditer(ins.result_text + " " + ins.rest):
            best = max(best, int(m.group(1)))
        if ins.op == "constant":
            m = re.search(r"s32\[\]", ins.result_text)
            c = re.search(r"constant\((\d+)\)", "constant(" + ins.rest)
            if m and c:
                best = max(best, int(c.group(1)))
    return best


def _dot_flops(ins: Instr, shapes: dict) -> float:
    _, _ = shapes, None
    res_elems, _ = _shape_elems_bytes(ins.result_text)
    cm = _CONTRACT_RE.search(ins.rest)
    if cm is None:
        return 2.0 * res_elems   # degenerate
    dims = [int(d) for d in cm.group(1).split(",") if d]
    lhs = ins.operands[0] if ins.operands else None
    lhs_shape_text = shapes.get(lhs, "")
    m = _SHAPE_RE.search(lhs_shape_text)
    contracted = 1
    if m and m.group(2):
        sizes = [int(d) for d in m.group(2).split(",")]
        for d in dims:
            if d < len(sizes):
                contracted *= sizes[d]
    return 2.0 * res_elems * contracted


@dataclasses.dataclass
class HloCost:
    dot_flops: float = 0.0
    bytes_accessed: float = 0.0
    collective_bytes: float = 0.0
    collective_breakdown: dict = dataclasses.field(
        default_factory=lambda: {op: 0.0 for op in _COLLECTIVES})
    max_trip_product: float = 1.0

    def add(self, other: "HloCost"):
        self.dot_flops += other.dot_flops
        self.bytes_accessed += other.bytes_accessed
        self.collective_bytes += other.collective_bytes
        for k, v in other.collective_breakdown.items():
            self.collective_breakdown[k] += v
        self.max_trip_product = max(self.max_trip_product,
                                    other.max_trip_product)


def _walk(comp: Computation, comps: dict, mult: float, cost: HloCost,
          in_fusion: bool, memo_shapes_cache: dict):
    cost.max_trip_product = max(cost.max_trip_product, mult)
    for ins in comp.instrs:
        op = ins.op
        base = op.replace("-start", "")
        if base in _COLLECTIVES and not op.endswith("-done"):
            _, nbytes = _shape_elems_bytes(ins.result_text)
            cost.collective_bytes += nbytes * mult
            cost.collective_breakdown[base] += nbytes * mult
        if op == "dot":
            cost.dot_flops += _dot_flops(ins, comp.shapes) * mult
        if op == "while":
            cm = _CALL_RE.findall(ins.rest)
            body_name = cond_name = None
            bm = re.search(r"body=%?([\w\.\-]+)", ins.rest)
            cm2 = re.search(r"condition=%?([\w\.\-]+)", ins.rest)
            if bm:
                body_name = bm.group(1)
            if cm2:
                cond_name = cm2.group(1)
            trips = _trip_count(comps[cond_name]) if cond_name in comps else 1
            if body_name in comps:
                _walk(comps[body_name], comps, mult * trips, cost,
                      in_fusion, memo_shapes_cache)
            continue
        if op in ("fusion", "call", "reduce", "sort", "scatter", "map",
                  "reduce-window", "select-and-scatter", "conditional"):
            for cname in _CALL_RE.findall(ins.rest):
                if cname in comps and cname != comp.name:
                    _walk(comps[cname], comps, mult, cost,
                          True, memo_shapes_cache)
        if not in_fusion and op not in _ALIAS_OPS and op != "while":
            if op == "dynamic-update-slice":
                # in-place on TPU: traffic = the update slice, not the buffer
                upd = ins.operands[1] if len(ins.operands) > 1 else None
                st = comp.shapes.get(upd)
                b = _shape_elems_bytes(st)[1] if st else 0
                cost.bytes_accessed += 2 * b * mult
                continue
            _, wbytes = _shape_elems_bytes(ins.result_text)
            rbytes = 0
            for o in ins.operands:
                st = comp.shapes.get(o)
                if st is not None:
                    _, b = _shape_elems_bytes(st)
                    rbytes += b
            cost.bytes_accessed += (wbytes + rbytes) * mult


def analyze(hlo_text: str, entry: str | None = None) -> HloCost:
    comps = parse_computations(hlo_text)
    if entry is None:
        # ENTRY computation: marked in header text
        m = re.search(r"^ENTRY\s+%?([\w\.\-]+)", hlo_text, re.M)
        entry = m.group(1) if m else next(iter(comps))
    cost = HloCost()
    _walk(comps[entry], comps, 1.0, cost, False, {})
    return cost



_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
_HEADER_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w\.\-]+)\s")


def _opcode(rhs: str) -> str:
    """The opcode of an instruction's right-hand side: what follows its
    result shape (a tuple shape is one balanced parenthesis group)."""
    i = 0
    if rhs.startswith("("):
        depth = 0
        for i, ch in enumerate(rhs):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
    rest = rhs[i:].split(" ", 1)[1] if " " in rhs[i:] else ""
    return rest.split("(", 1)[0]


def op_names(hlo_text: str) -> list[tuple[str, str, str | None]]:
    """``(instruction, opcode, op_name path or None)`` for every instruction
    that runs as an op of its own: those of the entry computation and of
    loop bodies and conditions, not the insides of fusions nor reducer or
    comparator computations.  The path is the ``jax.named_scope`` stack
    of the traced operation the instruction came from.  Reads CPU and TPU
    compiled text alike (layouts, memory spaces)."""
    inner = set(re.findall(r"(?:calls|to_apply)=%?([\w\.\-]+)", hlo_text))
    out, comp = [], None
    for line in hlo_text.splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            m = _HEADER_RE.match(line)
            comp = m.group(1) if m else None
            continue
        im = _INSTR_RE.match(line)
        if im is None or comp is None or comp in inner:
            continue
        m = _OP_NAME_RE.search(im.group(2))
        out.append((im.group(1), _opcode(im.group(2)),
                    m.group(1) if m else None))
    return out
