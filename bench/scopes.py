"""Device time by the program's named scopes, read from the traced run's
own profile.

The program names its layers with ``jax.named_scope`` (``group_fwd_bwd``,
``aggregate``, ``weiszfeld``, ...).  A scope lands in the ``op_name`` path
of each HLO instruction made under it, and the profiler writes the compiled
module of every program alive while it ran (an ``HloProto``) into the
``/host:metadata`` plane of its ``.xplane.pb``.  The device ops of the trace
are named after their instruction (``%fusion.12 = bf16[...] fusion(...)``),
so a scope's device time is the union of the ops whose instruction carries
it (``Trace.op_seconds``): a ``while`` and the ops of its body overlap and
count once.  A loop's iterations are the runs of its body's ops.

``jax.profiler.ProfileData`` does not show the metadata plane, so the
protobuf is read here field by field (``XSpace`` of ``tsl/profiler``,
``HloProto`` of ``xla/service/hlo.proto``).

Nothing here imports the program.
"""

from __future__ import annotations

import dataclasses
import functools
import glob
import os

from bench import harness

# the program's scopes: the group step's and the round runner's layers,
# and the stages inside aggregation
PROGRAM_SCOPES = ("group_fwd_bwd", "worker_grads", "attack", "aggregate",
                  "optimizer", "step_metrics", "encode", "decode",
                  "batch_means", "trim", "weiszfeld", "round_kernel")
METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"


def _varint(b: bytes, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return out, i


def fields(b: bytes):
    """``(field number, value)`` of a protobuf message: an int for a varint,
    bytes for a length-delimited or fixed-width field."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(b, i)
        elif wire == 1:
            value, i = b[i:i + 8], i + 8
        elif wire == 5:
            value, i = b[i:i + 4], i + 4
        elif wire == 2:
            size, i = _varint(b, i)
            value, i = b[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield number, value


def _ints(value) -> list[int]:
    """A repeated int64 field's entry: one varint, or a packed run."""
    if isinstance(value, int):
        return [value]
    out, i = [], 0
    while i < len(value):
        v, i = _varint(value, i)
        out.append(v)
    return out


@dataclasses.dataclass
class Module:
    """One compiled module: its instructions' names, ``scopes`` mapping
    instruction to ``op_name`` path (where it has one), and ``loops`` each
    ``while`` to the instructions of its body."""
    instructions: set
    scopes: dict
    loops: dict


def _instruction(proto: bytes) -> tuple[str, str, str, list]:
    """``(name, opcode, op_name path, called computation ids)`` of a
    serialised ``HloInstructionProto``."""
    name, opcode, path, called = "", "", "", []
    for f, v in fields(proto):
        if f == 1:
            name = v.decode()
        elif f == 2:
            opcode = v.decode()
        elif f == 7:
            path = next((x.decode() for g, x in fields(v) if g == 2), "")
        elif f == 38:
            called += _ints(v)
    return name, opcode, path, called


def hlo_module(hlo_proto: bytes) -> Module:
    """A ``Module`` from a serialised ``HloProto``."""
    module = next((v for f, v in fields(hlo_proto) if f == 1), b"")
    scopes, bodies, whiles = {}, {}, {}
    for comp in (v for f, v in fields(module) if f == 3):
        cid, names = None, []
        for f, v in fields(comp):
            if f == 5:
                cid = v
            elif f == 2:
                name, opcode, path, called = _instruction(v)
                names.append(name)
                if path:
                    scopes[name] = path
                if opcode == "while" and called:
                    whiles[name] = called[0]     # the body comes first
        bodies[cid] = names
    loops = {w: bodies.get(body, []) for w, body in whiles.items()}
    return Module(instructions={n for b in bodies.values() for n in b},
                  scopes=scopes, loops=loops)


def hlo_modules(xspace: bytes) -> list[Module]:
    """The compiled modules an ``.xplane.pb`` keeps in its metadata plane."""
    out = []
    for f, plane in fields(xspace):
        if f != 1:
            continue
        parts = {}
        for g, v in fields(plane):
            parts.setdefault(g, []).append(v)
        if parts.get(2, [b""])[0].decode() != METADATA_PLANE:
            continue
        stat_ids = set()
        for entry in parts.get(5, []):
            meta = dict(fields(dict(fields(entry)).get(2, b"")))
            if meta.get(2, b"").decode() == HLO_PROTO_STAT:
                stat_ids.add(meta.get(1, 0))
        for entry in parts.get(4, []):
            meta = dict(fields(entry)).get(2, b"")
            for g, stat in fields(meta):
                s = dict(fields(stat)) if g == 5 else {}
                if s.get(1, 0) in stat_ids and 6 in s:
                    out.append(hlo_module(s[6]))
    return out


def names_scopes(scopes: dict) -> bool:
    """Whether a module names the program's layers: some instruction
    carries one of ``PROGRAM_SCOPES``."""
    wanted = set(PROGRAM_SCOPES)
    return any(wanted.intersection(path.split("/"))
               for path in scopes.values())


@dataclasses.dataclass
class Program:
    """The modules that name the program's scopes, merged, and ``shared``:
    the names their instructions share with the other modules, whose ops
    the trace cannot tell from theirs."""
    scopes: dict
    loops: dict
    shared: set


def program(modules: list) -> Program | None:
    """The modules that name scopes, merged; None where none does (a
    program before its layers were named)."""
    named = [m for m in modules if names_scopes(m.scopes)]
    if not named:
        return None
    prog = Program(scopes={}, loops={}, shared=set())
    for m in named:
        prog.scopes.update(m.scopes)
        prog.loops.update(m.loops)
        prog.shared.update(n for o in modules if o not in named
                           for n in m.instructions & o.instructions)
    return prog


def trace_file(workload: str) -> str | None:
    """The ``.xplane.pb`` of the cell's traced run, where the harness keeps
    it (``harness.traced``)."""
    found = sorted(glob.glob(os.path.join(harness.TRACE_DIR, workload, "**",
                                          "*.xplane.pb"), recursive=True))
    return found[-1] if found else None


@functools.lru_cache(maxsize=1)
def _program_of_file(path: str, mtime: float) -> Program | None:
    with open(path, "rb") as f:
        return program(hlo_modules(f.read()))


def of_reading(r) -> Program | None:
    """The program of a metric's reading: from the cell's trace file, read
    once for all of its metrics."""
    path = trace_file(r.cell.get("name", ""))
    if path is None:
        return None
    return _program_of_file(path, os.path.getmtime(path))


def instruction(event_name: str) -> str:
    """The instruction a trace's op event is named after: the name without
    its ``%`` and without what follows it (`` = shape opcode(...)``)."""
    return event_name.lstrip("%").split(" ", 1)[0].split("=", 1)[0]


def in_scope(path: str, scope: str) -> bool:
    return scope in path.split("/")


def scope_seconds(trace, prog: Program | None, scope: str) -> float | None:
    """Device seconds of the ops made under ``scope``, inside the window,
    averaged over the chips: 0 where the compiler left no instruction of
    the scope (a mean over one group), None without a program that names
    scopes."""
    if prog is None:
        return None
    names = {n for n, path in prog.scopes.items() if in_scope(path, scope)}
    return trace.op_seconds(lambda e: instruction(e) in names)


def loop_iterations(trace, prog: Program | None,
                    scope: str) -> float | None:
    """Iterations the loops under ``scope`` ran in the window, averaged over
    the chips: for each such ``while`` that is not in the body of another,
    the runs of the most frequent op of its body, among the ops that no
    other module names.  None without a program that names scopes."""
    if prog is None:
        return None
    loops = {w: body for w, body in prog.loops.items()
             if in_scope(prog.scopes.get(w, ""), scope)}
    inner = {n for body in loops.values() for n in body}
    lo, hi = trace.window()
    runs = {}
    for ops in trace.ops.values():
        for name, s, _ in ops:
            if lo <= s < hi:
                inst = instruction(name)
                runs[inst] = runs.get(inst, 0) + 1
    total = sum(max((runs.get(n, 0) for n in body if n not in prog.shared),
                    default=0)
                for w, body in loops.items() if w not in inner)
    return total / len(trace.ops)


def busy_share(r, scope: str) -> float | None:
    """A scope's device time over the device's busy time, in percent."""
    t = scope_seconds(r.trace, of_reading(r), scope)
    if t is None:
        return None
    return 100.0 * t / r.trace.busy_s()
