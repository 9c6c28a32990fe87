"""The trainer's own marks in the profiler trace, reduced to what the
per-layer metrics of its parts read.

The trainer names its work itself (`repro.launch.spans`): host spans
around what `TrainHarness.run_span` does, with the slots each covers as a
stat (``slots``), and device scopes (``mll.grads``, ``mll.update``,
``mll.mix.*``) that its compiled ops carry in their ``op_name`` metadata.

`build` adds to the benchmark's `traces.Trace` what the metrics of the
trainer's parts need from the same file (the newest ``*.xplane.pb`` under
`TRACE`): each device op's program and part, each program's kind
(local scan, event step, dense step: `kind_of`), and the trainer's host
spans with their stats (``lo``/``hi`` or ``slots``).  The XLA Ops events
of a TPU v5e trace carry no ``op_name``, so an op's part and a program's
kind come from the HLO that the trace holds for the program (the "Hlo
Proto" of the ``/host:metadata`` plane): a part by instruction name
(`parts`), a kind by the scopes the program holds.

An op is charged to one part.  A fusion holds the instructions XLA fused
into it, and runs once the latest of their parts can run: it is charged
to that part, in step order (`ORDER`: gradient, update, mixing).  So in
an event program the update and the gradient's last ops that XLA fuses
into the mixing count as mixing.  XLA's copies of a program's arguments
or loop results into and out of the entry computation are the loop carry
(`CARRY`); any other instruction that names no part takes the part of
its nearest operand, else user, that has one.

The reductions are plain functions of these intervals, so the tests can
hold them to hand-computed values.  Loop ops (``while``, ``conditional``,
``call``) span the ops of their body and are left out, as in
`traces.top_ops`.  A trace of a trainer that names nothing holds no
charged op and no span with stats, and every reduction reads 0.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
import sys

from traces import LOOPS

TRACE = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), ".chipbench_cache", "trace")
GRADS = "mll.grads"
UPDATE = "mll.update"
MIX = "mll.mix"
ORDER = (GRADS, UPDATE, MIX)     # the parts of a step, in the order run
CARRY = "carry"                  # a loop's carried state, copied in and out
RUN_SPAN = "run_span"
LOCAL_SCAN = "local_scan"
SKIP_IDLE = "skip_idle"
# kinds of step program (`kind_of`), named as the spans that launch them
EVENT_STEP = "event_step"
DENSE_STEP = "dense_step"
# the trainer's host spans
HOST = re.compile(r"^(run_span|draw_batch|stack_batches|local_scan|"
                  r"event_step\.\d+|dense_step|skip_idle)$")
# the spans that cover one call into a compiled step program
DISPATCH = re.compile(r"^(local_scan|event_step\.\d+|dense_step)$")
# a scope of the trainer inside an op_name path
_SCOPE = re.compile(r"(?:^|[/(])(mll\.[a-z]+(?:\.[a-z]+)?)(?=[/)]|$)")


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: float
    end: float
    stats: dict


@dataclasses.dataclass
class Marks:
    ops: dict            # device id -> [(Op, program, part or "")]
    spans: list          # [Span] host spans that carry stats
    window: tuple        # (start, end) of the benchmark's window
    programs: dict = dataclasses.field(default_factory=dict)
    #                      program -> its kind (`kind_of`), or ""


def scope_of(op_name: str) -> str:
    """The innermost trainer scope an ``op_name`` path names, or ""."""
    found = _SCOPE.findall(op_name or "")
    return found[-1] if found else ""


def _rank(part: str) -> int:
    return next((k for k, p in enumerate(ORDER)
                 if part == p or part.startswith(p + ".")), -1)


def latest(scopes) -> str:
    """Of the trainer scopes ``scopes``, the one a step runs last; "" for
    none."""
    return max(scopes, key=_rank, default="")


def kind_of(instrs, rules) -> str:
    """The kind of a step program, from the trainer scopes its
    instructions name: the first of ``rules`` ([[kind, pattern], ...],
    `names.json`'s ``programs``) whose pattern one of them matches; "" for
    none.  The entry points' XLA module names do not tell them apart: a
    dense step and a local scan both compile as ``jit__lambda``, and an
    event step's name differs between vmap and `shard_map`."""
    held = {scope_of(i.op_name) for i in instrs} - {""}
    return next((kind for kind, pattern in rules
                 if any(re.search(pattern, s) for s in held)), "")


# --------------------------------------------------------------- reading
def of(ctx):
    """The marks a reader reads: ``ctx.marks``, built from the run's
    trace (``ctx.trace``) and the program kinds of ``ctx.names`` on first
    use; None where no device op was recorded."""
    if getattr(ctx, "marks", None) is None:
        trace = getattr(ctx, "trace", None)
        ctx.marks = (build(trace, ctx.names["programs"])
                     if trace is not None and trace.ops
                     else Marks({}, [], (0, 0)))
    return ctx.marks if ctx.marks.ops else None


def newest(directory: str) -> str:
    """The newest profiler trace under ``directory`` (as `traces.load`
    picks it)."""
    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no profiler trace under {directory}")
    return paths[-1]


def build(trace, rules, directory: str = TRACE) -> Marks:
    """The marks of ``trace`` (a `traces.Trace`): its device ops, each
    with the program execution it starts in and its part, the kind of
    each program (by ``rules``, see `kind_of`), and the trainer's host
    spans, read from the trace file it was loaded from."""
    path = newest(directory)
    placed = {}
    for d, ops in trace.ops.items():
        mods = trace.modules.get(d, [])
        starts = [m.start for m in mods]
        placed[d] = [(op, mods[i].name if i >= 0 else "") for op, i in
                     ((op, bisect.bisect_right(starts, op.start) - 1)
                      for op in ops)]
    wanted = {mod for ops in placed.values() for _, mod in ops}
    with open(path, "rb") as f:
        data = f.read()
    try:
        hlo = hlo_modules(data, wanted)
    except (ValueError, IndexError) as e:
        sys.stderr.write(f"chipbench: the trace's HLO could not be read "
                         f"({e!r}); no op is charged, no program known\n")
        hlo = {}
    charged = {mod: parts(*h) for mod, h in hlo.items()}
    ops = {d: [(op, mod, charged.get(mod, {}).get(instruction(op.name), ""))
               for op, mod in ops]
           for d, ops in placed.items()}
    return Marks(ops, host_spans(path), trace.window,
                 {mod: kind_of(h[1], rules) for mod, h in hlo.items()})


def host_spans(path: str) -> list:
    """The trainer's host spans in a trace file (those with stats), in
    start order."""
    import jax
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if HOST.match(ev.name):
                    stats = dict(ev.stats)
                    if "slots" in stats or "lo" in stats:
                        out.append(Span(ev.name, ev.start_ns,
                                        ev.start_ns + ev.duration_ns, stats))
    return sorted(out, key=lambda s: (s.start, -s.end))


def instruction(op_text: str) -> str:
    """The HLO instruction name of an op's text: ``%fusion.12 = ...`` ->
    ``fusion.12``."""
    return op_text.split(" ", 1)[0].lstrip("%")


@dataclasses.dataclass(frozen=True)
class Instr:
    """What is read of one HLO instruction."""
    name: str
    op_name: str
    id: int
    operands: tuple
    opcode: str = ""
    called: tuple = ()       # ids of the computations it calls
    computation: int = 0     # id of the computation that holds it


def parts(entry: int, instrs, depth: int = 3) -> dict:
    """{instruction name: the part it is charged to, or ""} of one module
    whose entry computation has id ``entry``.

    An instruction is charged to the latest part (`latest`) among the
    scope its own ``op_name`` names and, for a fusion, those of every
    instruction fused into it.  One that names none is a loop carry copy
    (`CARRY`) where it is a copy, in the entry computation, of an
    argument or of a loop's result; else it takes the part of the nearest
    instruction that has one, looking first along its operands, then
    along its users, through at most ``depth`` instructions that have
    none (the reshapes and broadcasts XLA adds carry an empty
    ``op_name``, or their caller's)."""
    by_id = {i.id: i for i in instrs}
    body: dict = {}
    for i in instrs:
        body.setdefault(i.computation, []).append(i)
    held: dict = {}

    def holds(i) -> set:
        if i.id not in held:
            out = {scope_of(i.op_name)} - {""}
            if i.opcode == "fusion":
                for c in i.called:
                    for j in body.get(c, ()):
                        out |= holds(j)
            held[i.id] = out
        return held[i.id]

    own = {i.id: latest(holds(i)) for i in instrs}
    operands = {i.id: i.operands for i in instrs}
    users: dict = {}
    for i in instrs:
        for o in i.operands:
            users.setdefault(o, []).append(i.id)

    def carry(i) -> bool:
        return (i.opcode == "copy" and i.computation == entry
                and all(by_id[o].opcode in ("parameter", "get-tuple-element")
                        for o in i.operands if o in by_id))

    def nearest(start: int, edges: dict) -> str:
        frontier, seen = [start], {start}
        for _ in range(depth):
            nxt = []
            for n in frontier:
                for m in edges.get(n, ()):
                    if m in seen or m not in own:
                        continue
                    if own[m]:
                        return own[m]
                    seen.add(m)
                    nxt.append(m)
            frontier = nxt
        return ""

    return {i.name: own[i.id] or (CARRY if carry(i) else "")
            or nearest(i.id, operands) or nearest(i.id, users)
            for i in instrs}


# A profiler trace is an XSpace protobuf; the few fields read here are
# decoded from the wire format, so no protobuf module is needed.
def _varint(buf, i: int) -> tuple[int, int]:
    shift = out = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of a message: ints for varints, memoryviews
    for length-delimited fields; fixed-width fields are skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
            yield field, value
        elif wire == 2:
            size, i = _varint(buf, i)
            yield field, buf[i:i + size]
            i += size
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")


def _first(buf, number: int, default=None):
    for field, value in _fields(buf):
        if field == number:
            return value
    return default


def _text(value) -> str:
    return bytes(value).decode("utf-8", "replace")


def _varints(value) -> list:
    """A repeated integer field: one varint, or a packed run of them."""
    if isinstance(value, int):
        return [value]
    out, i = [], 0
    while i < len(value):
        v, i = _varint(value, i)
        out.append(v)
    return out


def hlo_modules(xspace: bytes, modules=None) -> dict:
    """{module execution name: (entry computation id, [Instr])} from the
    HLO protos of a trace's ``/host:metadata`` plane, for the modules
    named in ``modules`` (all where None).

    XSpace.planes = 1; XPlane name 2, event_metadata 4 (map: key 1,
    value 2), stat_metadata 5; XEventMetadata name 2, stats 5; XStat
    metadata_id 1, bytes_value 6; XStatMetadata id 1, name 2; HloProto
    hlo_module 1; HloModuleProto computations 3, entry_computation_id 6;
    HloComputationProto instructions 2, id 5; HloInstructionProto name 1,
    opcode 2, metadata 7, id 35, operand_ids 36, called_computation_ids
    38; OpMetadata op_name 2."""
    out = {}
    for field, plane in _fields(memoryview(xspace)):
        if field != 1 or _text(_first(plane, 2, b"")) != "/host:metadata":
            continue
        stat_ids, metas = set(), []
        for f, entry in _fields(plane):
            if f == 5:
                meta = _first(entry, 2, b"")
                if _text(_first(meta, 2, b"")) == "Hlo Proto":
                    stat_ids.add(_first(meta, 1, 0))
            elif f == 4:
                metas.append(_first(entry, 2, b""))
        for meta in metas:
            module = _text(_first(meta, 2, b""))
            if modules is not None and module not in modules:
                continue
            for f, stat in _fields(meta):
                if f == 5 and _first(stat, 1, 0) in stat_ids:
                    out[module] = _module(_first(stat, 6, b""))
    return out


def _module(hlo_proto) -> tuple:
    out, entry = [], 0
    for f, comp in _fields(_first(hlo_proto, 1, b"")):
        if f == 6:
            entry = comp
        if f != 3:
            continue
        found, ident = [], 0
        for g, value in _fields(comp):
            if g == 2:
                found.append(value)
            elif g == 5:
                ident = value
        for instr in found:
            name = opcode = op_name = ""
            iid, operands, called = 0, [], []
            for h, value in _fields(instr):
                if h == 1:
                    name = _text(value)
                elif h == 2:
                    opcode = _text(value)
                elif h == 7:
                    op_name = _text(_first(value, 2, b""))
                elif h == 35:
                    iid = value
                elif h == 36:
                    operands += _varints(value)
                elif h == 38:
                    called += _varints(value)
                elif h > 38:     # fields come in number order
                    break
            out.append(Instr(name, op_name, iid, tuple(operands), opcode,
                             tuple(called), ident))
    return entry, out


# ------------------------------------------------------------ reductions
def part_ns(marks: Marks, part: str, programs=None) -> float:
    """Device time of the ops inside the window charged to ``part`` (or a
    part under it: ``mll.mix`` takes ``mll.mix.hub``), loop ops left out,
    averaged over the devices; only in the programs ``programs`` where
    given."""
    if not marks.ops:
        return 0.0
    lo, hi = marks.window
    total = 0.0
    for ops in marks.ops.values():
        for op, mod, p in ops:
            if (p == part or p.startswith(part + ".")) \
                    and (programs is None or mod in programs) \
                    and op.end > lo and op.start < hi \
                    and op.kind not in LOOPS:
                total += op.end - op.start
    return total / len(marks.ops)


def programs(marks: Marks, *kinds) -> set:
    """The programs of any of ``kinds`` (`kind_of`)."""
    return {mod for mod, k in marks.programs.items() if k in kinds}


def _inside(marks: Marks, pattern) -> list:
    lo, hi = marks.window
    rx = re.compile(pattern)
    return [s for s in marks.spans
            if lo <= s.start and s.end <= hi and rx.search(s.name)]


def slots(marks: Marks, pattern) -> int:
    """Slots on the spans inside the window whose name matches
    ``pattern`` (their ``slots`` stats summed)."""
    return sum(int(s.stats.get("slots", 0)) for s in _inside(marks, pattern))


def span_ns(marks: Marks, pattern) -> float:
    """Summed length of the spans inside the window whose name matches."""
    return sum(s.end - s.start for s in _inside(marks, pattern))


def self_ns(marks: Marks, parent: str, children) -> float:
    """Summed length of the spans named ``parent`` inside the window, less
    the spans matching ``children`` that lie within them."""
    kids = _inside(marks, children)
    total = 0.0
    for p in _inside(marks, f"^{re.escape(parent)}$"):
        total += (p.end - p.start) - sum(
            k.end - k.start for k in kids
            if p.start <= k.start and k.end <= p.end)
    return total


def model_slots(marks: Marks) -> int:
    """Slots whose step program ran the model (every dispatched slot)."""
    return slots(marks, DISPATCH)


def local_slots(marks: Marks) -> int:
    """Slots run by the local scans."""
    return slots(marks, f"^{LOCAL_SCAN}$")


def event_slots(marks: Marks) -> int:
    """Slots that end in a mixing event."""
    return slots(marks, r"^(event_step\.\d+|dense_step)$")


def all_slots(marks: Marks) -> int:
    """Every slot the window's `run_span` calls covered, fast-forwarded
    ones too."""
    return slots(marks, DISPATCH) + slots(marks, f"^{SKIP_IDLE}$")


def per_slot_ms(ns: float, n: int):
    """``ns`` per slot in ms, or None where nothing was read."""
    if not n or not ns:
        return None
    return ns / n / 1e6
