"""Roofline-term extraction from compiled HLO text.

Why not ``compiled.cost_analysis()``: XLA's HloCostAnalysis visits a while
body ONCE — for a depth-L ``lax.scan`` transformer it under-counts FLOPs and
bytes by ~L (verified empirically: ratio 1/7 for a 7-step scan).  This module
parses the post-optimization HLO text, builds the computation call graph, and
propagates **trip-count multipliers** (``known_trip_count`` backend config)
through while bodies, fusions, calls and conditionals, so scanned layers are
counted exactly.

Cost model (documented approximations):

  FLOPs      : dots count 2 * prod(result_dims) * prod(contracted_dims)
               exactly; a 1-flop-per-output-element estimate covers
               elementwise arithmetic (VPU term, minor for these models).
  HBM bytes  : every materializing op costs (operand bytes + result bytes);
               parameter/constant/tuple/GTE/bitcast are free.  This models
               each tensor as one HBM write + one read per consumer — an
               upper bound vs. TPU fusion, but consistent across variants,
               which is what the §Perf iteration deltas need.
  Collective : bytes = result bytes of every all-gather / all-reduce /
               reduce-scatter / all-to-all / collective-permute, with the
               trip-count multiplier applied; DCN (cross-pod) traffic is
               split out by decoding replica groups against the mesh's
               device numbering (pod axis = major).

Roofline terms (TPU v5e-class constants):

  compute    = flops / (chips * 197e12)
  memory     = bytes / (chips * 819e9)
  collective = coll_bytes / (chips * 50e9)      [ICI]
  dcn        = dcn_bytes / (chips * 25e9)       [cross-pod, reported too]
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict

import numpy as np

PEAK_FLOPS = 197e12          # bf16 per chip
HBM_BW = 819e9               # bytes/s per chip
ICI_BW = 50e9                # bytes/s per link
DCN_BW = 25e9                # bytes/s per chip across pods (assumed)

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8, "f8e4m3fn": 1, "f8e5m2": 1,
    "bf16": 2, "f16": 2, "f32": 4, "f64": 8, "c64": 8, "c128": 16,
    "token": 0, "opaque": 0,
}

_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")
_OP_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w\.\-]+)\s*=\s*(.*)$")
_CALLED_RE = {
    "body": re.compile(r"body=%?([\w\.\-]+)"),
    "condition": re.compile(r"condition=%?([\w\.\-]+)"),
    "calls": re.compile(r"calls=%?([\w\.\-]+)"),
    "to_apply": re.compile(r"to_apply=%?([\w\.\-]+)"),
}
_BRANCHES_RE = re.compile(r"branch_computations=\{([^}]*)\}")
_TRIP_RE = re.compile(r'known_trip_count[^0-9]*?(\d+)')
_OPCODE_RE = re.compile(r"\b([a-z][a-z0-9\-]*)\(")

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")

_ELEMENTWISE = {
    "add", "subtract", "multiply", "divide", "maximum", "minimum", "power",
    "exponential", "exponential-minus-one", "tanh", "log", "log-plus-one",
    "rsqrt", "sqrt", "negate", "abs", "floor", "ceil", "sign", "atan2",
    "logistic", "cosine", "sine",
}
_FREE_OPS = {
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "bitcast-convert", "after-all", "partition-id", "replica-id",
}


def _shape_bytes(type_str: str) -> int:
    """Sum bytes over every dtype[dims] group in an HLO type string
    (handles tuples by just summing all groups)."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _shape_dims(type_str: str) -> list[tuple[str, tuple[int, ...]]]:
    out = []
    for dt, dims in _SHAPE_RE.findall(type_str):
        out.append((dt, tuple(int(d) for d in dims.split(",")) if dims else ()))
    return out


@dataclasses.dataclass
class Op:
    name: str
    opcode: str
    result_type: str          # the HLO type string before the opcode
    line: str
    is_root: bool = False


@dataclasses.dataclass
class Computation:
    name: str
    ops: list[Op]
    symtab: dict[str, str]    # op name -> result type string

    @property
    def root(self) -> "Op | None":
        for op in self.ops:
            if op.is_root:
                return op
        return self.ops[-1] if self.ops else None


def parse_computations(hlo_text: str) -> dict[str, Computation]:
    comps: dict[str, Computation] = {}
    cur: Computation | None = None
    for raw in hlo_text.splitlines():
        line = raw.rstrip()
        stripped = line.strip()
        if cur is None:
            # computation header: "%name (params...) -> type {" or "ENTRY ..."
            if stripped.endswith("{") and ("->" in stripped or
                                           stripped.startswith("ENTRY")):
                m = re.match(r"(?:ENTRY\s+)?%?([\w\.\-]+)\s*\(", stripped)
                if m:
                    cur = Computation(m.group(1), [], {})
            continue
        if stripped == "}":
            comps[cur.name] = cur
            cur = None
            continue
        m = _OP_RE.match(stripped)
        if not m:
            continue
        name, rhs = m.groups()
        # result type = text up to the opcode token followed by "("
        om = _OPCODE_RE.search(rhs)
        if not om:
            continue
        opcode = om.group(1)
        result_type = rhs[: om.start()].strip()
        op = Op(name, opcode, result_type, stripped,
                is_root=stripped.startswith("ROOT "))
        cur.ops.append(op)
        cur.symtab[name] = result_type
    return comps


def _entry_name(hlo_text: str, comps: dict[str, Computation]) -> str:
    m = re.search(r"^ENTRY\s+%?([\w\.\-]+)", hlo_text, re.M)
    if m and m.group(1) in comps:
        return m.group(1)
    # fall back: the computation no one calls
    called = set()
    for c in comps.values():
        for op in c.ops:
            for rx in _CALLED_RE.values():
                called.update(rx.findall(op.line))
            bm = _BRANCHES_RE.search(op.line)
            if bm:
                called.update(x.strip().lstrip("%")
                              for x in bm.group(1).split(","))
    for name in comps:
        if name not in called:
            return name
    raise ValueError("cannot locate entry computation")


def _call_edges(comp: Computation) -> tuple[list[tuple[str, float]], int]:
    """(callee, weight) edges out of `comp`; weight = trip count for while
    bodies/conditions, 1 otherwise.  Second return: #whiles w/o trip count."""
    edges: list[tuple[str, float]] = []
    unknown = 0
    for op in comp.ops:
        trip = 1.0
        if op.opcode == "while":
            tm = _TRIP_RE.search(op.line)
            if tm:
                trip = float(tm.group(1))
            else:
                unknown += 1
        for key, rx in _CALLED_RE.items():
            for callee in rx.findall(op.line):
                if callee == comp.name:
                    continue
                edges.append((callee,
                              trip if key in ("body", "condition") else 1.0))
        bm = _BRANCHES_RE.search(op.line)
        if bm:
            for callee in bm.group(1).split(","):
                edges.append((callee.strip().lstrip("%"), 1.0))
    return edges, unknown


def compute_multipliers(comps: dict[str, Computation],
                        entry: str) -> dict[str, float]:
    """Execution-count multiplier per computation: propagate while trip
    counts (``known_trip_count``) down the (acyclic) HLO call graph.
    Unknown trip counts count as 1; their number is recorded under
    '__unknown_trips__'."""
    edges: dict[str, list[tuple[str, float]]] = {}
    unknown_total = 0
    for name, comp in comps.items():
        edges[name], u = _call_edges(comp)
        unknown_total += u

    # topological order from entry (HLO call graphs cannot recurse)
    order: list[str] = []
    seen: set[str] = set()

    def dfs(name: str):
        if name in seen or name not in comps:
            return
        seen.add(name)
        for callee, _ in edges.get(name, ()):
            dfs(callee)
        order.append(name)

    dfs(entry)
    mult: dict[str, float] = defaultdict(float)
    mult[entry] = 1.0
    for name in reversed(order):           # entry first
        m = mult[name]
        if m == 0.0:
            continue
        for callee, w in edges.get(name, ()):
            mult[callee] += m * w
    out = dict(mult)
    out["__unknown_trips__"] = float(unknown_total)
    return out


_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")
_OPERANDS_RE = re.compile(r"\(([^)]*)\)")


def _split_operands(arglist: str) -> list[str]:
    """Split an HLO operand list on top-level commas only: operand tokens
    may carry inline types whose dims/layouts contain commas, e.g.
    ``f32[32,64]{1,0} %lhs, f32[64,64]{1,0} %rhs``."""
    out, depth, cur = [], 0, []
    for ch in arglist:
        if ch in "[{(":
            depth += 1
        elif ch in "]})":
            depth -= 1
        if ch == "," and depth == 0:
            out.append("".join(cur).strip())
            cur = []
        else:
            cur.append(ch)
    tail = "".join(cur).strip()
    if tail:
        out.append(tail)
    return out


def _dot_flops(op: Op, comp: Computation) -> float:
    """2 * prod(result) * prod(lhs contracting dims)."""
    shapes = _shape_dims(op.result_type)
    if not shapes:
        return 0.0
    out_elems = float(np.prod(shapes[0][1])) if shapes[0][1] else 1.0
    cm = _CONTRACT_RE.search(op.line)
    if not cm:
        return 2.0 * out_elems  # degenerate
    cdims = [int(x) for x in cm.group(1).split(",") if x]
    # first operand inside dot(...): its inline type if present, else symtab
    pm = _OPERANDS_RE.search(op.line[op.line.find("dot("):])
    lhs_dims: tuple[int, ...] = ()
    if pm:
        operands = _split_operands(pm.group(1))
        if operands:
            first = operands[0]
            ds = _shape_dims(first)
            if ds:                                  # inline "f32[32,64]{1,0} %x"
                lhs_dims = ds[0][1]
            else:
                name = first.split()[-1].lstrip("%")
                t = comp.symtab.get(name)
                if t:
                    ds = _shape_dims(t)
                    if ds:
                        lhs_dims = ds[0][1]
    contract = 1.0
    for d in cdims:
        if d < len(lhs_dims):
            contract *= lhs_dims[d]
    return 2.0 * out_elems * contract


# -------------------------------------------------- replica-group decoding
_IOTA_RG_RE = re.compile(
    r"replica_groups=\[(\d+),(\d+)\]<=\[([0-9,]+)\](?:T\(([0-9,]+)\))?")
_LIST_RG_RE = re.compile(r"replica_groups=\{\{([0-9,{} ]*)\}\}")
_STP_RE = re.compile(r"source_target_pairs=\{([0-9,{} ]*)\}")


def _crosses_pods(line: str, pod_stride: int) -> bool:
    """True if any replica group spans device ids >= pod_stride apart
    (pod axis is major in our mesh device ordering).  collective-permute
    carries source_target_pairs instead of replica_groups."""
    if pod_stride <= 0:
        return False
    mp_ = _STP_RE.search(line)
    if mp_:
        for pair in mp_.group(1).split("},{"):
            ids = [int(x) for x in
                   pair.replace("{", "").replace("}", "").split(",")
                   if x.strip()]
            if len(ids) == 2 and abs(ids[1] - ids[0]) >= pod_stride:
                return True
        return False
    m = _IOTA_RG_RE.search(line)
    if m:
        g, n = int(m.group(1)), int(m.group(2))
        dims = tuple(int(x) for x in m.group(3).split(","))
        ids = np.arange(int(np.prod(dims))).reshape(dims)
        if m.group(4):
            perm = tuple(int(x) for x in m.group(4).split(","))
            ids = ids.transpose(perm)
        groups = ids.reshape(g, n)
        return bool((groups.max(1) - groups.min(1) >= pod_stride).any())
    m = _LIST_RG_RE.search(line)
    if m:
        for grp in m.group(1).split("},{"):
            ids = [int(x) for x in grp.replace("{", "").replace("}", "").split(",") if x.strip()]
            if ids and max(ids) - min(ids) >= pod_stride:
                return True
    return False


@dataclasses.dataclass
class HloCosts:
    flops: float = 0.0
    dot_flops: float = 0.0
    bytes: float = 0.0
    collective_bytes: float = 0.0
    dcn_bytes: float = 0.0
    collective_counts: dict = dataclasses.field(
        default_factory=lambda: defaultdict(float))
    collective_bytes_by_op: dict = dataclasses.field(
        default_factory=lambda: defaultdict(float))
    top_collectives: list = dataclasses.field(default_factory=list)
    unknown_trip_whiles: int = 0

    def as_dict(self) -> dict:
        return {
            "flops": self.flops,
            "dot_flops": self.dot_flops,
            "bytes": self.bytes,
            "collective_bytes": self.collective_bytes,
            "dcn_bytes": self.dcn_bytes,
            "collective_counts": dict(self.collective_counts),
            "collective_bytes_by_op": dict(self.collective_bytes_by_op),
            "top_collectives": self.top_collectives[:20],
        }


# ops whose result is not fresh HBM traffic at the call site (their bodies
# are walked separately with the right multiplier)
_CONTROL_OPS = {"while", "conditional", "call"}


def _operand_names(op: Op) -> list[str]:
    pm = _OPERANDS_RE.search(op.line[op.line.find(op.opcode + "("):])
    if not pm:
        return []
    out = []
    for tok in _split_operands(pm.group(1)):
        if tok:
            out.append(tok.split()[-1].lstrip("%"))
    return out


def _operand_bytes(op: Op, comp: Computation) -> tuple[int, int]:
    """(total operand bytes, largest single operand bytes)."""
    total, biggest = 0, 0
    for name in _operand_names(op):
        t = comp.symtab.get(name)
        if t:
            b = _shape_bytes(t)
            total += b
            biggest = max(biggest, b)
    return total, biggest


def _dus_update_bytes(op: Op, comp: Computation) -> int:
    """Bytes of the update operand (operand 1) of a dynamic-update-slice."""
    names = _operand_names(op)
    if len(names) >= 2:
        t = comp.symtab.get(names[1])
        if t:
            return _shape_bytes(t)
    return 0


_SLICE_OPS = {"dynamic-slice", "slice", "gather"}
_PARAM_IDX_RE = re.compile(r"parameter\((\d+)\)")


def _fusion_param_read_bytes(body: Computation) -> dict[int, int]:
    """For fusion parameters consumed ONLY by slice-like interior ops, the
    HBM read is the slice, not the whole operand (a per-step dynamic-slice
    of a scanned tensor reads ~KB, not the full array).  Returns
    {param_index: adjusted read bytes} for such params."""
    params: dict[str, int] = {}
    for op in body.ops:
        if op.opcode == "parameter":
            m = _PARAM_IDX_RE.search(op.line)
            if m:
                params[op.name] = int(m.group(1))
    if not params:
        return {}
    uses: dict[str, list[Op]] = {p: [] for p in params}
    for op in body.ops:
        if op.opcode == "parameter":
            continue
        for name in _operand_names(op):
            if name in uses:
                uses[name].append(op)
    out: dict[int, int] = {}
    for pname, consumers in uses.items():
        if consumers and all(c.opcode in _SLICE_OPS for c in consumers):
            out[params[pname]] = sum(_shape_bytes(c.result_type)
                                     for c in consumers)
    return out


def _classify_computations(comps: dict[str, Computation]) -> set[str]:
    """Names of INLINE computations (fusion bodies / reduce lambdas etc.):
    their ops cost FLOPs but no HBM bytes — the fusion boundary pays the
    traffic.  Computations reached via while/conditional/call control flow
    stay byte-accounted."""
    inline: set[str] = set()
    control: set[str] = set()
    for comp in comps.values():
        for op in comp.ops:
            for callee in _CALLED_RE["calls"].findall(op.line):
                inline.add(callee)
            for callee in _CALLED_RE["to_apply"].findall(op.line):
                inline.add(callee)
            for key in ("body", "condition"):
                for callee in _CALLED_RE[key].findall(op.line):
                    control.add(callee)
            bm = _BRANCHES_RE.search(op.line)
            if bm:
                control.update(x.strip().lstrip("%")
                               for x in bm.group(1).split(","))
    return inline - control


def analyze_hlo(hlo_text: str, *, pod_stride: int = 0) -> HloCosts:
    """Walk every computation with its execution multiplier and accumulate
    the cost model above.  ``pod_stride`` (e.g. 256 for a (2,16,16) mesh)
    enables DCN traffic classification."""
    comps = parse_computations(hlo_text)
    entry = _entry_name(hlo_text, comps)
    mult = compute_multipliers(comps, entry)
    inline = _classify_computations(comps)
    costs = HloCosts(unknown_trip_whiles=int(mult.pop("__unknown_trips__", 0)))
    coll_details = []
    for cname, comp in comps.items():
        m = mult.get(cname, 0.0)
        if m == 0.0:
            continue
        count_bytes = cname not in inline
        for op in comp.ops:
            if op.opcode in _FREE_OPS:
                continue
            rbytes = _shape_bytes(op.result_type)
            # ---- FLOPs (counted everywhere, incl. fusion interiors)
            if op.opcode == "dot":
                f = _dot_flops(op, comp)
                costs.flops += m * f
                costs.dot_flops += m * f
            elif op.opcode in _ELEMENTWISE:
                shapes = _shape_dims(op.result_type)
                if shapes:
                    costs.flops += m * float(
                        np.prod(shapes[0][1]) if shapes[0][1] else 1)
            # ---- collectives.  The TPU compiler splits them into async
            # X-start / X-done pairs: the start counts the call, the done's
            # result is the bytes moved (the start's is an operand tuple)
            coll, part = op.opcode, ""
            if coll.endswith(("-start", "-done")):
                coll, part = coll.rsplit("-", 1)
            if coll in COLLECTIVES:
                if part != "done":
                    costs.collective_counts[coll] += m
                if part != "start":
                    cb = m * rbytes
                    costs.collective_bytes += cb
                    costs.collective_bytes_by_op[coll] += cb
                    if _crosses_pods(op.line, pod_stride):
                        costs.dcn_bytes += cb
                    coll_details.append((cb, coll, op.result_type, cname))
            # ---- HBM bytes (fusion boundaries only; in-place DUS)
            if not count_bytes or op.opcode in _CONTROL_OPS:
                continue
            obytes, biggest = _operand_bytes(op, comp)
            if op.opcode == "dynamic-update-slice":
                upd = _dus_update_bytes(op, comp)
                costs.bytes += m * 2 * upd          # read update, write region
            elif op.opcode in _SLICE_OPS:
                costs.bytes += m * 2 * rbytes       # read slice, write slice
            elif op.opcode == "fusion":
                callee = next(iter(_CALLED_RE["calls"].findall(op.line)), None)
                body = comps.get(callee)
                # slice-consumed params read only their slices
                if body is not None:
                    onames = _operand_names(op)
                    sliced = _fusion_param_read_bytes(body)
                    for idx, read in sliced.items():
                        if idx < len(onames):
                            t = comp.symtab.get(onames[idx])
                            if t:
                                obytes -= _shape_bytes(t) - read
                root = body.root if body is not None else None
                if root is not None and root.opcode == "dynamic-update-slice":
                    # in-place DUS fusion: don't charge the aliased buffer
                    upd = _dus_update_bytes(root, body)
                    costs.bytes += m * max(obytes - biggest, 0) + m * 2 * upd
                else:
                    costs.bytes += m * (rbytes + obytes)
            else:
                costs.bytes += m * (rbytes + obytes)
    coll_details.sort(reverse=True)
    costs.top_collectives = [
        {"bytes": b, "op": o, "type": t, "computation": c}
        for b, o, t, c in coll_details[:20]]
    return costs


# ----------------------------------------------------------------- roofline
@dataclasses.dataclass
class Roofline:
    compute_s: float
    memory_s: float
    collective_s: float
    dcn_s: float
    flops: float
    bytes: float
    collective_bytes: float
    dcn_bytes: float
    chips: int

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    def as_dict(self) -> dict:
        return {**dataclasses.asdict(self), "dominant": self.dominant}


def roofline_terms(costs: HloCosts, chips: int) -> Roofline:
    """`costs` come from the post-SPMD-partitioning HLO, i.e. they are
    PER-DEVICE.  Terms are per-device work / per-device bandwidth (equal to
    global/(chips*bw) for symmetric SPMD); the flops/bytes fields are scaled
    back to GLOBAL totals for the table."""
    return Roofline(
        compute_s=costs.flops / PEAK_FLOPS,
        memory_s=costs.bytes / HBM_BW,
        collective_s=costs.collective_bytes / ICI_BW,
        dcn_s=costs.dcn_bytes / DCN_BW,
        flops=costs.flops * chips,
        bytes=costs.bytes * chips,
        collective_bytes=costs.collective_bytes * chips,
        dcn_bytes=costs.dcn_bytes * chips,
        chips=chips,
    )


def model_flops(param_count_active: int, tokens: int) -> float:
    """MODEL_FLOPS = 6 * N_active * D (training) — the useful-compute yardstick."""
    return 6.0 * param_count_active * tokens
