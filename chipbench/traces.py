"""The profiler's trace, reduced to what the per-layer metrics read.

`load` reads the newest ``*.xplane.pb`` under a directory with
`jax.profiler.ProfileData`: for each device plane, the executions of its
"XLA Modules" line (one per call of a compiled program, named
``jit_<fn>(<fingerprint>)``) and the ops of its "XLA Ops" line (named by
their HLO instruction text, ``%fusion.12 = bf16[...] fusion(...)``, which
the profiler cuts short); from the host, the spans the benchmark placed
(`jax.profiler.TraceAnnotation`).  A loop op (``while``, ``conditional``,
``call``) spans the ops of its body, which are listed too: the reductions
that attribute time to single ops leave loop ops out.  The reductions are
plain functions of these intervals, so the tests can hold them to
hand-computed values.  All times are nanoseconds on the profiler's clock.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

WINDOW = "chipbench.window"


LOOPS = ("while", "conditional", "call")


@dataclasses.dataclass(frozen=True)
class Op:
    name: str
    start: float
    end: float

    @property
    def kind(self) -> str:
        """The instruction's name without '%' and its numeric suffix."""
        return re.sub(r"\.\d+$", "", self.name.split(" ", 1)[0].lstrip("%"))


@dataclasses.dataclass
class Trace:
    ops: dict            # device id -> [Op], sorted by start
    modules: dict        # device id -> [Op] program executions
    host: list           # [Op] host spans
    window: tuple        # (start, end)

    @property
    def window_ns(self) -> float:
        return self.window[1] - self.window[0]


def load(directory: str, labels) -> Trace:
    """The newest trace under ``directory``; of the host's spans, those
    named in ``labels`` (the benchmark's own) and the window."""
    import jax
    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no profiler trace under {directory}")
    pd = jax.profiler.ProfileData.from_file(paths[-1])
    ops, modules, host = {}, {}, []
    for plane in pd.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m:
            d = int(m.group(1))
            for line in plane.lines:
                into = {"XLA Ops": ops, "XLA Modules": modules}.get(line.name)
                if into is not None:
                    into[d] = sorted((Op(ev.name, ev.start_ns,
                                         ev.start_ns + ev.duration_ns)
                                      for ev in line.events),
                                     key=lambda o: o.start)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in labels or ev.name == WINDOW:
                        host.append(Op(ev.name, ev.start_ns,
                                       ev.start_ns + ev.duration_ns))
    spans = [h for h in host if h.name == WINDOW]
    if not spans:
        raise ValueError(f"the trace holds no {WINDOW!r} span")
    w = spans[-1]
    return Trace(ops, modules, [h for h in host if h.name != WINDOW],
                 (w.start, w.end))


# ------------------------------------------------------------- reductions
def merge(intervals, lo: float, hi: float) -> list:
    """Union of (start, end) intervals clipped to [lo, hi], as disjoint
    sorted intervals."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def minus(a, b) -> list:
    """The parts of disjoint sorted intervals ``a`` that no interval of
    disjoint sorted ``b`` covers."""
    out, k = [], 0
    for s, e in a:
        while k < len(b) and b[k][1] <= s:
            k += 1
        j = k
        while j < len(b) and b[j][0] < e:
            if b[j][0] > s:
                out.append((s, b[j][0]))
            s = max(s, b[j][1])
            j += 1
        if s < e:
            out.append((s, e))
    return out


def busy_ns(trace: Trace) -> dict:
    """Per device, the union of its op intervals inside the window."""
    lo, hi = trace.window
    return {d: length(merge(((o.start, o.end) for o in ops), lo, hi))
            for d, ops in trace.ops.items()}


def idle_share(trace: Trace) -> float:
    """1 - busy / window, averaged over the devices."""
    busy = busy_ns(trace)
    return 1.0 - sum(busy.values()) / len(busy) / trace.window_ns


def _inside(trace: Trace, table: dict, match):
    lo, hi = trace.window
    for ops in table.values():
        for o in ops:
            if o.end > lo and o.start < hi and match(o.name):
                yield o


def module_ns(trace: Trace, programs) -> float:
    """Device time of the executions of the programs named in
    ``programs``, summed over devices (an execution overlapping the window
    counts whole)."""
    return sum(o.end - o.start for o in
               _inside(trace, trace.modules, programs.__contains__))


def op_ns(trace: Trace, pattern: str) -> float:
    """Device time of the ops whose instruction text matches, summed over
    devices."""
    rx = re.compile(pattern)
    return sum(o.end - o.start for o in _inside(trace, trace.ops, rx.search))


def top_ops(trace: Trace, n: int = 10) -> list:
    """The ops that took most device time, by instruction name without its
    numeric suffix (loop ops left out), averaged over devices:
    [[name, seconds], ...]."""
    agg = {}
    for o in _inside(trace, trace.ops, lambda name: True):
        if o.kind not in LOOPS:
            agg[o.kind] = agg.get(o.kind, 0.0) + (o.end - o.start)
    k = max(len(trace.ops), 1)
    return [[name, t / k / 1e9] for name, t in
            sorted(agg.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: Trace, n: int = 10) -> list:
    """The longest gaps between ops on the first device inside the window,
    each labelled by the innermost host span around its middle:
    [[label, seconds], ...]."""
    if not trace.ops:
        return []
    lo, hi = trace.window
    dev = min(trace.ops)
    busy = merge(((o.start, o.end) for o in trace.ops[dev]), lo, hi)
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = (s + e) / 2
        around = [h for h in trace.host if h.start <= mid <= h.end]
        label = min(around, key=lambda h: h.end - h.start).name \
            if around else "no host span"
        out.append([label, (e - s) / 1e9])
    return out
