"""Device time of the collectives in the event programs (all-reduce,
collective-permute, all-gather; an asynchronous ``-start`` / ``-done``
pair counted once, from the start's beginning to the done's end) during
which no other op runs on that chip, per event slot, averaged over the
cell's chips: the time the exchange between chips holds a mixing event
up.  A run on one chip has no such exchange and reads nothing."""
import re

import spans
import traces

_NAMES = r"(all-reduce|collective-permute|all-gather)(-start|-done)?"
_KIND = re.compile(_NAMES)
_TEXT = re.compile(" " + _NAMES + r"\(")


def collective(op):
    """(collective, "" | "-start" | "-done") of an op, by its instruction
    name or, where XLA named it otherwise, its opcode; None for others."""
    m = _KIND.fullmatch(op.kind) or _TEXT.search(op.name)
    return (m.group(1), m.group(2) or "") if m else None


def exposed_ns(ops, events, lo, hi) -> tuple[float, bool]:
    """One chip's exposed collective time in the programs ``events``
    inside [lo, hi), and whether any collective was found there.  ``ops``
    = [(Op, program, part)] in start order; a ``-done`` closes the oldest
    open ``-start`` of its collective."""
    coll, other, open_ = [], [], {}
    for op, mod, _ in ops:
        c = collective(op) if mod in events else None
        if c is None:
            if op.kind not in traces.LOOPS:
                other.append((op.start, op.end))
        else:
            kind, phase = c
            if phase == "-start":
                open_.setdefault(kind, []).append(op.start)
            elif phase == "-done" and open_.get(kind):
                coll.append((open_[kind].pop(0), op.end))
            else:
                coll.append((op.start, op.end))
    busy = traces.merge(other, lo, hi)
    return traces.length(traces.minus(traces.merge(coll, lo, hi), busy)), \
        bool(coll)


def read(ctx):
    m = spans.of(ctx)
    if m is None or ctx.chips < 2:
        return None
    events = spans.programs(m, spans.EVENT_STEP, spans.DENSE_STEP)
    lo, hi = m.window
    per_chip = [exposed_ns(ops, events, lo, hi)
                for ops in m.ops.values()]
    n = spans.event_slots(m)
    if not n or not any(found for _, found in per_chip):
        return None
    return sum(t for t, _ in per_chip) / len(per_chip) / n / 1e6
