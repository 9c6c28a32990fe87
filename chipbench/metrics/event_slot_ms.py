"""Device time of the `event_step` programs (a slot's gradient step that
ends in a subnet or hub mixing event), per event slot, averaged over the
cell's chips.  A program is an event step by the trainer scopes its HLO
holds (`spans.kind_of`)."""
import spans


def read(ctx):
    m = spans.of(ctx)
    n = ctx.window["event_slots"]
    if m is None or not n:
        return None
    t = ctx.traces.module_ns(ctx.trace, spans.programs(m, spans.EVENT_STEP))
    return t / len(ctx.trace.ops) / n / 1e6 if t else None
