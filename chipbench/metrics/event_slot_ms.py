"""Device time of the `event_step` programs (a slot's gradient step that
ends in a subnet or hub mixing event), per event slot, averaged over the
cell's chips."""


def read(ctx):
    n = ctx.window["event_slots"]
    t = ctx.traces.module_ns(ctx.trace, ctx.names["event_step"])
    if not n or not t:
        return None
    return t / len(ctx.trace.ops) / n / 1e6
