"""Device time of the programs `TrainHarness.local_scan` compiled (the
local-only slots between mixing events), per local slot, averaged over the
cell's chips."""


def read(ctx):
    n = ctx.window["local_slots"]
    t = ctx.traces.module_ns(ctx.trace, ctx.names["local_scan"])
    if not n or not t:
        return None
    return t / len(ctx.trace.ops) / n / 1e6
