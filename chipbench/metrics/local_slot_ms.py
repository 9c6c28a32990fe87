"""Device time of the programs `TrainHarness.local_scan` compiled (the
local-only slots between mixing events), per local slot, averaged over the
cell's chips.  A program is a local scan by the trainer scopes its HLO
holds (`spans.kind_of`)."""
import spans


def read(ctx):
    m = spans.of(ctx)
    n = ctx.window["local_slots"]
    if m is None or not n:
        return None
    t = ctx.traces.module_ns(ctx.trace, spans.programs(m, spans.LOCAL_SCAN))
    return t / len(ctx.trace.ops) / n / 1e6 if t else None
