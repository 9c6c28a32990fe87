"""Share of the traced window in which no op runs on a device, averaged
over the cell's chips: 1 - (union of the device's op intervals / window)."""


def read(ctx):
    if not ctx.trace.ops:
        return None
    return 100.0 * ctx.traces.idle_share(ctx.trace)
