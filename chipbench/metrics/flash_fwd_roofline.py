"""Roofline share of the flash-attention forward kernel: max(FLOPs / peak
FLOP/s, bytes / peak bytes/s) over its summed device time.  FLOPs and
bytes are those of causal attention at the call's (B, H, Hkv, T, hd) for
every layer of every slot of the traced window, whatever implements it
(`flops.attention_least_seconds`)."""


def read(ctx):
    t = ctx.traces.op_ns(ctx.trace, ctx.names["flash_fwd"]) / 1e9
    if not t:
        return None
    return 100.0 * ctx.flops.attention_least_seconds(
        ctx.spec, ctx.traffic, ctx.window["slots"], ctx.peaks, False) / t
