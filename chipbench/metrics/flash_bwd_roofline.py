"""Roofline share of the flash-attention backward (dq and dkv kernels
together), as `flash_fwd_roofline`: the backward's four matmuls (twice the
forward's FLOPs) and its least bytes, over the two kernels' summed device
time.  The recomputed Q K^T is not counted."""


def read(ctx):
    t = ctx.traces.op_ns(ctx.trace, ctx.names["flash_bwd"]) / 1e9
    if not t:
        return None
    return 100.0 * ctx.flops.attention_least_seconds(
        ctx.spec, ctx.traffic, ctx.window["slots"], ctx.peaks, True) / t
