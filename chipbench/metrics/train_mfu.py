"""Model FLOP utilisation of the trainer's programs in the traced window:
the forward and backward operations of every slot the window ran
(`flops.train_flops_per_token` times the window's tokens) over the device
time of the programs that ran them (`local_scan` and `event_step`,
averaged over the cell's chips) times the chips' bf16 peak.  Host gaps
between the programs do not count; recomputation does not count."""


def read(ctx):
    t = (ctx.traces.module_ns(ctx.trace, ctx.names["local_scan"])
         + ctx.traces.module_ns(ctx.trace, ctx.names["event_step"]))
    if not t:
        return None
    seconds = t / len(ctx.trace.ops) / 1e9
    work = ctx.flops.train_flops_per_token(
        ctx.spec, ctx.traffic["seq_len"]) * ctx.window["tokens"]
    return 100.0 * work / (ctx.chips * seconds
                           * ctx.peaks["bf16_flops_per_s"])
