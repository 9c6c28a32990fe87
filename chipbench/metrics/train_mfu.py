"""Model FLOP utilisation of the trainer's programs in the traced window:
the forward and backward operations of every slot the window ran
(`flops.train_flops_per_token` times the window's tokens) over the device
time of the programs that ran them (local scans, event and dense steps,
told by their scopes as `spans.kind_of` tells them, averaged over the
cell's chips) times the chips' bf16 peak.  Host gaps between the programs
do not count; recomputation does not count."""
import spans


def read(ctx):
    m = spans.of(ctx)
    if m is None:
        return None
    t = ctx.traces.module_ns(ctx.trace, spans.programs(
        m, spans.LOCAL_SCAN, spans.EVENT_STEP, spans.DENSE_STEP))
    if not t:
        return None
    seconds = t / len(ctx.trace.ops) / 1e9
    work = ctx.flops.train_flops_per_token(
        ctx.spec, ctx.traffic["seq_len"]) * ctx.window["tokens"]
    return 100.0 * work / (ctx.chips * seconds
                           * ctx.peaks["bf16_flops_per_s"])
