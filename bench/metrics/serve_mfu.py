"""Whole step: model FLOPs of every prefill and decode token processed in
the traced window over (window seconds x chips x peak bf16 FLOP/s), in %."""


def read(ctx):
    if ctx.trace is None or ctx.trace.window_s <= 0:
        return None
    flops = ctx.prefill_work().flops + ctx.decode_work().flops
    if flops <= 0:
        return None
    return 100.0 * flops / (ctx.trace.window_s * ctx.chips * ctx.peak_flops)
