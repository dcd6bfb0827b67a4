"""Prefill: the least time the prefill calls in the trace need (from
the shape's ``prefill_call``: the larger of FLOPs over peak and bytes over
bandwidth, call by call) over their device time, in %."""
from layer import PREFILL_PROGRAM


def read(ctx):
    if ctx.trace is None:
        return None
    dev = ctx.trace.program_s.get(PREFILL_PROGRAM, 0.0)
    if dev <= 0:
        return None
    least = 0.0
    for t in ctx.traced():
        for rows, plen in t.prefill:
            least += ctx.shape.prefill_call(rows, plen).least_seconds(
                ctx.peak_flops, ctx.peak_bw)
    if least <= 0:
        return None
    return 100.0 * least / dev
