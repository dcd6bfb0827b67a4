"""Decode step: the least time the decode calls in the trace need over
their device time, in %.  Every decode call of these cells is bound by
bytes (``counts.decode_bandwidth_bound``), so the sum of per-call least
times is the larger of the summed FLOP and byte bounds."""
from layer import DECODE_PROGRAM


def read(ctx):
    if ctx.trace is None:
        return None
    dev = ctx.trace.program_s.get(DECODE_PROGRAM, 0.0)
    w = ctx.decode_work()
    if dev <= 0 or w.calls == 0:
        return None
    return 100.0 * w.least_seconds(ctx.peak_flops, ctx.peak_bw) / dev
