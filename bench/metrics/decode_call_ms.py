"""Decode step: mean device time of one decode call in the trace, ms.  On
a cell of several chips a call's device time is summed over its chips and
each launch counts once (``layer.py``)."""
from layer import DECODE_PROGRAM


def read(ctx):
    if ctx.trace is None:
        return None
    n = ctx.trace.program_calls.get(DECODE_PROGRAM, 0)
    if n == 0:
        return None
    return 1000.0 * ctx.trace.program_s[DECODE_PROGRAM] / n
