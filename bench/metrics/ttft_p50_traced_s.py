"""Router + admission, as users feel it: the 50th percentile of the time
to first token over every request the traced run sent, by the rule of
the end-to-end metrics.  It is read per layer because its runs differ
by host and by trajectory more than an end-to-end bound can hold (wave
admission makes it bimodal; PERF.md)."""
from openloop import end_to_end


def read(ctx):
    if not ctx.drive.tracked:
        return None
    return end_to_end(ctx.drive)["ttft_p50_s"]
