"""Group (wave admission + split/fuse): useful tokens per decode
slot-step over the measured window, from the groups' ``ServeStats``, in %."""


def read(ctx):
    u0, s0 = ctx.drive.stats_at["open"]
    u1, s1 = ctx.drive.stats_at["close"]
    if s1 <= s0:
        return None
    return 100.0 * (u1 - u0) / (s1 - s0)
