"""Router + admission: requests waiting in the groups' queues, averaged
over the ticks of the measured window (sampled after each tick)."""


def read(ctx):
    ticks = ctx.window_ticks()
    if not ticks:
        return None
    return sum(t.queue for t in ticks) / len(ticks)
