"""Router + admission: the 90th percentile, by nearest rank, of the time
each request the traced run sent waited between ``FleetEngine.submit`` and
the admission wave that first took it (the program's ``submitted_s`` and
``admitted_s`` stamps on ``Request``, host clock), in s.  A request never
admitted counts from its due time to the drain's end.  Nothing to read
where the program does not stamp its requests."""
from openloop import nearest_rank


def read(ctx):
    waits = []
    for tr in ctx.drive.tracked:
        sub = getattr(tr.req, "submitted_s", None)
        if sub is None:
            return None
        adm = tr.req.admitted_s
        waits.append(adm - sub if adm is not None
                     else ctx.drive.end_s - tr.arrival.due_s)
    return nearest_rank(waits, 0.90) if waits else None
