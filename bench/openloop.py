"""The open-loop driver: delivers a schedule to ``FleetEngine`` on the wall
clock and stamps every token on the host.

It uses only the engine's public surface: ``submit``, ``run`` with
``max_ticks=eng.wall + 1`` (one tick per call; the engine resumes a run
after a ``max_ticks`` cutoff), ``groups`` with each group's ``queue``,
``stats``, ``ways`` and ``part_live``, and the ``Request`` objects.

Each loop iteration:

1. deliver: every request whose due time has passed goes to ``submit``
   with ``arrival = eng.wall``;
2. tick: one ``run`` call;
3. stamp: each request that gained tokens in the tick gets them stamped
   with the clock at the tick's return (the argmax is on the host by then:
   the engine reads it back with ``np.asarray``).

When nothing is live and nothing is due, it sleeps until the next due
time.  Arrivals stop at the window's end; every request sent is then
served to completion, up to the drain limit.

Work is attributed per tick for the rooflines: the requests that got
their first token in a tick are the tick's prefill rows, grouped into
calls by (group, prompt length); the others that gained a token are
decode rows, with the number of positions each one's new token attended
to.  Reconfigurations are seen as a change of a group's ``splits`` or
``fuses`` count; the requests then live in that group crossed it.
"""
from __future__ import annotations

import collections
import contextlib
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import numpy as np

from generator import Arrival


@dataclass
class Tracked:
    arrival: Arrival
    req: object                     # the engine's Request
    stamps: List[float] = field(default_factory=list)
    group: Optional[int] = None
    crossed_reconfig: bool = False

    @property
    def done(self) -> bool:
        return len(self.req.generated) >= self.req.max_new_tokens


@dataclass
class TickRecord:
    t: float                        # seconds since the window opened
    queue: int                      # queued requests after the tick
    prefill: List[tuple]            # (rows, prompt_len) per call
    decode_ctx: List[int]           # positions attended per decode row


@dataclass
class DriveResult:
    tracked: List[Tracked]
    ticks: List[TickRecord]
    window_s: float
    end_s: float                    # when the drain ended
    stats_at: Dict[str, tuple]      # "open"/"close": (useful, slot_steps)
    reconfigs: int


def _stats(eng) -> tuple:
    return (sum(g.stats.useful_tokens for g in eng.groups),
            sum(g.stats.slot_steps for g in eng.groups),
            sum(g.stats.splits + g.stats.fuses for g in eng.groups))


def _span(name: str, on: bool):
    if not on:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)


def drive(eng, arrivals: List[Arrival], seconds: float, drain_s: float,
          make_request: Callable, clock: Callable[[], float] = time.perf_counter,
          sleep: Callable[[float], None] = time.sleep,
          on_tick: Optional[Callable[[float], None]] = None,
          spans: bool = False) -> DriveResult:
    """Serve ``arrivals`` (sorted by due time) open loop.

    ``make_request(arrival, wall)`` builds the engine's request.
    ``on_tick(t)`` is called after every tick with the window time (the
    traced run starts and stops the profiler from it).
    """
    tracked: List[Tracked] = []
    live: List[Tracked] = []
    ticks: List[TickRecord] = []
    stats_at: Dict[str, tuple] = {}
    deadline = seconds + drain_s
    k = 0
    t0 = clock()
    stats_at["open"] = _stats(eng)[:2]
    while True:
        now = clock() - t0
        if "close" not in stats_at and now >= seconds:
            stats_at["close"] = _stats(eng)[:2]
        if now >= deadline:
            break
        with _span("deliver", spans):
            while k < len(arrivals) and arrivals[k].due_s <= now \
                    and arrivals[k].due_s < seconds:
                a = arrivals[k]
                r = make_request(a, eng.wall)
                eng.submit([r])
                tr = Tracked(a, r)
                tracked.append(tr)
                live.append(tr)
                k += 1
        if not live:
            if k >= len(arrivals) or arrivals[k].due_s >= seconds:
                break
            sleep(max(0.0, arrivals[k].due_s - (clock() - t0)))
            continue
        before = [len(tr.req.generated) for tr in live]
        reconf_before = [g.stats.splits + g.stats.fuses for g in eng.groups]
        with _span("tick", spans):
            eng.run(max_ticks=eng.wall + 1)
        t = clock() - t0
        with _span("stamp", spans):
            rec = _stamp(eng, live, before, t)
            reconf_after = [g.stats.splits + g.stats.fuses
                            for g in eng.groups]
            for gi, (a, b) in enumerate(zip(reconf_before, reconf_after)):
                if b != a:
                    for tr in live:
                        if tr.group == gi and not tr.done:
                            tr.crossed_reconfig = True
            live = [tr for tr in live if not tr.done]
        ticks.append(rec)
        if on_tick is not None:
            on_tick(t)
    end = clock() - t0
    stats_at.setdefault("close", _stats(eng)[:2])
    return DriveResult(tracked=tracked, ticks=ticks, window_s=seconds,
                       end_s=end, stats_at=stats_at,
                       reconfigs=_stats(eng)[2])


def _locate(eng, tr: Tracked) -> Optional[int]:
    """The group a request was routed to (read once, when it is first
    seen in a group's parts)."""
    if tr.group is not None:
        return tr.group
    for gi, g in enumerate(eng.groups):
        for i in range(g.ways):
            if any(r is tr.req for r in g.part_live(i)):
                tr.group = gi
                return gi
    return None


def _stamp(eng, live: List[Tracked], before: List[int],
           t: float) -> TickRecord:
    prefill: Dict[tuple, int] = collections.Counter()
    decode_ctx: List[int] = []
    for tr, n0 in zip(live, before):
        n1 = len(tr.req.generated)
        if n1 == n0:
            continue
        tr.stamps.extend([t] * (n1 - n0))
        plen = len(tr.req.prompt)
        first_decode = n0 + 1
        if n0 == 0:
            gi = _locate(eng, tr)
            prefill[(gi, plen)] += 1
            first_decode = 2
        # the j-th token (1-based, j >= 2) came from a decode step whose
        # input sat at position plen + j - 2: it attended plen + j - 1
        for j in range(max(first_decode, 2), n1 + 1):
            decode_ctx.append(plen + j - 1)
    queue = sum(len(g.queue) for g in eng.groups)
    return TickRecord(t=t, queue=queue,
                      prefill=[(n, plen) for (_, plen), n in prefill.items()],
                      decode_ctx=decode_ctx)


# -- end-to-end metrics --------------------------------------------------------

def nearest_rank(values, q: float) -> float:
    """The q-quantile by nearest rank: the smallest value with at least
    q of the sample at or below it."""
    v = np.sort(np.asarray(values, np.float64))
    if v.size == 0:
        raise ValueError("empty sample")
    return float(v[max(0, int(np.ceil(q * v.size)) - 1)])


def end_to_end(res: DriveResult) -> dict:
    """TTFT and inter-token gaps over every request sent in the window
    (a request that never got its first token counts at the drain's end,
    and gives no gaps it does not have), and output tokens produced inside
    the window per second."""
    ttft, gaps = [], []
    in_window = 0
    for tr in res.tracked:
        due = tr.arrival.due_s
        ttft.append((tr.stamps[0] if tr.stamps else res.end_s) - due)
        if len(tr.stamps) > 1:
            gaps.extend(np.diff(tr.stamps).tolist())
        in_window += sum(1 for s in tr.stamps if s <= res.window_s)
    return {
        "ttft_p50_s": nearest_rank(ttft, 0.50),
        "ttft_p90_s": nearest_rank(ttft, 0.90),
        "itl_p95_s": nearest_rank(gaps, 0.95) if gaps else None,
        "output_tokens_per_s": in_window / res.window_s,
        "samples": {"requests": len(ttft), "gaps": len(gaps),
                    "tokens_in_window": in_window},
    }


def failures(res: DriveResult) -> List[int]:
    """Requests sent in the window that did not get exactly their
    ``max_new_tokens`` tokens by the drain's end."""
    return [tr.arrival.rid for tr in res.tracked
            if len(tr.req.generated) != tr.req.max_new_tokens]
