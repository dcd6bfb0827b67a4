"""The device's idle time put down to what the serving program's host was
doing, from the program's own spans in the profiler trace.

The program writes named host spans into the trace (``repro.obs.spans``:
``fleet.tick`` around each iteration of ``FleetEngine.run``, and inside it
admission, prefill, control, reshard, decode, the host syncs and
telemetry; ``fleet.close`` at the end of every ``run``; ``python.gc``
around each garbage collection).  They sit on the host plane beside the
driver's ``deliver``/``tick``/``stamp`` spans, on the clock ``devtrace``
puts the device planes on.  This module reads them by exact name (the CPU
Python tracer's ``$file:line`` events stay out) and adds to what
``devtrace.reduce`` gives, changing none of its numbers:

* ``span_s``, ``span_n``: host seconds and count per program span that
  starts inside the traced window;
* ``idle_by_span``: device idle seconds inside the window, keyed by the
  innermost span open at each instant, averaged over the devices as
  ``busy_s`` is.  Each idle interval is clipped exactly against the
  timeline of innermost spans, so the values sum to ``window_s - busy_s``;
  time in no span keeps the key ``outside driver spans``;
* ``idle_gaps``: the longest idle gaps, as ``devtrace`` measures and
  orders them, each named by the innermost span (program spans included)
  at its midpoint.

The innermost span at an instant is the shortest one covering it, the
rule ``devtrace`` names its gaps by.

    python3 bench/hostspans.py --workload <cell> --seed <n> --seconds 51

serves a cell as ``run.py --trace 1`` does (the same warm-up, schedule,
driver and traced part of the window) and prints the split of the device's
idle time by host layer, the longest gaps by name, the program spans' time
and counts, host syncs per tick and the ticks of the window; ``--trace 0``
serves with the profiler off and prints the ticks of the window and the
end-to-end metrics.  Serving's correctness is not judged here: that is
``run.py``'s.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import bisect
import collections
import heapq
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import devtrace

PROGRAM_SPANS = (
    "fleet.tick", "fleet.deliver", "fleet.rebalance", "group.admit",
    "group.prefill", "group.prefill_sync", "group.control", "group.reshard",
    "group.decode", "group.decode_sync", "fleet.telemetry", "fleet.close",
    "python.gc")
SYNC_SPANS = ("group.prefill_sync", "group.decode_sync")
OUTSIDE = "outside driver spans"

# The shares of ``device_idle`` by host layer: each is the idle time under
# its spans over the traced window, in %.  The remaining keys of
# ``idle_by_span`` (``fleet.tick`` itself, the driver's spans and
# ``outside driver spans``) make up the rest of ``device_idle``.
IDLE_LAYERS = {
    "admission_idle_pct": ("fleet.deliver", "group.admit", "group.prefill",
                           "group.prefill_sync"),
    "decode_loop_idle_pct": ("group.decode", "group.decode_sync"),
    "control_idle_pct": ("fleet.rebalance", "group.control",
                         "group.reshard"),
    "telemetry_idle_pct": ("fleet.telemetry", "fleet.close"),
    "gc_idle_pct": ("python.gc",),
}

Span = Tuple[str, float, float]                 # (name, start_ns, end_ns)


def program_spans(path: str) -> List[Span]:
    """The program's spans on the host planes of an ``.xplane.pb``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    wanted = set(PROGRAM_SPANS)
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name in wanted]


def load(path: str) -> devtrace.Events:
    """``devtrace.load`` with the program's spans among the host spans
    (the file is read twice: ``devtrace.load`` keeps the driver's only)."""
    ev = devtrace.load(path)
    ev.host.extend(program_spans(path))
    return ev


def timeline(host: List[Span], lo: float, hi: float) -> List[Span]:
    """``[lo, hi)`` cut into segments ``(name, start, end)``, each named by
    the innermost span covering it: the shortest, and of equal ones the
    first to start."""
    spans = sorted((s, e, n) for n, s, e in host if e > s and e > lo
                   and s < hi)
    cuts = sorted({lo, hi} | {x for s, e, _ in spans for x in (s, e)
                              if lo < x < hi})
    out: List[Span] = []
    open_: List[tuple] = []                   # (length, start, end, name)
    k = 0
    for a, b in zip(cuts, cuts[1:]):
        while k < len(spans) and spans[k][0] <= a:
            s, e, n = spans[k]
            heapq.heappush(open_, (e - s, s, e, n))
            k += 1
        while open_ and open_[0][2] <= a:
            heapq.heappop(open_)
        out.append((open_[0][3] if open_ else OUTSIDE, a, b))
    return out


@dataclass
class SpanSplit:
    span_s: Dict[str, float]             # host seconds per program span
    span_n: Dict[str, int]
    idle_by_span: Dict[str, float]       # device idle s, mean over devices
    idle_gaps: List[Tuple[str, float]]


def reduce(ev: devtrace.Events, top: int = 10) -> SpanSplit:
    """The additions to ``devtrace.reduce(ev)`` (``ev`` aligned)."""
    lo, hi = devtrace.window_of(ev)
    if not ev.devices:
        raise ValueError("trace has no TPU device plane")
    names = set(devtrace.HOST_SPANS) | set(PROGRAM_SPANS)
    segs = timeline([h for h in ev.host if h[0] in names], lo, hi)
    starts = [s for _, s, _ in segs]
    span_s: Dict[str, float] = collections.defaultdict(float)
    span_n: Dict[str, int] = collections.Counter()
    for n, s, e in ev.host:
        if n in PROGRAM_SPANS and lo <= s < hi:
            span_s[n] += (e - s) * 1e-9
            span_n[n] += 1
    idle_ns: Dict[str, float] = collections.defaultdict(float)
    gaps: List[Tuple[str, float]] = []
    for dev in ev.devices.values():
        ops = dev.ops or dev.modules
        busy = devtrace.union(devtrace.clip([(s, e) for _, s, e in ops],
                                            lo, hi))
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b <= a:
                continue
            i = bisect.bisect_right(starts, (a + b) / 2) - 1
            gaps.append((segs[i][0], (b - a) * 1e-9))
            i = bisect.bisect_right(starts, a) - 1
            while i < len(segs) and segs[i][1] < b:
                name, s, e = segs[i]
                idle_ns[name] += min(e, b) - max(s, a)
                i += 1
    n = len(ev.devices)
    gaps.sort(key=lambda g: -g[1])
    return SpanSplit(
        span_s=dict(span_s), span_n=dict(span_n),
        idle_by_span={k: v * 1e-9 / n for k, v in idle_ns.items()},
        idle_gaps=gaps[:top])


def idle_shares(split: SpanSplit, window_s: float) -> Dict[str, float]:
    """``IDLE_LAYERS``' shares of the window, in %."""
    return {m: 100.0 * sum(split.idle_by_span.get(s, 0.0) for s in spans)
            / window_s for m, spans in IDLE_LAYERS.items()}


def host_syncs_per_tick(split: SpanSplit, ticks: int) -> Optional[float]:
    """Host readbacks (first tokens of a prefill, tokens of a decode) per
    driver tick of the trace."""
    if ticks <= 0:
        return None
    return sum(split.span_n.get(s, 0) for s in SYNC_SPANS) / ticks


def drive_cell(spec: dict, seed: int, seconds: float, logdir: str,
               trace: bool):
    """Serve a cell as ``run.py`` does, up to the drain's end, with the
    profiler on for the mix's traced part of the window when ``trace``;
    returns the drive's result and the ``run.Tracer`` (or None)."""
    import jax

    import generator
    import openloop
    import run
    import system
    cfg_file, mix = spec["cfg"], spec["mix"]
    sut = system.System(cfg_file, spec["cell"]["chips"])
    fleet = system.fleet_config(cfg_file, generator.ring_window(mix))
    with sut.serving():
        params = jax.block_until_ready(sut.make_params(seed))
        sut.warm_up(params, fleet, mix["prompt_buckets"],
                    cfg_file["vocab_size"])
        eng = sut.make_engine(params, fleet)
        arrivals = generator.schedule(mix, seconds, seed,
                                      cfg_file["vocab_size"])
        tracer = None
        if trace:
            lo, length = mix["trace_window_s"]
            tracer = run.Tracer(logdir, lo, min(lo + length, seconds))
        try:
            res = openloop.drive(eng, arrivals, seconds, mix["drain_s"],
                                 system.make_request,
                                 on_tick=tracer.on_tick if tracer else None,
                                 spans=trace)
        finally:
            if tracer is not None:
                tracer.close()
    return res, tracer


def main(argv=None) -> int:
    import argparse
    import json
    import os
    import shutil
    import types

    import openloop
    import run
    from layer import load_reader
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)
    spec = run.load_cell(args.workload)
    run.require_device(spec["cell"]["chips"])
    import system  # noqa: F401  (puts the program on the path)
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    out_dir = os.path.join(run.ROOT, "chiprun_out", "hostspans")
    logdir = os.path.join(out_dir, f"trace.{args.seed}")
    res, tracer = drive_cell(spec, args.seed, args.seconds, logdir,
                             bool(args.trace))
    line = {"workload": args.workload, "seed": args.seed,
            "trace": args.trace, "sent": len(res.tracked),
            "unfinished": len(openloop.failures(res)), "ticks": len(res.ticks),
            "window_ticks": sum(1 for t in res.ticks
                                if t.t <= args.seconds),
            "queue_wait_p90_s": load_reader(
                run.BENCH_DIR, "queue_wait_p90_traced_s")(
                    types.SimpleNamespace(drive=res))}
    if tracer is None:
        line["end_to_end"] = {k: v for k, v in
                              openloop.end_to_end(res).items()
                              if k != "samples"}
    else:
        ev = devtrace.align(load(devtrace.find_xplane(logdir)))
        shutil.rmtree(logdir, ignore_errors=True)
        red, split = devtrace.reduce(ev), reduce(ev)
        traced = res.ticks[tracer.ticks[0]:tracer.ticks[1]]
        line.update(
            traced_ticks=len(traced), window_s=red.window_s,
            busy_s=red.busy_s,
            device_idle=100.0 * (1.0 - red.busy_s / red.window_s),
            **idle_shares(split, red.window_s),
            rest_idle_pct={k: 100.0 * v / red.window_s
                           for k, v in split.idle_by_span.items()
                           if not any(k in s for s in IDLE_LAYERS.values())},
            host_syncs_per_tick=host_syncs_per_tick(split, len(traced)),
            idle_gaps=split.idle_gaps, driver_idle_gaps=red.idle_gaps,
            span_s=split.span_s, span_n=split.span_n,
            idle_by_span=split.idle_by_span,
            program_calls=red.program_calls)
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"{args.workload}.{args.seed}."
                           f"{args.trace}.json"), "w") as f:
        json.dump(line, f, indent=1)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
