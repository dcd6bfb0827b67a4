"""Reduction of a profiler trace to device busy time, per-program device
time, the top device operations and the longest idle gaps.

The trace is JAX's ``.xplane.pb``, read with ``jax.profiler.ProfileData``.
Device planes are those named ``/device:TPU:<n>``; on each, the ``XLA
Ops`` line holds every operation that ran and the ``XLA Modules`` line
every program execution (a jitted function's module is named
``jit_<function>``, with a suffix in parentheses on some versions).  The
host's spans that the driver writes with ``jax.profiler.TraceAnnotation``
(``deliver``, ``tick``, ``stamp``) and the ``traced`` span that covers the
traced window sit on the host plane.

Device events are first put on the host's clock (``align``).  Busy time
is the union of the operation intervals inside the traced window; the idle share is one minus busy over the window.  Each idle gap
is named by the host span that covers its midpoint.

On several chips busy time is averaged over the device planes and a
program's device time is summed over them.  One launch of a program over a
mesh shows once on every plane it runs on, so its launches are its count
on the plane that shows it most.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "traced"
HOST_SPANS = ("deliver", "tick", "stamp")

Interval = Tuple[float, float]          # (start_ns, end_ns)


@dataclass
class DeviceEvents:
    ops: List[Tuple[str, float, float]] = field(default_factory=list)
    modules: List[Tuple[str, float, float]] = field(default_factory=list)


@dataclass
class Events:
    devices: Dict[str, DeviceEvents]
    host: List[Tuple[str, float, float]]        # (span name, start, end)


def find_xplane(logdir: str) -> str:
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return max(paths, key=os.path.getmtime)


def program_name(module: str) -> str:
    """``jit_decode_step(42)`` -> ``jit_decode_step``."""
    return module.split("(")[0].strip()


def op_name(text: str) -> str:
    """An operation's event is named by its HLO text,
    ``%fusion.12 = bf16[...] fusion(...)``: keep ``fusion.12``."""
    return text.split(" = ", 1)[0].lstrip("%").strip()


def load(path: str) -> Events:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[str, DeviceEvents] = {}
    host: List[Tuple[str, float, float]] = []
    wanted = set(HOST_SPANS) | {WINDOW_SPAN}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and \
                plane.name[len("/device:TPU:"):].isdigit():
            dev = DeviceEvents()
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for ev in line.events:
                        dev.ops.append((op_name(ev.name), ev.start_ns,
                                        ev.start_ns + ev.duration_ns))
                elif line.name == "XLA Modules":
                    for ev in line.events:
                        dev.modules.append((program_name(ev.name),
                                            ev.start_ns,
                                            ev.start_ns + ev.duration_ns))
            devices[plane.name] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in wanted:
                        host.append((ev.name, ev.start_ns,
                                     ev.start_ns + ev.duration_ns))
    return Events(devices=devices, host=host)


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def window_of(ev: Events) -> Interval:
    spans = [(s, e) for n, s, e in ev.host if n == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"trace has no '{WINDOW_SPAN}' span")
    return min(s for s, _ in spans), max(e for _, e in spans)


@dataclass
class Reduced:
    window_s: float
    busy_s: float                        # mean over the devices
    program_s: Dict[str, float]          # device seconds, summed over planes
    program_calls: Dict[str, int]        # launches
    top_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]
    devices: int


def align(ev: Events) -> Events:
    """Shift each device's events onto the host's clock.

    The device planes' timestamps can sit a millisecond or so off the
    host's.  The driver starts and stops the profiler between ticks, after
    the device has finished, so every device event belongs inside the
    ``traced`` span: the shift is the least that puts them there."""
    lo, hi = window_of(ev)
    out = {}
    for name, dev in ev.devices.items():
        evs = dev.ops + dev.modules
        if not evs:
            out[name] = dev
            continue
        first = min(s for _, s, _ in evs)
        last = max(e for _, _, e in evs)
        d = lo - first if first < lo else (hi - last if last > hi else 0.0)
        shift = lambda xs: [(n, s + d, e + d) for n, s, e in xs]
        out[name] = DeviceEvents(ops=shift(dev.ops),
                                 modules=shift(dev.modules))
    return Events(devices=out, host=ev.host)


def reduce(ev: Events, top: int = 10) -> Reduced:
    lo, hi = window_of(ev)
    if not ev.devices:
        raise ValueError("trace has no TPU device plane")
    busy_total = 0.0
    prog_s: Dict[str, float] = collections.defaultdict(float)
    prog_n: Dict[str, int] = collections.Counter()
    op_s: Dict[str, float] = collections.defaultdict(float)
    gaps: List[Tuple[str, float]] = []
    host = sorted((s, e, n) for n, s, e in ev.host if n in HOST_SPANS)
    for dev in ev.devices.values():
        ops = dev.ops or dev.modules
        busy = union(clip([(s, e) for _, s, e in ops], lo, hi))
        busy_total += sum(e - s for s, e in busy)
        here: Dict[str, int] = collections.Counter()
        for name, s, e in dev.modules:
            # a program counts when it starts inside the window
            if lo <= s < hi:
                prog_s[name] += (e - s) * 1e-9
                here[name] += 1
        for name, k in here.items():
            prog_n[name] = max(prog_n[name], k)
        mods = sorted((s, e, name) for name, s, e in dev.modules)
        starts = [m[0] for m in mods]
        for name, s, e in dev.ops:
            if e > lo and s < hi:
                i = bisect.bisect_right(starts, s) - 1
                if i >= 0 and mods[i][0] <= s < mods[i][1]:
                    name = f"{mods[i][2]}/{name}"
                op_s[name] += (min(e, hi) - max(s, lo)) * 1e-9
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((_host_at(host, (a + b) / 2), (b - a) * 1e-9))
    n = len(ev.devices)
    gaps.sort(key=lambda g: -g[1])
    return Reduced(
        window_s=(hi - lo) * 1e-9, busy_s=busy_total * 1e-9 / n,
        program_s=dict(prog_s), program_calls=dict(prog_n),
        top_ops=sorted(op_s.items(), key=lambda kv: -kv[1])[:top],
        idle_gaps=gaps[:top], devices=n)


def _host_at(host: List[Tuple[float, float, str]], t: float) -> str:
    """Name of the innermost driver span covering time ``t``."""
    best: Optional[Tuple[float, str]] = None
    for s, e, n in host:
        if s > t:
            break
        if s <= t < e and (best is None or e - s < best[0]):
            best = (e - s, n)
    return best[1] if best else "outside driver spans"
