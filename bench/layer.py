"""What the per-layer readers (``bench/metrics/<name>.py``) read.

Each reader is a module with ``read(ctx: LayerContext) -> float | None``.
It returns None where it finds nothing to read, and the harness then
leaves the metric out of the result line.  A share of a roofline or of a
peak is never returned as 0 in place of nothing.

On a cell of several chips the numbers are put together so:

- work counts (``ctx.shape``: FLOPs and bytes) are of the whole model;
- device time (``trace.program_s``, ``trace.busy_s``'s sum) is summed over
  the cell's chips;
- a program's calls (``trace.program_calls``) are its launches: one per
  execution across the chips, however many planes show it.

A roofline share, least time of the whole model's work at one chip's
peak over device time summed over the chips, is then the share of each
chip's own peak; ``serve_mfu`` divides by window x chips x peak.  On one
chip every number is what it is without this rule.
"""
from __future__ import annotations

import importlib.util
import os
from dataclasses import dataclass
from typing import List, Optional

from counts import Work
from openloop import DriveResult, TickRecord
from devtrace import Reduced

PREFILL_PROGRAM = "jit_prefill"
DECODE_PROGRAM = "jit_decode_step"


@dataclass
class LayerContext:
    drive: DriveResult
    shape: object                     # the architecture's ``Shape``
    peak_flops: float
    peak_bw: float
    chips: int
    window_compiles: int
    trace: Optional[Reduced] = None
    traced_ticks: Optional[slice] = None    # the ticks inside the trace

    def window_ticks(self) -> List[TickRecord]:
        return [t for t in self.drive.ticks if t.t <= self.drive.window_s]

    def traced(self) -> List[TickRecord]:
        if self.trace is None or self.traced_ticks is None:
            return []
        return self.drive.ticks[self.traced_ticks]

    def prefill_work(self) -> Work:
        w = Work()
        for t in self.traced():
            for rows, plen in t.prefill:
                w.add(self.shape.prefill_call(rows, plen))
        return w

    def decode_work(self) -> Work:
        """Per-row decode work of the traced ticks plus one weight read per
        decode launch the trace shows."""
        ctx = [c for t in self.traced() for c in t.decode_ctx]
        w = self.shape.decode_rows(ctx)
        w.calls = self.trace.program_calls.get(DECODE_PROGRAM, 0) \
            if self.trace else 0
        w.bytes += w.calls * self.shape.weight_bytes_per_call
        return w


def load_reader(bench_dir: str, name: str):
    path = os.path.join(bench_dir, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
