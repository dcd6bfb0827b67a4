"""Wall-clock spans of the serving program, written into the profiler's trace.

Decisions go to :class:`~repro.obs.events.EventLog`, stamped in ticks; wall
time goes here.  :func:`span` is a ``jax.profiler.TraceAnnotation``: while a
profiler session is active it records a host span under ``name``, with each
of ``ids`` (a group's ``gid``, a part's ``part``) as a stat of the event and
never in its name, and the profiler writes the spans out with the device's
planes when the trace stops, on the same host clock.  With no session active
a span costs one inactive ``TraceMe``, so the program has no switch for them.

Span names (the layer each one times):

* ``fleet.tick`` — one iteration of ``FleetEngine.run``'s loop; every span
  below except ``fleet.close`` (and ``python.gc``) opens inside one;
* ``fleet.deliver`` — routing and queueing of due arrivals (router +
  admission);
* ``fleet.rebalance`` — the chip-level controller and its plans' execution
  (control plane);
* ``group.admit`` — one group's per-part retire and admission waves (router
  + admission), around each wave's ``group.prefill`` per prompt length
  (token upload, prefill dispatch, argmax) and its ``group.prefill_sync``
  (the readback of the first tokens);
* ``group.control`` — the controller's features and ``observe`` (control
  plane);
* ``group.reshard`` — the KV re-partition of a split or fuse (KV reshard);
* ``group.decode`` — the tick's one decode call over the slot pool, its
  argmax and row mask, and the token appends (decode step, host loop),
  around its ``group.decode_sync`` (the readback);
* ``fleet.telemetry`` — per-tick telemetry and metrics sampling;
* ``fleet.close`` — the end of every ``run`` call: finalize and summary;
* ``python.gc`` — each collection of the interpreter's garbage collector,
  with its ``generation`` (:func:`install_gc_spans`).
"""
from __future__ import annotations

import gc

from jax.profiler import TraceAnnotation


def span(name: str, **ids) -> TraceAnnotation:
    """A host span named ``name``; use as ``with span("group.decode",
    gid=0, part=1):``."""
    return TraceAnnotation(name, **ids)


class GcSpans:
    """A ``gc.callbacks`` hook that opens a ``python.gc`` span when a
    collection starts and closes it when the collection stops."""

    def __init__(self):
        self._open = None

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._open = span("python.gc", generation=info["generation"])
            self._open.__enter__()
        elif self._open is not None:
            self._open.__exit__(None, None, None)
            self._open = None


def install_gc_spans() -> None:
    """Time every garbage collection of this process as a ``python.gc``
    span.  Idempotent: the hook is installed once per process."""
    if not any(isinstance(cb, GcSpans) for cb in gc.callbacks):
        gc.callbacks.append(GcSpans())
