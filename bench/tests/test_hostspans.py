"""Device idle time put down to the program's host spans (``hostspans``):
on events written by hand, on the trace recorded on a TPU v5e
(``bench/testdata``), and on a traced run of the driver at a CPU size."""
import os
import types

import pytest

import devtrace as tr
import hostspans as hs
import run
from conftest import tiny_spec
from layer import load_reader
from test_openloop import BIG_SEED

DATA = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "testdata", "v5e_two_programs.xplane.pb")
SAME = ("window_s", "busy_s", "program_s", "program_calls", "top_ops",
        "devices")


def _events(program: bool = True):
    # window 0..100 ns; busy 10..40, 60..90, 95..100; idle 0..10, 40..60,
    # 90..95
    dev = tr.DeviceEvents(
        ops=[("fusion.1", 10, 30), ("fusion.2", 25, 40), ("dot.3", 60, 90),
             ("dot.3", 95, 120)],
        modules=[("jit_decode_step", 10, 40), ("jit_prefill", 60, 90),
                 ("jit_prefill", 95, 120)])
    host = [("traced", 0, 100), ("tick", 0, 50), ("stamp", 50, 58),
            ("tick", 58, 100), ("deliver", 41, 49)]
    if program:
        host += [("fleet.tick", 1, 48), ("group.decode", 2, 12),
                 ("group.decode_sync", 5, 8), ("python.gc", 44, 46),
                 ("fleet.close", 92, 99)]
    return tr.Events(devices={"/device:TPU:0": dev}, host=host)


def test_gap_is_named_by_its_innermost_program_span():
    split = hs.reduce(_events())
    assert [g[0] for g in split.idle_gaps] == \
        ["stamp", "group.decode_sync", "fleet.close"]
    assert [g[1] for g in split.idle_gaps] == \
        pytest.approx([20e-9, 10e-9, 5e-9])


def test_gaps_are_split_exactly_between_spans():
    split = hs.reduce(_events())
    want = {"tick": 6, "fleet.tick": 2, "group.decode": 5,
            "group.decode_sync": 3, "deliver": 6, "python.gc": 2,
            "stamp": 8, "fleet.close": 3}
    assert split.idle_by_span == pytest.approx(
        {k: v * 1e-9 for k, v in want.items()})
    red = tr.reduce(_events())
    assert sum(split.idle_by_span.values()) == \
        pytest.approx(red.window_s - red.busy_s)
    # the driver's own deliver span is no program layer's
    assert hs.idle_shares(split, red.window_s) == pytest.approx(
        {"admission_idle_pct": 0.0, "decode_loop_idle_pct": 8.0,
         "control_idle_pct": 0.0, "telemetry_idle_pct": 3.0,
         "gc_idle_pct": 2.0})


def test_span_time_and_counts_start_inside_the_window():
    ev = _events()
    ev.host += [("group.decode_sync", -9, -2), ("group.decode_sync", 98, 140)]
    split = hs.reduce(ev)
    assert split.span_n == {"fleet.tick": 1, "group.decode": 1,
                            "group.decode_sync": 2, "python.gc": 1,
                            "fleet.close": 1}
    assert split.span_s["group.decode_sync"] == pytest.approx(45e-9)
    assert hs.host_syncs_per_tick(split, 4) == 0.5
    assert hs.host_syncs_per_tick(split, 0) is None


def test_driver_spans_alone_name_gaps_as_devtrace_does():
    for ev in (_events(program=False), tr.align(tr.load(DATA))):
        assert hs.reduce(ev).idle_gaps == tr.reduce(ev).idle_gaps


def test_timeline_takes_the_shortest_then_the_first():
    segs = hs.timeline([("a", 0, 10), ("b", 5, 15), ("c", 6, 8)], 0, 20)
    assert segs == [("a", 0, 5), ("a", 5, 6), ("c", 6, 8), ("a", 8, 10),
                    ("b", 10, 15), (hs.OUTSIDE, 15, 20)]


@pytest.mark.parametrize("source", ["by hand", "v5e"])
def test_devtrace_numbers_do_not_move_with_program_spans(source):
    if source == "by hand":
        bare, full = _events(program=False), _events()
    else:
        bare = tr.align(tr.load(DATA))
        full = tr.align(hs.load(DATA))
        lo, hi = tr.window_of(full)
        full.host += [("fleet.tick", lo, hi), ("group.decode", lo, lo + 1e6)]
    a, b = tr.reduce(bare), tr.reduce(full)
    for name in SAME:
        assert getattr(a, name) == getattr(b, name), name
    red, split = tr.reduce(full), hs.reduce(full)
    assert sum(split.idle_by_span.values()) == \
        pytest.approx(red.window_s - red.busy_s, rel=1e-9)


def test_traced_cpu_run_records_the_program_spans(tmp_path):
    """The driver's traced part of the window, at a CPU size: the host
    plane holds the engine's spans under their plain names, and the
    requests carry the program's admission stamps."""
    spec = tiny_spec()
    spec["mix"]["trace_window_s"] = [0.2, 1.0]
    logdir = str(tmp_path / "trace")
    res, tracer = hs.drive_cell(spec, BIG_SEED, 2.0, logdir, trace=True)
    assert tracer.ticks[0] is not None and tracer.ticks[1] is not None
    names = {n for n, _, _ in hs.program_spans(tr.find_xplane(logdir))}
    assert {"fleet.tick", "fleet.deliver", "group.admit", "group.decode",
            "group.decode_sync", "group.control", "fleet.telemetry",
            "fleet.close"} <= names
    read = load_reader(run.BENCH_DIR, "queue_wait_p90_traced_s")
    wait = read(types.SimpleNamespace(drive=res))
    assert wait is not None and 0.0 <= wait < 2.0 + 60.0
