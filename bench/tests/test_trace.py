"""The trace reducer: union of busy intervals, idle share, per-program
device time and idle gaps named by the host span, on events written by
hand and on a trace recorded on a TPU v5e (``bench/testdata``)."""
import os

import pytest

import devtrace as tr

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA = os.path.join(BENCH, "testdata", "v5e_two_programs.xplane.pb")


def _events():
    # window 0..100 ns; ops overlap inside each program
    dev = tr.DeviceEvents(
        ops=[("fusion.1", 10, 30), ("fusion.2", 25, 40),
             ("dot.3", 60, 90),
             ("dot.3", 95, 120)],                      # runs past the end
        modules=[("jit_decode_step", 10, 40), ("jit_prefill", 60, 90),
                 ("jit_prefill", 95, 120), ("jit_prefill", -20, 5)])
    host = [("traced", 0, 100), ("tick", 0, 50), ("stamp", 50, 58),
            ("tick", 58, 100), ("deliver", 41, 49)]
    return tr.Events(devices={"/device:TPU:0": dev}, host=host)


def test_union_and_clip():
    assert tr.union([(5, 9), (0, 3), (2, 4), (9, 10)]) == [(0, 4), (5, 10)]
    assert tr.clip([(0, 4), (5, 10)], 3, 7) == [(3, 4), (5, 7)]


def test_reduce_by_hand():
    red = tr.reduce(_events())
    assert red.window_s == pytest.approx(100e-9)
    # busy: 10..40, 60..90, 95..100 -> 65 ns
    assert red.busy_s == pytest.approx(65e-9)
    # a program counts when it starts inside the window
    assert red.program_calls == {"jit_decode_step": 1, "jit_prefill": 2}
    assert red.program_s["jit_prefill"] == pytest.approx(55e-9)
    assert red.top_ops[0] == ("jit_prefill/dot.3", pytest.approx(35e-9))
    # gaps: 0..10 (tick), 40..60 (midpoint 50: stamp), 90..95 (tick)
    assert red.idle_gaps[0] == ("stamp", pytest.approx(20e-9))
    assert [g[0] for g in red.idle_gaps] == ["stamp", "tick", "tick"]


def test_innermost_span_names_the_gap():
    ev = _events()
    ev.devices["/device:TPU:0"].ops = [("x", 0, 42), ("x", 48, 100)]
    red = tr.reduce(ev)
    assert red.idle_gaps == [("deliver", pytest.approx(6e-9))]


def test_no_window_span_is_an_error():
    ev = _events()
    ev.host = [h for h in ev.host if h[0] != "traced"]
    with pytest.raises(ValueError):
        tr.reduce(ev)


def test_program_name():
    assert tr.program_name("jit_decode_step(42)") == "jit_decode_step"
    assert tr.program_name("jit_prefill") == "jit_prefill"


def test_op_name():
    assert tr.op_name("%fusion.12 = bf16[8]{0} fusion(%a), kind=kLoop") \
        == "fusion.12"


def test_recorded_v5e_trace():
    """Three ticks, each running a jitted ``decode_step`` and a jitted
    ``prefill`` (a small matmul each), recorded on one v5e chip."""
    ev = tr.align(tr.load(DATA))
    assert list(ev.devices) == ["/device:TPU:0"]
    lo, hi = tr.window_of(ev)
    dev = ev.devices["/device:TPU:0"]
    assert lo <= min(s for _, s, _ in dev.ops + dev.modules)
    assert max(e for _, _, e in dev.ops + dev.modules) <= hi
    red = tr.reduce(ev)
    assert red.program_calls == {"jit_decode_step": 3, "jit_prefill": 3}
    module_s = sum(red.program_s.values())
    assert 0 < module_s <= red.busy_s * 1.001
    assert red.busy_s <= red.window_s
    assert red.busy_s < 1.2 * module_s      # nothing else ran on the chip
    assert all(name.startswith(("jit_decode_step/", "jit_prefill/"))
               for name, _ in red.top_ops)
    assert {g[0] for g in red.idle_gaps} <= {"tick", "outside driver spans"}


def test_align_moves_early_device_events_into_the_window():
    ev = _events()
    dev = ev.devices["/device:TPU:0"]
    ev.devices["/device:TPU:0"] = tr.DeviceEvents(
        ops=[(n, s - 15, e - 15) for n, s, e in dev.ops[:3]],
        modules=[(n, s - 15, e - 15) for n, s, e in dev.modules[:2]])
    al = tr.align(ev).devices["/device:TPU:0"]
    assert min(s for _, s, _ in al.ops + al.modules) == 0
    assert [o[1] for o in al.ops] == [0, 15, 50]


def _decode_trace(planes: int) -> tr.Events:
    """Two decode launches and a prefill in a 0..1000 ns window.  On one
    plane each takes its whole time; split over ``planes`` chips, each
    plane shows every launch at its start, taking its share of the time."""
    one = [("jit_decode_step", 100, 500), ("jit_prefill", 520, 600),
           ("jit_decode_step", 600, 1000)]
    devices = {}
    for p in range(planes):
        mods = [(n, s, s + (e - s) / planes) for n, s, e in one]
        devices[f"/device:TPU:{p}"] = tr.DeviceEvents(
            ops=[("fusion.1", s, e) for _, s, e in mods], modules=mods)
    return tr.Events(devices=devices, host=[("traced", 0, 1000),
                                            ("tick", 0, 1000)])


def test_four_planes_count_launches_and_keep_the_decode_readings():
    import counts
    from conftest import load_config
    from layer import LayerContext, load_reader
    from openloop import DriveResult, TickRecord
    shape = counts.Shape.from_config(load_config("qwen3-14b"))
    ticks = [TickRecord(t=0.0, queue=0, prefill=[], decode_ctx=[300, 40]),
             TickRecord(t=0.0, queue=0, prefill=[], decode_ctx=[301, 41])]
    drive = DriveResult(tracked=[], ticks=ticks, window_s=1.0, end_s=1.0,
                        stats_at={}, reconfigs=0)
    read = {}
    for planes in (1, 4):
        red = tr.reduce(_decode_trace(planes))
        assert red.devices == planes
        assert red.program_calls == {"jit_decode_step": 2, "jit_prefill": 1}
        assert red.program_s["jit_decode_step"] == pytest.approx(800e-9)
        ctx = LayerContext(drive=drive, shape=shape, peak_flops=197e12,
                           peak_bw=819e9, chips=planes, window_compiles=0,
                           trace=red, traced_ticks=slice(0, 2))
        read[planes] = {m: load_reader(BENCH, m)(ctx)
                        for m in ("decode_roofline", "decode_call_ms")}
    assert read[4]["decode_call_ms"] == pytest.approx(400e-6)
    for m in read[1]:
        assert read[4][m] == pytest.approx(read[1][m], rel=1e-12)
