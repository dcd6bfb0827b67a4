"""repro.obs: event log, metrics, decision audit, exporters, reports.

Unit tests for the observability pipeline plus integration tests that
run real (vec) fleets with ``FleetConfig(obs=...)`` and assert the
acceptance properties: off-mode summaries carry no obs block, full-mode
traces round-trip through JSONL exactly, and the attribution table
answers "which decision preceded each topology change".
"""
import collections
import json

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import (AmoebaConfig, ClusterConfig, FleetConfig,
                                MigrationConfig)
from repro.control.features import ReplayBuffer
from repro.fleet.scheduler import FleetEngine
from repro.fleet.telemetry import FleetTelemetry, RollingWindow
from repro.fleet.traffic import TenantProfile, imbalanced_trace, make_trace
from repro.obs import (EVENT_KINDS, EventLog, MetricsRegistry, NULL_LOG,
                       attribution_rows, chrome_trace, decision_rows,
                       jsonable, misprediction_rate, read_jsonl,
                       render_attribution, render_mispredictions,
                       render_report, render_timeline, top_mispredictions,
                       verify_replay, write_chrome_trace, write_jsonl)
from repro.obs.metrics import Histogram


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("qwen3-14b", reduced=True)
    return cfg


AMOEBA = AmoebaConfig(split_threshold=0.3, fuse_threshold=0.05,
                      min_phase_steps=2)


def _fleet_cfg(obs, **kw):
    base = dict(num_groups=2, capacity=4, window=64, mode="dynamic",
                router="sticky", engine="vec",
                migrate=MigrationConfig(enabled=True), amoeba=AMOEBA,
                obs=obs)
    base.update(kw)
    return FleetConfig(**base)


def _run(cfg, fc, seed=5, horizon=40):
    eng = FleetEngine(cfg, None, fleet=fc)
    eng.submit(imbalanced_trace(horizon, cfg.vocab_size, seed=seed,
                                shards=fc.num_groups))
    return eng, eng.run()


# -- EventLog ------------------------------------------------------------------

def test_eventlog_off_is_inert():
    log = EventLog(mode="off")
    assert not log.enabled and not log.full
    log.emit("steal", gid=1, rid=7)
    assert log.total == 0 and len(log) == 0
    assert log.counts["steal"] == 0
    assert log is not NULL_LOG and not NULL_LOG.enabled


def test_eventlog_summary_counts_without_retention():
    log = EventLog(mode="summary")
    for _ in range(3):
        log.emit("reconfig", gid=0, to=(2, 2))
    log.emit("steal", gid=1)
    assert log.total == 4
    assert len(log) == 0                      # no ring in summary mode
    assert log.summary() == {
        "mode": "summary", "total_events": 4,
        "by_kind": {"reconfig": 3, "steal": 1}}


def test_eventlog_full_ring_and_payload_normalization():
    log = EventLog(mode="full")
    log.set_tick(9)
    log.emit("reconfig", gid=0, part=1,
             **{"from": (4,), "to": (np.int64(2), np.int64(2)),
                "gain": np.float32(0.25)})
    (e,) = log.events()
    assert (e.seq, e.tick, e.kind, e.gid, e.part) == (1, 9, "reconfig", 0, 1)
    # raw at emit time (hot path stores the dict as-is) ...
    assert e.payload["from"] == (4,)
    # ... tuples -> lists, numpy -> native on first view (JSONL fixed point)
    p = e.as_dict()["payload"]
    assert p["from"] == [4]
    assert p["to"] == [2, 2]
    assert isinstance(p["to"][0], int)
    assert isinstance(p["gain"], float)
    assert e.as_dict() == json.loads(json.dumps(e.as_dict()))
    log.emit("steal", gid=1, tick=11)          # explicit tick wins
    assert log.events("steal")[0].tick == 11
    assert log.count("steal") == 1 and log.total == 2


def test_eventlog_ring_bounded_counters_exact():
    log = EventLog(mode="full", capacity=4)
    for i in range(10):
        log.emit("stall", gid=0, tick=i, remaining=1)
    assert len(log) == 4 and log.total == 10 and log.dropped == 6
    assert [e.tick for e in log.events()] == [6, 7, 8, 9]
    s = log.summary()
    assert s["retained"] == 4 and s["dropped"] == 6
    log.clear()
    assert log.total == 0 and len(log) == 0 and log.dropped == 0


def test_eventlog_rejects_unknown_mode():
    with pytest.raises(ValueError, match="unknown obs mode"):
        EventLog(mode="verbose")


def test_fleet_config_obs_validated(setup):
    cfg = setup
    with pytest.raises(ValueError, match="unknown obs mode"):
        FleetEngine(cfg, None, fleet=_fleet_cfg("loud"))


def test_jsonable_fixed_point():
    v = {"a": (1, np.int32(2)), "b": np.array([1.5, 2.5]),
         "c": [np.float64(0.5), {"d": (np.int64(3),)}]}
    j = jsonable(v)
    assert j == json.loads(json.dumps(j))
    assert j == {"a": [1, 2], "b": [1.5, 2.5], "c": [0.5, {"d": [3]}]}


# -- MetricsRegistry -----------------------------------------------------------

def test_histogram_log2_buckets():
    h = Histogram()
    for v in [0, 1, 2, 3, 4, 9]:
        h.observe(v)
    s = h.snapshot()
    assert s["count"] == 6 and s["min"] == 0 and s["max"] == 9
    # bucket b holds [2^(b-1), 2^b): 0->0, 1->1, {2,3}->2, 4->3, 9->4
    assert s["log2_buckets"] == {"0": 1, "1": 1, "2": 2, "3": 1, "4": 1}
    assert Histogram().snapshot() == {"count": 0}


def test_metrics_registry_sample_fleet():
    class _G:
        def __init__(self, q, live):
            self.queue = [None] * q
            self._live = live

        def live_count(self):
            return self._live

    class _Planner:
        tier_bytes = {"intra": 128, "inter": 64}

    m = MetricsRegistry()
    m.count("x")
    m.count("x", 2)
    m.sample_fleet(7, [_G(3, 2), _G(1, 1)], planner=_Planner())
    snap = m.snapshot()
    assert snap["counters"] == {"x": 3}
    assert snap["gauges"]["fleet.queue_depth"] == 4
    assert snap["gauges"]["fleet.live"] == 3
    assert snap["gauges"]["fleet.tick"] == 7
    assert snap["gauges"]["tier.inter.bytes"] == 64
    assert snap["histograms"]["fleet.live"]["count"] == 1
    assert snap == json.loads(json.dumps(snap))


# -- decision audit ------------------------------------------------------------

def _decision(tick, gid, proba, label, applied=True, seq=1):
    return {"seq": seq, "tick": tick, "kind": "policy_decision", "gid": gid,
            "part": None,
            "payload": {"from": [4], "target": [2, 2], "applied": applied,
                        "proba": proba, "gain": 0.1, "reason": "r",
                        "features": [0.5, 0.5], "replay_idx": seq - 1,
                        "label": label, "label_gain": 0.0}}


def test_decision_rows_and_mispredictions():
    events = [
        _decision(1, 0, proba=0.9, label=0.0, seq=1),   # confident, wrong
        _decision(2, 0, proba=0.6, label=1.0, seq=2),   # right
        _decision(3, 1, proba=0.3, label=1.0, seq=3),   # wrong, less sure
        {"seq": 4, "tick": 3, "kind": "steal", "gid": 1, "part": None,
         "payload": {}},                                 # ignored
    ]
    rows = decision_rows(events)
    assert len(rows) == 3
    assert [r["mispredicted"] for r in rows] == [True, False, True]
    assert misprediction_rate(rows) == pytest.approx(2 / 3)
    worst = top_mispredictions(rows, k=5)
    assert [r["tick"] for r in worst] == [1, 3]          # by confidence desc
    assert worst[0]["confidence"] == pytest.approx(0.4)


def test_decision_rows_unlabeled_kept_but_unscored():
    e = _decision(1, 0, proba=0.9, label=None)
    e["payload"].pop("label")
    e["payload"].pop("replay_idx")
    (row,) = decision_rows([e])
    assert row["mispredicted"] is None and row["confidence"] is None
    assert misprediction_rate([row]) is None


def test_verify_replay_checks_and_skips_evicted():
    replay = ReplayBuffer(maxlen=2)
    idxs = [replay.add(np.zeros(4), float(y)) for y in (1.0, 0.0, 1.0)]
    assert idxs == [0, 1, 2] and replay.total_added == 3
    rows = [{"replay_idx": i, "label": lab}
            for i, lab in zip(idxs, (1.0, 0.0, 1.0))]
    # idx 0 was evicted by the bounded buffer -> skipped, 2 checked
    assert verify_replay(rows, replay) == 2
    rows[2]["label"] = 0.0
    with pytest.raises(AssertionError, match="audit/replay mismatch"):
        verify_replay(rows, replay)


# -- an observed run: summary plumbing + exporters -----------------------------

def test_off_mode_summary_has_no_obs_block(setup):
    _, s = _run(setup, _fleet_cfg("off"))
    assert "obs" not in s
    assert s["completed"] == s["submitted"]


def test_summary_mode_counts_only(setup):
    _, s = _run(setup, _fleet_cfg("summary"))
    obs = s["obs"]
    assert obs["mode"] == "summary" and obs["total_events"] > 0
    assert "retained" not in obs and "metrics" not in obs
    assert obs["by_kind"].keys() <= set(EVENT_KINDS)


def test_off_and_observed_summaries_agree(setup):
    """Turning observability on must not perturb the run itself."""
    _, s_off = _run(setup, _fleet_cfg("off"))
    _, s_full = _run(setup, _fleet_cfg("full"))
    s_full = dict(s_full)
    s_full.pop("obs")
    for s in (s_off, s_full):
        s.pop("wall_s")
        s.pop("ticks_per_sec")
    assert s_off == s_full


def test_full_mode_trace_and_metrics(setup):
    eng, s = _run(setup, _fleet_cfg("full"))
    obs = s["obs"]
    assert obs["mode"] == "full"
    assert obs["retained"] == len(eng.obs.events())
    assert sum(obs["by_kind"].values()) == obs["total_events"]
    m = obs["metrics"]
    assert m["gauges"]["fleet.tick"] == s["wall_ticks"] - 1
    assert m["histograms"]["fleet.queue_depth"]["count"] > 0
    # every event is tick-stamped within the run and well-formed
    for e in eng.obs.events():
        assert e.kind in EVENT_KINDS
        assert 0 <= e.tick < s["wall_ticks"]


def test_jsonl_roundtrip_exact(setup, tmp_path):
    eng, _ = _run(setup, _fleet_cfg("full"))
    path = str(tmp_path / "trace.jsonl")
    n = write_jsonl(path, eng.obs.events(), meta=eng.obs.meta)
    meta, events = read_jsonl(path)
    assert n == len(events) == len(eng.obs.events())
    assert meta == eng.obs.meta
    assert events == [e.as_dict() for e in eng.obs.events()]
    # and the file is the fixed point of parse -> re-serialize
    rebuilt = [json.dumps({"kind": "_meta", **meta}, sort_keys=True)]
    rebuilt += [json.dumps(jsonable(e), sort_keys=True) for e in events]
    with open(path) as f:
        original = [ln.strip() for ln in f if ln.strip()]
    assert original == rebuilt


def test_chrome_trace_structure(setup, tmp_path):
    eng, s = _run(setup, _fleet_cfg("full"))
    trace = chrome_trace(eng.obs.events(), meta=eng.obs.meta)
    evs = trace["traceEvents"]
    by_ph = {}
    for e in evs:
        by_ph.setdefault(e["ph"], []).append(e)
    # thread metadata for every group that emitted
    names = {e["args"]["name"] for e in by_ph["M"]}
    assert {"group 0", "group 1"} <= names
    # topology spans tile [0, wall) per group, in order, no overlap
    for g in (0, 1):
        spans = sorted((e for e in by_ph["X"] if e["tid"] == g),
                       key=lambda e: e["ts"])
        assert spans and spans[0]["ts"] == 0
        for a, b in zip(spans, spans[1:]):
            assert a["ts"] + a["dur"] == b["ts"]
        assert "+" in spans[0]["name"] or spans[0]["name"].isdigit()
    # steal/migrate flows come in s/f pairs sharing an id
    starts = {e["id"] for e in by_ph.get("s", [])}
    ends = {e["id"] for e in by_ph.get("f", [])}
    assert starts and starts == ends
    out = str(tmp_path / "chrome.json")
    assert write_chrome_trace(out, eng.obs.events(), eng.obs.meta) == len(evs)
    with open(out) as f:
        assert json.load(f)["traceEvents"] == evs


def test_attribution_answers_which_decision_preceded_each_reconfig(setup):
    """Acceptance: every applied topology change joins back to the
    policy_decision that caused it, with features/prediction attached."""
    fc = _fleet_cfg("full", amoeba=AMOEBA.replace(policy="online"))
    eng, s = _run(setup, fc, horizon=60)
    rows = attribution_rows(eng.obs.events())
    assert rows, "run produced no reconfigs"
    for r in rows:
        assert r["decision_tick"] is not None
        assert r["decision_tick"] <= r["tick"]
        assert r["proba"] is not None
        assert isinstance(r["features"], list) and r["features"]
        assert r["from"] != r["to"]
    # the decision the reconfig joins to proposed exactly that target
    decisions = {(e.gid, e.tick): e for e in eng.obs.events("policy_decision")}
    for r in rows:
        d = decisions[(r["gid"], r["decision_tick"])]
        assert d.payload["applied"]
        assert d.payload["target"] == r["to"]
    # audit labels cross-check against the live replay buffer
    checked = verify_replay(decision_rows(
        e.as_dict() for e in eng.obs.events()), eng.policy.replay)
    assert checked > 0


def test_text_reports_render(setup):
    eng, _ = _run(setup, _fleet_cfg(
        "full", amoeba=AMOEBA.replace(policy="online")), horizon=60)
    events = eng.obs.events()
    tl = render_timeline(events, limit=10)
    assert len(tl.splitlines()) == 11 and "more events" in tl.splitlines()[-1]
    attr = render_attribution(events)
    assert attr.splitlines()[0].startswith("tick") and "->" in attr
    assert "misprediction rate" in render_mispredictions(events, k=3)
    report = render_report(events, meta=eng.obs.meta, timeline_limit=5)
    for section in ("== meta ==", "== timeline ==",
                    "== decisions preceding each topology change ==",
                    "== top-10 mispredictions =="):
        assert section in report
    assert render_attribution([]) == "(no reconfigs in trace)"
    assert "no labeled decisions" in render_mispredictions([])


def test_cluster_trace_carries_mesh_and_region_events(setup):
    from repro.cluster import ClusterEngine
    from repro.fleet.traffic import multichip_imbalanced_trace
    cfg = setup
    fc = _fleet_cfg("full", num_groups=4, rebalance_every=4,
                    cluster=ClusterConfig(groups_per_chip=2))
    eng = ClusterEngine(cfg, None, fleet=fc)
    eng.submit(multichip_imbalanced_trace(
        40, cfg.vocab_size, seed=5, chips=2, groups_per_chip=2))
    eng.run()
    mesh = eng.obs.meta["mesh"]
    assert mesh["num_groups"] == 4
    assert set(mesh["chip_of"]) == {"0", "1", "2", "3"}   # string keys
    # chips become Perfetto processes
    trace = chrome_trace(eng.obs.events(), meta=eng.obs.meta)
    procs = {e["args"]["name"] for e in trace["traceEvents"]
             if e.get("name") == "process_name"}
    assert procs == {"chip 0", "chip 1"}


# -- telemetry satellites ------------------------------------------------------

def test_rolling_window_push_gap_carries_boundary():
    """Regression: idle gaps must push a flat boundary sample so the
    post-gap rate is computed over the true span, not a stale window."""
    w = RollingWindow(window=10)
    w.push(0, 0.0)
    w.push(4, 40.0)
    w.push_gap(100)                   # idle ticks 5..104: counter is flat
    assert w._samples[-1] == (104, 40.0)
    assert w.rate() == 0.0            # pre-gap samples expired -> flat
    w.push(105, 45.0)
    assert w.rate() == pytest.approx(5.0)
    # no-ops: zero-length gap, and a gap before any sample
    w2 = RollingWindow(window=10)
    w2.push_gap(8)
    assert not w2._samples
    w2.push(0, 1.0)
    w2.push_gap(0)
    assert len(w2._samples) == 1


def test_telemetry_idle_gap_updates_rate_windows():
    class _Stats:
        useful_tokens = 30
        completed = 3

    class _G:
        stats = _Stats()
        queue = ()

    t = FleetTelemetry(window=16)
    t.on_tick(0, [_G()], ticked=1)
    t.on_idle_gap(50, 1)
    assert t.tokens_window._samples[-1] == (50, 30.0)
    assert t.done_window._samples[-1] == (50, 3.0)
    assert t.tokens_window.rate() == 0.0


def _summary_fixture(requests):
    class _Stats:
        ticks = slot_steps = useful_tokens = completed = 0
        splits = fuses = resizes = stall_ticks = 0
        steals_in = steals_out = migrations_in = migrations_out = 0
        leases_out = leases_in = 0
        efficiency = 0.0

    class _G:
        gid, mode, is_split = 0, "fused", False
        queue = ()
        stats = _Stats()

        def live_requests(self):
            return []

    t = FleetTelemetry()
    t.on_tick(0, [_G()], ticked=1)
    return t, [_G()]


def test_summary_single_tenant_has_no_per_tenant_block():
    from repro.serve.engine import Request
    reqs = [Request(rid=i, prompt=[1], max_new_tokens=2, tenant="only")
            for i in range(3)]
    t, groups = _summary_fixture(reqs)
    s = t.summary(groups, reqs)
    assert "per_tenant" not in s
    reqs2 = reqs + [Request(rid=9, prompt=[1], max_new_tokens=2, tenant="b")]
    s2 = t.summary(groups, reqs2)
    assert set(s2["per_tenant"]) == {"only", "b"}


def test_summary_empty_latency_run_is_zero_not_nan():
    t, groups = _summary_fixture([])
    s = t.summary(groups, [])
    assert s["latency"] == {"mean": 0.0, "p50": 0.0, "p95": 0.0,
                            "p99": 0.0, "max": 0.0}
    assert s["completed"] == 0 and s["submitted"] == 0


def test_summary_router_state_spills_plumb_through():
    t, groups = _summary_fixture([])
    s = t.summary(groups, [], router_state={"planner": object(), "spills": 4})
    assert s["control"]["admission_spills"] == 4
    s2 = t.summary(groups, [], router_state={"spills": 4})   # no planner
    assert "admission_spills" not in s2["control"]


# -- wall-clock spans (repro.obs.spans) ---------------------------------------

SPANS = ("fleet.tick", "fleet.deliver", "fleet.rebalance", "group.admit",
         "group.prefill", "group.prefill_sync", "group.control",
         "group.reshard", "group.decode", "group.decode_sync",
         "fleet.telemetry", "fleet.close", "python.gc")
# span -> the span it opens inside (fleet.tick holds all but these three)
PARENT = {"group.prefill": "group.admit",
          "group.prefill_sync": "group.prefill",
          "group.decode_sync": "group.decode"}


def _profiled(logdir, eng):
    """Run ``eng`` to the end under the profiler, with one explicit
    collection inside the trace; return its spans, ``(name, start, end,
    thread line)`` in start order."""
    import gc
    import glob
    import os
    jax.profiler.start_trace(logdir)
    try:
        eng.run()
        gc.collect()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    return sorted((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                   (plane.name, i))
                  for plane in pd.planes if plane.name.startswith("/host:")
                  for i, line in enumerate(plane.lines)
                  for ev in line.events if ev.name in SPANS)


@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    """The same migrating, splitting trace through an object and a vec
    engine, each under the profiler, with the object engine's prefill and
    decode calls counted."""
    from repro.models import transformer as T
    from repro.serve import engine as serve_engine
    cfg = get_config("qwen3-14b", reduced=True)
    params, _ = T.init_model(jax.random.PRNGKey(0), cfg)
    fc = _fleet_cfg("off", engine="object")
    calls = {"prefill": 0, "decode": 0}

    def counted(fn, key):
        def call(*a, **k):
            calls[key] += 1
            return fn(*a, **k)
        return call

    saved = serve_engine.jit_prefill, serve_engine.jit_decode
    serve_engine.jit_prefill = counted(saved[0], "prefill")
    serve_engine.jit_decode = counted(saved[1], "decode")
    try:
        eng_o = FleetEngine(cfg, params, fleet=fc)
        eng_o.submit(imbalanced_trace(40, cfg.vocab_size, seed=5,
                                      shards=fc.num_groups))
        spans_o = _profiled(str(tmp_path_factory.mktemp("object")), eng_o)
    finally:
        serve_engine.jit_prefill, serve_engine.jit_decode = saved
    eng_v = FleetEngine(cfg, None, fleet=fc.replace(engine="vec"))
    eng_v.submit(imbalanced_trace(40, cfg.vocab_size, seed=5,
                                  shards=fc.num_groups))
    spans_v = _profiled(str(tmp_path_factory.mktemp("vec")), eng_v)
    return eng_o, spans_o, calls, eng_v, spans_v


def test_spans_are_inert_without_a_profiler_and_gc_hook_installs_once():
    import gc
    from repro.obs import install_gc_spans, span
    from repro.obs.spans import GcSpans
    with span("group.decode", gid=0, part=1):
        pass
    install_gc_spans()
    install_gc_spans()
    assert sum(isinstance(cb, GcSpans) for cb in gc.callbacks) == 1
    gc.collect()                         # the hook runs outside a trace too


def test_engine_spans_nest_as_documented(profiled):
    _, spans, _, _, _ = profiled
    assert {n for n, *_ in spans} == set(SPANS)

    def inside(child, parent_name):
        _, s, e, line = child
        return any(n == parent_name and ln == line and ps <= s and e <= pe
                   for n, ps, pe, ln in spans)

    for sp in spans:
        name = sp[0]
        if name in ("fleet.tick", "fleet.close", "python.gc"):
            continue
        assert inside(sp, "fleet.tick"), sp
        if name in PARENT:
            assert inside(sp, PARENT[name]), sp
    assert not any(inside(sp, "fleet.tick") for sp in spans
                   if sp[0] == "fleet.close")


def test_sync_spans_count_the_device_calls(profiled):
    eng, spans, calls, _, _ = profiled
    count = collections.Counter(n for n, *_ in spans)
    assert calls["decode"] > 0 and calls["prefill"] > 0
    assert count["group.decode_sync"] == count["group.decode"] \
        == calls["decode"]
    assert count["group.prefill_sync"] == count["group.prefill"] \
        == calls["prefill"]
    assert count["fleet.close"] == 1
    assert count["group.reshard"] == sum(g.stats.splits + g.stats.fuses
                                         + g.stats.resizes
                                         for g in eng.groups) > 0


def test_requests_are_stamped_at_submit_and_first_admission(profiled):
    for eng in (profiled[0], profiled[3]):
        assert eng.requests
        for r in eng.requests:
            assert r.submitted_s is not None and r.admitted_s is not None
            assert r.submitted_s <= r.admitted_s


def test_vec_engine_emits_the_same_control_spans(profiled):
    _, spans_o, _, _, spans_v = profiled
    for name in ("group.admit", "group.control", "group.reshard"):
        n_o = sum(1 for n, *_ in spans_o if n == name)
        n_v = sum(1 for n, *_ in spans_v if n == name)
        assert n_o == n_v > 0, name
