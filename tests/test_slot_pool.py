"""The slot pool: every part of every group decodes from one fixed-width
set of rows, in one ``jit_decode`` call per tick.

A split, a fuse or a migration inside the pool re-labels rows and moves
no KV; a row that does not advance in a tick (its part stalls, its group
reconfigures) keeps its position and next token, so it resumes with the
tokens it would have had without the pause.
"""
import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import (AmoebaConfig, FleetConfig, LeaseConfig,
                                MigrationConfig)
from repro.fleet import FleetEngine, bursty_longtail_trace, imbalanced_trace
from repro.fleet.migrate import LIVE, MigrationPlanner
from repro.models import transformer as T
from repro.serve import ReconfigurableGroup, Request
from repro.serve import engine as serve_engine
from repro.serve import state_utils as su

AMOEBA = AmoebaConfig(split_threshold=0.3, fuse_threshold=0.05,
                      min_phase_steps=2)


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("qwen3-14b", reduced=True)
    params, _ = T.init_model(jax.random.PRNGKey(0), cfg)
    return cfg, params


@pytest.fixture(scope="module", params=["qwen3-14b", "recurrentgemma-9b",
                                        "falcon-mamba-7b"])
def fp32(request):
    """Attention only, rglru with attention, and ssm only."""
    cfg = get_config(request.param, reduced=True).replace(dtype="float32")
    params, _ = T.init_model(jax.random.PRNGKey(1), cfg)
    return cfg, params


class _Compiles:
    """Executables built while the context is open."""

    def __enter__(self):
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1


def _count_decodes(monkeypatch):
    calls = [0]
    real = serve_engine.jit_decode

    def counted(*a, **k):
        calls[0] += 1
        return real(*a, **k)

    monkeypatch.setattr(serve_engine, "jit_decode", counted)
    return calls


def test_one_decode_call_per_tick_with_a_live_row(setup, monkeypatch):
    cfg, params = setup
    calls = _count_decodes(monkeypatch)
    eng = FleetEngine(cfg, params, fleet=FleetConfig(
        num_groups=2, capacity=4, window=64, mode="dynamic", amoeba=AMOEBA))
    trace = bursty_longtail_trace(horizon=25, vocab_size=cfg.vocab_size,
                                  seed=2)
    eng.submit(trace)
    decoding_ticks = 0
    while not all(r.done for r in trace):
        before = [len(r.generated) for r in trace]
        c0 = calls[0]
        eng.run(max_ticks=eng.wall + 1)
        # tokens past each request's first (prefill) token came from a decode
        rows = sum(len(r.generated) - n - (n == 0 < len(r.generated))
                   for r, n in zip(trace, before))
        assert calls[0] - c0 == (rows > 0), eng.wall
        decoding_ticks += rows > 0
    assert sum(g.stats.splits for g in eng.groups) > 0
    assert eng.decode_calls == calls[0] == decoding_ticks > 0
    assert eng.decode_parts > eng.decode_calls


def _refuse_kv_moves(monkeypatch):
    def refuse(*a, **k):
        raise AssertionError("KV moved")

    monkeypatch.setattr(su, "take", refuse)
    monkeypatch.setattr(su, "concat", refuse)


def test_split_and_fuse_move_no_kv(setup, monkeypatch):
    cfg, params = setup
    _refuse_kv_moves(monkeypatch)
    eng = FleetEngine(cfg, params, fleet=FleetConfig(
        num_groups=2, capacity=4, window=64, mode="dynamic", router="sticky",
        migrate=MigrationConfig(enabled=True), amoeba=AMOEBA))
    trace = imbalanced_trace(40, cfg.vocab_size, seed=5, shards=2)
    eng.submit(trace)
    s = eng.run()
    assert s["completed"] == len(trace)
    assert all(len(r.generated) == r.max_new_tokens for r in trace)
    assert sum(g.stats.splits for g in eng.groups) > 0
    assert sum(g.stats.fuses for g in eng.groups) > 0


def test_live_migration_inside_one_pool_moves_no_kv(setup, monkeypatch):
    """Two groups on one pool: the migrated request's row changes owner,
    and its tokens are those of an undisturbed fused run."""
    cfg, params = setup
    rt = T.Runtime(production=False, remat=False)

    def serve(migrate):
        pool = serve_engine.SlotPool(cfg, params, rt, rows=8, window=64,
                                     wave=4)
        g0, g1 = (ReconfigurableGroup(cfg, params, capacity=4, window=64,
                                      mode=mode, gid=i, amoeba=AMOEBA,
                                      pool=pool)
                  for i, mode in enumerate(("fused", "split")))
        reqs = [Request(i, [1, 2, 3, 4], n)
                for i, n in enumerate([60, 3, 3, 3])]
        g0.submit(reqs)
        g0.step(now=0)
        pool.decode(0)
        if migrate:
            p = MigrationPlanner(
                MigrationConfig(enabled=True, live=True, min_gain=0.0,
                                link_bandwidth=1e12), cfg,
                long_threshold=24, window=64)
            plans = p.plan(0, [g0, g1])
            assert [m.request for m in plans if m.kind == LIVE] == reqs[:1]
            assert p.execute(plans, [g0, g1], now=0) == 1
            assert g1.stats.migrations_in == 1
        for t in range(1, 500):
            g0.step(now=t)
            g1.step(now=t)
            pool.decode(t)
            if all(r.done for r in reqs):
                break
        return reqs, g1

    ref, _ = serve(False)
    _refuse_kv_moves(monkeypatch)
    got, g1 = serve(True)
    assert g1.stats.stall_ticks > 0
    assert [r.generated for r in got] == [r.generated for r in ref]


@pytest.mark.parametrize("pause", ["stall", "reconfig"])
def test_a_paused_row_resumes_with_the_same_tokens(fp32, pause,
                                                   monkeypatch):
    """Group 0 (requests 0 and 2) pauses for some ticks while request 1,
    in group 1, keeps the pool decoding; every request gets the tokens
    and the logits of the same pool served without the pause (a held
    row's recurrent state is kept too)."""
    cfg, params = fp32
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab_size, 8).tolist() for _ in range(3)]
    logits = {}                  # rid -> the logits of each of its decodes
    tick = {}
    real_advance = serve_engine.jit_advance
    real_decoded = ReconfigurableGroup._decoded

    def advance(lg, *a):
        tick["logits"] = np.asarray(lg)
        return real_advance(lg, *a)

    advance.lower = real_advance.lower        # pools build their programs

    def decoded(self, part, tokens, now):
        for row, r in zip(part.rows, part.requests):
            if not r.done:
                logits.setdefault(r.rid, []).append(tick["logits"][row])
        real_decoded(self, part, tokens, now)

    monkeypatch.setattr(serve_engine, "jit_advance", advance)
    monkeypatch.setattr(ReconfigurableGroup, "_decoded", decoded)

    def serve(pause_at):
        logits.clear()
        eng = FleetEngine(cfg, params, fleet=FleetConfig(
            num_groups=2, capacity=2, window=64, mode="dynamic",
            router="round_robin", amoeba=AmoebaConfig(
                split_threshold=2.0, fuse_threshold=0.0,
                min_phase_steps=1)))
        reqs = [Request(i, prompts[i], n) for i, n in enumerate([12, 20, 16])]
        eng.submit(reqs)
        g = eng.groups[0]
        while not all(r.done for r in reqs):
            if eng.wall == pause_at:
                if pause == "stall":
                    g._stall[0] = 3
                else:
                    g.controller.request_topology((1, 1))
            eng.run(max_ticks=eng.wall + 1)
        return reqs, g, {rid: np.stack(v) for rid, v in logits.items()}

    ref, _, ref_logits = serve(None)
    got, g, got_logits = serve(4)
    if pause == "stall":
        assert g.stats.stall_ticks == 3
    else:
        assert g.stats.splits > 0
    assert got[0].finish > ref[0].finish
    assert [r.generated for r in got] == [r.generated for r in ref]
    assert sorted(got_logits) == [0, 1, 2]
    for rid, lg in ref_logits.items():
        np.testing.assert_allclose(got_logits[rid], lg, rtol=0, atol=1e-5,
                                   err_msg=f"request {rid}")


def test_warm_engine_serves_a_splitting_load_without_compiling(setup):
    """One wave of each size and prompt length through a one-group engine
    builds the prefills; the pool's own programs are built when the
    two-group engine is made.  A ring of 48 makes every program new to
    this process."""
    cfg, params = setup
    fleet = FleetConfig(num_groups=2, capacity=4, window=48, mode="dynamic",
                        amoeba=AMOEBA)
    rng = np.random.default_rng(3)
    warm = FleetEngine(cfg, params, fleet=fleet.replace(num_groups=1,
                                                        mode="fused"))
    rid = 0
    for plen in (8, 16):
        for n in range(1, fleet.capacity + 1):
            reqs = [Request(rid + i, rng.integers(0, cfg.vocab_size,
                                                  plen).tolist(), 2,
                            arrival=warm.wall) for i in range(n)]
            rid += n
            warm.submit(reqs)
            warm.run()
    eng = FleetEngine(cfg, params, fleet=fleet)
    trace = bursty_longtail_trace(horizon=25, vocab_size=cfg.vocab_size,
                                  seed=2)
    with _Compiles() as built:
        eng.submit(trace)
        s = eng.run()
    assert s["completed"] == len(trace)
    assert sum(g.stats.splits + g.stats.fuses for g in eng.groups) > 0
    assert built.count == 0


def test_pool_reuses_freed_rows_and_refuses_past_its_width(setup):
    """A prefill's rows land in the lowest free rows with their state and
    first tokens; freed rows are reused; a pool never grows."""
    cfg, params = setup
    pool = serve_engine.SlotPool(cfg, params, T.Runtime(production=False,
                                                        remat=False),
                                 rows=4, window=32, wave=2)
    toks = np.arange(16, dtype=np.int32).reshape(2, 8) % cfg.vocab_size
    logits, st = serve_engine.jit_prefill(params, {"tokens": toks}, cfg=cfg,
                                          rt=pool.rt, window=32)
    nxt = np.asarray(logits.argmax(-1), np.int32)
    first = pool.put(st, nxt)
    more = pool.put(st, nxt)
    assert first == [0, 1] and more == [2, 3]
    np.testing.assert_array_equal(np.asarray(pool.last)[:, 0],
                                  np.concatenate([nxt, nxt]))
    k_pool = jax.tree.leaves(pool.state.reps)[0]
    k_wave = jax.tree.leaves(st.reps)[0]
    np.testing.assert_array_equal(np.asarray(k_pool[:, 2:]),
                                  np.asarray(k_wave))
    with pytest.raises(RuntimeError, match="4 rows"):
        pool.alloc(1)
    pool.free(first)
    assert pool.alloc(2) == [0, 1]


@pytest.mark.parametrize("lease", [False, True])
def test_fleet_pool_holds_what_its_parts_can_admit(setup, lease):
    """Every group's capacity, twice that with leases (rows admitted on
    borrowed slots stay after the slots go home); a leasing load is
    served within it."""
    cfg, params = setup
    eng = FleetEngine(cfg, params, fleet=FleetConfig(
        num_groups=2, capacity=4, window=64, mode="dynamic", amoeba=AMOEBA,
        lease=LeaseConfig(enabled=lease)))
    assert eng.pool.rows == 2 * 4 * (2 if lease else 1)
    trace = bursty_longtail_trace(horizon=25, vocab_size=cfg.vocab_size,
                                  seed=2)
    eng.submit(trace)
    assert eng.run()["completed"] == len(trace)
    assert (sum(g.stats.leases_in for g in eng.groups) > 0) == lease
