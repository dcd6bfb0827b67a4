"""The open-loop driver and the whole run, at a CPU size, through the
test-only entry ``run.serve_cell`` (the measurement path itself refuses a
CPU)."""
import jax
import numpy as np
import pytest

import generator
import openloop
import run
import system
from conftest import TINY_MIX, tiny, tiny_spec
from layer import LayerContext, load_reader

BIG_SEED = 2 ** 31 + 77


@pytest.fixture(scope="module")
def model():
    cfg_file = tiny()
    sut = system.System(cfg_file)
    return cfg_file, sut, sut.make_params(BIG_SEED)


def _drive(model, mix, seconds, drain_s, seed):
    cfg_file, sut, params = model
    fleet = system.fleet_config(cfg_file, generator.ring_window(mix))
    eng = sut.make_engine(params, fleet)
    arrivals = generator.schedule(mix, seconds, seed, cfg_file["vocab_size"])
    return arrivals, openloop.drive(eng, arrivals, seconds, drain_s,
                                    system.make_request)


@pytest.mark.parametrize("config", ["qwen3-14b", "starcoder2-15b"])
def test_whole_run_is_correct(config, tmp_path):
    line = run.serve_cell(tiny_spec(config), BIG_SEED, 3.0, False,
                          jax.devices(), {}, str(tmp_path))
    assert line["correct"] is True
    n = len(generator.schedule(TINY_MIX, 3.0, BIG_SEED, 256))
    assert line["attempted"] == n and line["failed"] == 0
    assert set(line["metrics"]) == {"ttft_p50_s", "ttft_p90_s", "itl_p95_s",
                                    "output_tokens_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert list(line)[-1] == "compared"
    assert line["compared"]["max_logit_gap"]["value"] <= 0.05


def test_stamps_follow_due_times_and_drain(model):
    arrivals, res = _drive(model, TINY_MIX, 2.0, 60.0, 5)
    assert len(res.tracked) == len(arrivals)
    for tr in res.tracked:
        assert len(tr.stamps) == tr.arrival.max_new_tokens
        assert tr.stamps[0] >= tr.arrival.due_s
        assert tr.stamps == sorted(tr.stamps)
    assert openloop.failures(res) == []
    e2e = openloop.end_to_end(res)
    total = sum(a.max_new_tokens for a in arrivals)
    assert 0 < e2e["output_tokens_per_s"] * 2.0 <= total
    assert e2e["ttft_p50_s"] <= e2e["ttft_p90_s"]
    assert e2e["samples"]["requests"] == len(arrivals)
    # one decode row per token after the first, and prefill rows once each
    decode_rows = sum(len(t.decode_ctx) for t in res.ticks)
    prefill_rows = sum(n for t in res.ticks for n, _ in t.prefill)
    assert prefill_rows == len(arrivals)
    assert decode_rows == total - len(arrivals)


def test_traced_ttft_readers_match_end_to_end(model):
    _, res = _drive(model, TINY_MIX, 2.0, 60.0, 6)
    e2e = openloop.end_to_end(res)
    ctx = LayerContext(drive=res, shape=None, peak_flops=0.0, peak_bw=0.0,
                       chips=1, window_compiles=0)
    for q in ("p50", "p90"):
        read = load_reader(run.BENCH_DIR, f"ttft_{q}_traced_s")
        assert read(ctx) == e2e[f"ttft_{q}_s"] > 0


def test_drain_limit_counts_failures(model):
    mix = dict(TINY_MIX, long={"dist": "uniform", "min": 200, "max": 200},
               long_share={"phase_s": 1, "shares": [1.0]})
    arrivals, res = _drive(model, mix, 0.5, 0.0, 9)
    failed = openloop.failures(res)
    assert failed, "a 200-token answer cannot finish with no drain time"
    e2e = openloop.end_to_end(res)
    # a request with no first token counts at the drain's end
    starved = [tr for tr in res.tracked if not tr.stamps]
    if starved:
        assert e2e["ttft_p90_s"] >= min(res.end_s - tr.arrival.due_s
                                         for tr in starved) - 1e-9


def test_nearest_rank():
    v = np.arange(1, 101)
    assert openloop.nearest_rank(v, 0.5) == 50
    assert openloop.nearest_rank(v, 0.9) == 90
    assert openloop.nearest_rank(v, 0.95) == 95
    assert openloop.nearest_rank([3.0], 0.9) == 3.0


def test_warm_up_leaves_nothing_to_compile():
    """After the warm-up, serving the mix, splits and fuses included,
    builds no executable.  Three layers make every program's shapes new
    to this process."""
    cfg_file = tiny()
    cfg_file["num_hidden_layers"] = 3
    sut = system.System(cfg_file)
    params = sut.make_params(BIG_SEED)
    fleet = system.fleet_config(cfg_file, generator.ring_window(TINY_MIX))
    n = sut.warm_up(params, fleet, TINY_MIX["prompt_buckets"],
                    cfg_file["vocab_size"])
    cap = fleet.capacity
    assert n["waves"] == 2 * cap
    assert n["take"] == cap * (cap + 1) // 2 - 1
    assert n["concat"] == cap * (cap - 1) // 2
    eng = sut.make_engine(params, fleet)
    # a load at which waves hold both buckets and groups split and fuse
    mix = dict(TINY_MIX, arrivals=dict(TINY_MIX["arrivals"], rate_rps=40.0))
    arrivals = generator.schedule(mix, 3.0, 11, cfg_file["vocab_size"])
    with run.CompileCounter() as built:
        res = openloop.drive(eng, arrivals, 3.0, 60.0, system.make_request)
    assert openloop.failures(res) == []
    assert res.reconfigs > 0
    assert built.count == 0
