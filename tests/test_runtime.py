"""Trainer (fault tolerance, compression), checkpointing, data, serving."""
import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt import CheckpointManager, latest_step
from repro.configs import get_config
from repro.configs.base import AmoebaConfig, ShapeConfig, TrainConfig
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.models import transformer as T
from repro.serve import Request, ServeEngine
from repro.train import Trainer
from repro.train.stragglers import StragglerMonitor

SHAPE = ShapeConfig("tiny", 64, 4, "train")


def _trainer(arch="qwen3-14b", **tkw):
    cfg = get_config(arch, reduced=True)
    tcfg = TrainConfig(total_steps=10, warmup_steps=2, learning_rate=1e-3,
                       checkpoint_every=4, **tkw)
    return Trainer(cfg, SHAPE, tcfg)


def test_loss_decreases():
    out = _trainer().train(10)
    hist = out["history"]
    first3 = np.mean([m.loss for m in hist[:3]])
    last3 = np.mean([m.loss for m in hist[-3:]])
    assert last3 < first3


def test_failure_resume_is_exact(tmp_path):
    base = _trainer().train(10)
    losses = [m.loss for m in base["history"]]

    ck = CheckpointManager(str(tmp_path), keep=2)
    fails = {5, 8}

    def inject(k):
        if k in fails:
            fails.discard(k)
            return True
        return False

    out = _trainer().train(10, ckpt=ck, failure_injector=inject)
    assert out["resumes"] == 2
    got = {m.step: m.loss for m in out["history"]}
    for s, l in got.items():
        assert abs(l - losses[s]) < 1e-6, (s, l, losses[s])


def test_grad_compression_trains():
    out = _trainer(grad_compression=True).train(8)
    hist = out["history"]
    assert hist[-1].loss < hist[0].loss + 0.1
    assert out["state"].residuals is not None


def test_micro_steps_match_full_batch():
    """Gradient accumulation over microbatches == one big batch (fp32)."""
    cfg = get_config("qwen3-14b", reduced=True).replace(dtype="float32")
    t1 = Trainer(cfg, SHAPE, TrainConfig(total_steps=3, warmup_steps=1,
                                         learning_rate=1e-3, micro_steps=1))
    t2 = Trainer(cfg, SHAPE, TrainConfig(total_steps=3, warmup_steps=1,
                                         learning_rate=1e-3, micro_steps=2))
    h1 = t1.train(3)["history"]
    h2 = t2.train(3)["history"]
    for a, b in zip(h1, h2):
        assert abs(a.loss - b.loss) < 5e-4, (a.step, a.loss, b.loss)


def test_moe_divergence_telemetry():
    from repro.core.controller import AmoebaController
    cfg = get_config("deepseek-moe-16b", reduced=True)
    ctl = AmoebaController(AmoebaConfig(min_phase_steps=1))
    tr = Trainer(cfg, SHAPE, TrainConfig(total_steps=4, warmup_steps=1),
                 controller=ctl)
    out = tr.train(4)
    assert all(m.divergence > 0 for m in out["history"])
    assert len(ctl.split_state.history) == 4


def test_straggler_monitor():
    import time
    mon = StragglerMonitor(threshold=3.0, warmup=1)
    for i in range(6):
        mon.start()
        time.sleep(0.03 if i != 4 else 0.2)
        mon.stop(i)
    assert len(mon.events) == 1 and mon.events[0]["step"] == 4


# -- checkpoint manager -------------------------------------------------------

def test_ckpt_roundtrip_and_retention(tmp_path):
    ck = CheckpointManager(str(tmp_path), keep=2)
    tree = {"a": jnp.arange(6, dtype=jnp.bfloat16).reshape(2, 3),
            "b": [jnp.ones(()), jnp.zeros((4,), jnp.int32)]}
    for s in (1, 2, 3):
        ck.save(s, tree, extra={"tag": s}, blocking=True)
    assert latest_step(str(tmp_path)) == 3
    # retention: only the newest `keep` survive
    steps = sorted(int(n.split("_")[1]) for n in os.listdir(tmp_path)
                   if n.startswith("step_"))
    assert steps == [2, 3]
    step, got, extra = ck.restore(like=tree)
    assert step == 3 and extra == {"tag": 3}
    assert got["a"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(got["a"], np.float32),
                                  np.asarray(tree["a"], np.float32))


def test_ckpt_atomicity(tmp_path):
    """A lingering .tmp dir is never picked up as a checkpoint."""
    ck = CheckpointManager(str(tmp_path))
    os.makedirs(os.path.join(tmp_path, "step_9.tmp"))
    ck.save(1, {"x": jnp.ones((2,))}, blocking=True)
    assert latest_step(str(tmp_path)) == 1


# -- data pipeline -------------------------------------------------------------

def test_data_determinism_and_seek():
    cfg = get_config("qwen3-14b", reduced=True)
    d1 = SyntheticLM(cfg, SHAPE, DataConfig(seed=7))
    d2 = SyntheticLM(cfg, SHAPE, DataConfig(seed=7))
    np.testing.assert_array_equal(d1.batch_at(5)["tokens"],
                                  d2.batch_at(5)["tokens"])
    it = iter(d1)
    first = next(it)
    np.testing.assert_array_equal(first["tokens"], d1.batch_at(0)["tokens"])


def test_data_host_sharding_disjoint():
    cfg = get_config("qwen3-14b", reduced=True)
    shape = ShapeConfig("t", 32, 8, "train")
    h0 = SyntheticLM(cfg, shape, DataConfig(seed=1), host_index=0,
                     host_count=2)
    h1 = SyntheticLM(cfg, shape, DataConfig(seed=1), host_index=1,
                     host_count=2)
    assert h0.local_batch == 4
    assert not np.array_equal(h0.batch_at(0)["tokens"],
                              h1.batch_at(0)["tokens"])


def test_data_has_learnable_structure():
    """Markov stream: successor entropy far below uniform."""
    cfg = get_config("qwen3-14b", reduced=True)
    d = SyntheticLM(cfg, ShapeConfig("t", 256, 4, "train"), DataConfig(seed=0))
    toks = d.batch_at(0)["tokens"]
    # each token has only `branching` successors out of vocab
    succ = {}
    for row in toks:
        for a, b in zip(row[:-1], row[1:]):
            succ.setdefault(int(a), set()).add(int(b))
    avg_succ = np.mean([len(v) for v in succ.values()])
    assert avg_succ <= d.cfg.branching + 1


# -- serving -------------------------------------------------------------------

@pytest.fixture(scope="module")
def serve_setup():
    cfg = get_config("qwen3-14b", reduced=True)
    params, _ = T.init_model(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _requests(cfg, n=12, seed=0):
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        plen = int(rng.choice([8, 16]))
        mx = int(rng.choice([3, 6, 24]))
        out.append(Request(i, list(map(int, rng.integers(
            0, cfg.vocab_size, plen))), mx))
    return out


def test_serve_all_policies_complete_and_agree(serve_setup):
    """Generated tokens must be identical under every grouping policy —
    batch composition cannot change per-request results."""
    cfg, params = serve_setup
    texts = {}
    stats = {}
    for name, dyn, pol in [("fused", False, "warp_regroup"),
                           ("direct", True, "direct_split"),
                           ("regroup", True, "warp_regroup")]:
        eng = ServeEngine(cfg, params, amoeba=AmoebaConfig(
            regroup_policy=pol, split_threshold=0.3, fuse_threshold=0.05,
            min_phase_steps=2), capacity=4)
        reqs = _requests(cfg)
        eng.submit(reqs)
        st = eng.run(dynamic=dyn)
        assert st.completed == len(reqs)
        texts[name] = {r.rid: tuple(r.generated) for r in reqs}
        stats[name] = st
    assert texts["fused"] == texts["regroup"] == texts["direct"]
    assert stats["regroup"].efficiency >= stats["fused"].efficiency - 1e-9


def test_serve_regroup_beats_fused_on_divergent_load(serve_setup):
    cfg, params = serve_setup
    # long-tail decode lengths: most requests short, a few dominate the
    # batch critical path — the regime where quarantining the tail pays
    rng = np.random.default_rng(3)
    mk = lambda: [Request(i, list(map(int, rng.integers(0, cfg.vocab_size,
                                                        8))),
                          int(rng.choice([2, 40], p=[0.75, 0.25])))
                  for i in range(16)]
    effs = {}
    for name, dyn in [("fused", False), ("regroup", True)]:
        rng = np.random.default_rng(3)
        eng = ServeEngine(cfg, params, amoeba=AmoebaConfig(
            regroup_policy="warp_regroup", split_threshold=0.3,
            fuse_threshold=0.05, min_phase_steps=2), capacity=8)
        eng.submit(mk())
        effs[name] = eng.run(dynamic=dyn).efficiency
    assert effs["regroup"] > effs["fused"] * 1.1


# -- compile cache -------------------------------------------------------------

def test_compile_cache_dir_from_env_else_fixed_in_checkout(monkeypatch,
                                                            tmp_path):
    from repro.compile_cache import enable_compile_cache
    prev = jax.config.jax_compilation_cache_dir
    prev_min = jax.config.jax_persistent_cache_min_compile_time_secs
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        first = enable_compile_cache()
        assert enable_compile_cache() == first
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert first == os.path.join(repo, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == first
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          prev_min)
