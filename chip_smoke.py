"""Bring-up check: serve qwen3-14b at its published widths on a TPU.

    python chip_smoke.py              # one chip: the serving main path
    python chip_smoke.py --chips 4    # four chips: the sharded path only

One chip: builds the model from ``--seed``, drains a seeded trace through
``FleetEngine`` (2 dynamic groups of 8 slots, so groups split and fuse),
replays it once more as the steady window, then compares the logits of the
jitted prefill and decode steps with the float32 reference forward.

Four chips: the same model through ``T.prefill``/``T.decode_step`` under a
(data=1, model=4) mesh with production sharding, compared by logits with
the same model on one of those chips.

The script needs a TPU and runs in one process.  It exits non-zero when
JAX finds no TPU, and any failed check raises, so it then exits non-zero
without its last line.  The last line of standard output is one JSON
object naming the device.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.configs.base import AmoebaConfig, FleetConfig  # noqa: E402
from repro.core.fusion import MeshPlan  # noqa: E402
from repro.fleet import FleetEngine, poisson_trace  # noqa: E402
from repro.launch.mesh import make_plan_mesh  # noqa: E402
from repro.models import transformer as T  # noqa: E402
from repro.parallel import shardctx  # noqa: E402
from repro.serve.engine import jit_prefill, make_decode_fn  # noqa: E402

ARCH = "qwen3-14b"
# Depth is the only cut.  Qwen3-14B has 40 identical dense layers, so one
# layer is a whole period of its pattern.  In bf16 the 40 layers alone
# take 26 GB, more than the 16 GB of one v5e chip.  8 layers keep every
# published width (d_model 5120, 40 heads, 8 KV heads, head_dim 128,
# d_ff 17408, vocab 151936) and come to 4.20 B parameters, 7.8 GiB with
# the embedding and output head.  That leaves about 8 GiB for the KV
# cache, the decode state's second copy, activations and the float32
# reference's one-layer copy.
PUBLISHED_LAYERS = 40
LAYERS = 8

GROUPS, CAPACITY, MAX_WAYS = 2, 8, 2
WINDOW = 256                       # KV ring length per request
PROMPT_LEN = 16
MAX_NEW = 96                       # PROMPT_LEN + MAX_NEW <= WINDOW: no wrap
AMOEBA = AmoebaConfig(max_ways=MAX_WAYS, split_threshold=0.3,
                      fuse_threshold=0.05, min_phase_steps=2)
SERVE_RT = T.Runtime(production=False, remat=False)

CHECK_PROMPTS, CHECK_STEPS = 4, 4
# Logit tolerance: relative L2 error per (prompt, position) row, worst row.
# bf16 keeps 8 significant bits; the served path rounds every matmul
# output and the residual stream to bf16, and through 8 layers that left
# 1.1% against the float32 reference on CPU runs of this architecture at
# d_model 512 and 1280 (the error did not grow with width).  Rounding the
# weights to float8_e4m3 (4 significant bits) gave 10%.  3% admits bf16
# with room and fails any path that computes below bf16.
LOGIT_TOL = 3e-2


def require_tpu(chips: int) -> list:
    """The device check, made before any other work: no CPU fallback."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found platform "
                 f"{devs[0].platform!r}")
    if len(devs) < chips:
        sys.exit(f"chip_smoke: needs {chips} chips; JAX found {len(devs)}")
    return devs


def smoke_config():
    cfg = get_config(ARCH)
    assert cfg.num_layers == PUBLISHED_LAYERS, cfg.num_layers
    return cfg.replace(num_layers=LAYERS)


def init_params(cfg, seed: int):
    """Random weights from ``seed``, generated on the default device."""
    return jax.jit(lambda k: T.init_model(k, cfg)[0])(
        jax.random.PRNGKey(seed))


def smoke_trace(cfg, seed: int):
    """24 requests over 30 ticks with lognormal (long-tailed) lengths."""
    return poisson_trace(0.8, 30, cfg.vocab_size, seed=seed,
                         length_dist="lognormal", mean_tokens=12.0,
                         sigma=1.0, max_tokens=MAX_NEW,
                         prompt_lengths=(PROMPT_LEN,))


class CompileCounter:
    """Counts XLA executables built (compiled, or read from the persistent
    cache) and the seconds spent building them, while the context is open.
    """

    def __init__(self):
        self.count = 0
        self.seconds = 0.0
        self.cache_hits = 0

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += duration

    def _event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return self.count, self.seconds, self.cache_hits


def _block_on_engine(eng: FleetEngine) -> None:
    jax.block_until_ready(eng.pool.state)


def serve(cfg, params, seed: int) -> dict:
    """Drain one seeded trace through FleetEngine; check every request."""
    trace = smoke_trace(cfg, seed)
    eng = FleetEngine(cfg, params, rt=SERVE_RT, fleet=FleetConfig(
        num_groups=GROUPS, capacity=CAPACITY, mode="dynamic",
        router="length_aware", window=WINDOW, amoeba=AMOEBA))
    eng.submit(trace)
    t0 = time.perf_counter()
    summary = eng.run()
    _block_on_engine(eng)
    wall = time.perf_counter() - t0
    short = [(r.rid, len(r.generated), r.max_new_tokens) for r in trace
             if len(r.generated) != r.max_new_tokens]
    if summary["completed"] != len(trace) or short:
        raise RuntimeError(f"completed {summary['completed']} of "
                           f"{len(trace)}; short requests {short}")
    return {"requests": len(trace),
            "tokens": sum(len(r.generated) for r in trace),
            "splits": sum(g.stats.splits for g in eng.groups),
            "fuses": sum(g.stats.fuses for g in eng.groups),
            "ticks": summary["wall_ticks"], "wall_s": wall}


def served_logits(cfg, params, tokens, rt=SERVE_RT):
    """Logits as the engine computes them: the shared jitted prefill on
    ``tokens[:, :PROMPT_LEN]``, then one jitted decode step per further
    token.  Returns (B, S - PROMPT_LEN + 1, V): the logits at positions
    PROMPT_LEN - 1 .. S - 1."""
    decode = make_decode_fn(cfg, rt)
    lg, st = jit_prefill(params, {"tokens": tokens[:, :PROMPT_LEN]},
                         cfg=cfg, rt=rt, window=WINDOW)
    outs = [lg]
    for t in range(PROMPT_LEN, tokens.shape[1]):
        lg, st = decode(params, st, tokens[:, t:t + 1])
        outs.append(lg)
    return jnp.stack(outs, axis=1)


def rel_err(got, ref) -> float:
    """Worst relative L2 error over rows of the trailing (vocab) axis."""
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    num = np.linalg.norm(got - ref, axis=-1)
    return float(np.max(num / np.linalg.norm(ref, axis=-1)))


def check_tokens(cfg, seed: int):
    """A few seeded sequences: a prompt and the tokens decode is fed."""
    return jax.random.randint(jax.random.PRNGKey(seed + 1),
                              (CHECK_PROMPTS, PROMPT_LEN + CHECK_STEPS), 0,
                              cfg.vocab_size, dtype=jnp.int32)


def _within_tol(what: str, got, ref) -> float:
    if not np.isfinite(np.asarray(got, np.float32)).all():
        raise RuntimeError(f"{what}: logits are not finite")
    err = rel_err(got, ref)
    if not err <= LOGIT_TOL:
        raise RuntimeError(f"{what}: logit error {err:.3e} > tolerance "
                           f"{LOGIT_TOL}")
    return err


def logit_check(cfg, params, seed: int) -> float:
    """Served prefill + decode vs the float32 reference forward."""
    tokens = check_tokens(cfg, seed)
    got = served_logits(cfg, params, tokens)
    ref = T.reference_logits(params, tokens, cfg)[:, PROMPT_LEN - 1:]
    return _within_tol("served vs float32 reference", got, ref)


def sharded_check(cfg, params, seed: int, chips: int = 4) -> dict:
    """Production sharding on a (data=1, model=chips) mesh vs one chip."""
    tokens = check_tokens(cfg, seed)
    single = served_logits(cfg, params, tokens)
    mesh = make_plan_mesh(MeshPlan("smoke", data=1, model=chips))
    _, pspecs = T.model_pspecs(cfg)
    placed = jax.tree.map(
        lambda s, a: jax.device_put(a, NamedSharding(mesh, s)),
        pspecs, params, is_leaf=lambda x: isinstance(x, P))
    wi = placed["reps"][0]["ffn"]["wi_up"]
    n_shards = len({s.device for s in wi.addressable_shards})
    if n_shards != chips:
        raise RuntimeError(f"wi_up lives on {n_shards} devices, not {chips}")
    with shardctx.use_mesh(mesh):
        sharded = served_logits(cfg, placed, np.asarray(tokens),
                                T.Runtime(production=True, remat=False))
    err = _within_tol("sharded vs one chip", sharded, single)
    return {"mesh": dict(mesh.shape), "err": err,
            "wi_up_shard": tuple(wi.addressable_shards[0].data.shape)}


def _peak_bytes(dev) -> int:
    return int(dev.memory_stats()["peak_bytes_in_use"])


def run_one_chip(dev, seed: int) -> None:
    cfg = smoke_config()
    with CompileCounter() as compiles:
        t0 = time.perf_counter()
        params = jax.block_until_ready(init_params(cfg, seed))
        print(f"model: {ARCH} {LAYERS} of {PUBLISHED_LAYERS} layers, "
              f"{T.count_params(params) / 1e9:.3f} B params, init "
              f"{time.perf_counter() - t0:.2f} s")
        warm = serve(cfg, params, seed)
        c1 = compiles.snapshot()
        steady = serve(cfg, params, seed)
        c2 = compiles.snapshot()
    peak_serve = _peak_bytes(dev)
    print(f"serve warm-up: {warm['requests']} requests, {warm['tokens']} "
          f"tokens, {warm['ticks']} ticks, {warm['wall_s']:.3f} s wall")
    print(f"set-up/compile (init + warm-up): {c1[0]} executables "
          f"({c1[2]} from the persistent cache) in {c1[1]:.2f} s")
    print(f"serve steady: {steady['requests']} requests, "
          f"{steady['tokens']} tokens, {steady['ticks']} ticks, "
          f"{steady['wall_s']:.3f} s wall (block_until_ready), "
          f"{steady['tokens'] / steady['wall_s']:.1f} tokens/s, "
          f"{c2[0] - c1[0]} compilations in the window")
    print(f"reconfiguration: {steady['splits']} splits, "
          f"{steady['fuses']} fuses; all {steady['requests']} requests "
          f"completed with max_new_tokens tokens")
    print(f"peak_bytes_in_use after serving: {peak_serve} "
          f"({peak_serve / 2**30:.2f} GiB)")
    if warm["splits"] < 1 or warm["fuses"] < 1:
        raise RuntimeError(f"no split/fuse: {warm}")
    if c2[0] != c1[0]:
        raise RuntimeError(f"{c2[0] - c1[0]} compilations in the steady "
                           f"window")
    err = logit_check(cfg, params, seed)
    print(f"logits vs float32 reference: max relative L2 error {err:.4e} "
          f"<= tolerance {LOGIT_TOL} ({CHECK_PROMPTS} sequences, prefill + "
          f"{CHECK_STEPS} decode steps)")
    print(f"peak_bytes_in_use with the reference: {_peak_bytes(dev)}")


def run_four_chips(devs, seed: int) -> None:
    cfg = smoke_config()
    params = jax.block_until_ready(init_params(cfg, seed))
    t0 = time.perf_counter()
    res = sharded_check(cfg, params, seed, chips=4)
    print(f"sharded ({res['mesh']}, wi_up shard {res['wi_up_shard']}) vs "
          f"one chip: max relative L2 logit error {res['err']:.4e} <= "
          f"tolerance {LOGIT_TOL} ({time.perf_counter() - t0:.2f} s)")
    print("peak_bytes_in_use per chip: "
          + ", ".join(str(_peak_bytes(d)) for d in devs[:4]))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    devs = require_tpu(args.chips)
    cache = enable_compile_cache()
    dev = devs[0]
    print(f"device: {dev.platform} {dev.device_kind} x{len(devs)}; "
          f"compile cache {cache}")
    if args.chips == 4:
        run_four_chips(devs, args.seed)
    else:
        run_one_chip(dev, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
