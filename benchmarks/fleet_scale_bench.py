"""fleet_scale sweep: the vectorized SoA core at 100+ groups x 100k requests.

The scaling benchmark the ROADMAP gated on: replay a 100k-request trace
through a 100-group fleet under the struct-of-arrays engine
(``FleetConfig.engine="vec"``, see ``repro.fleet.vec``) in CI minutes,
and measure its ticks-per-second advantage over the object engine on the
*same* dynamic configuration.  The object baseline is priced on a
steady-state segment (a warmup run absorbs the jit compiles first) so
the reported speedup is engine-vs-engine, not compile-vs-no-compile.

Also carries the ``suggest_split`` micro-benchmark: the control plane's
candidate scan used to re-sort and re-partition the live batch for every
candidate topology (O(parts x capacity) full evaluations); the shared-
ordering evaluator in ``repro.control.space`` sorts once and prices each
candidate from cached per-part counts.  The micro-benchmark times the
faithful legacy formulation against the shipped one on identical inputs
and asserts identical argmins.

    PYTHONPATH=src python benchmarks/fleet_scale_bench.py \
        --groups 100 --requests 100000 --budget-s 600 --min-speedup 20
"""
from __future__ import annotations

import argparse
import json
import os
import time
from typing import Dict, List, Optional

ROOT = os.path.join(os.path.dirname(__file__), "..")
OUT = os.path.join(ROOT, "BENCH_fleet.json")
TIMING_OUT = os.path.join(ROOT, "BENCH_fleet_scale_timing.json")

# summary keys kept per variant — full summaries carry one snapshot per
# group (100+ entries), which would bloat the committed artifact
_KEEP = ("wall_ticks", "idle_ticks", "wall_s", "ticks_per_sec",
         "completed", "submitted", "efficiency", "utilization",
         "throughput_tokens_per_tick", "latency", "mean_queue_depth",
         "churn_per_kilotick")


def scale_trace(n_requests: int, groups: int, horizon: int,
                seed: int = 0) -> List:
    """A flat 100k-request trace built directly (no per-tick sampling).

    Work-balanced arrivals over ``horizon`` ticks, a bimodal-ish length
    mix, round-robin shards (so sticky routing would spread it), and one
    shared prompt object — requests never mutate their prompt, and the
    single length keeps the object baseline to one prefill shape per
    batch size.
    """
    import numpy as np

    from repro.serve.engine import Request
    rng = np.random.default_rng(seed)
    lengths = rng.choice([4, 8, 16, 32, 48], size=n_requests,
                         p=[0.35, 0.3, 0.2, 0.1, 0.05])
    arrivals = np.sort(rng.integers(0, horizon, size=n_requests))
    prompt = [1] * 8
    return [Request(rid=i, prompt=prompt, max_new_tokens=int(lengths[i]),
                    arrival=int(arrivals[i]), shard=i % groups)
            for i in range(n_requests)]


def fleet_scale_sweep(cfg, params, rt, *, groups: int = 100,
                      capacity: int = 8, n_requests: int = 100_000,
                      obj_warmup_ticks: int = 10,
                      obj_measure_ticks: int = 20,
                      seed: int = 0,
                      budget_s: Optional[float] = None,
                      min_speedup: Optional[float] = None) -> Dict:
    """Vec-engine variants over the full trace + object steady-state tps."""
    from repro.configs.base import AmoebaConfig, FleetConfig
    from repro.fleet import FleetEngine

    amoeba = AmoebaConfig(split_threshold=0.3, fuse_threshold=0.05,
                          min_phase_steps=2)
    # horizon sized so the fleet stays loaded but drains: total decode
    # work over ~70% of the fleet's peak token throughput
    mean_len = 0.35 * 4 + 0.3 * 8 + 0.2 * 16 + 0.1 * 32 + 0.05 * 48
    horizon = max(int(n_requests * mean_len / (groups * capacity * 0.7)), 1)
    variants = {
        "static_fused": dict(mode="fused", router="least_loaded"),
        "static_split": dict(mode="split", router="least_loaded"),
        "dynamic_threshold": dict(mode="dynamic", router="least_loaded"),
    }
    out: Dict = {"config": {
        "groups": groups, "capacity": capacity, "n_requests": n_requests,
        "horizon": horizon, "seed": seed, "window": 64,
        "obj_warmup_ticks": obj_warmup_ticks,
        "obj_measure_ticks": obj_measure_ticks}}

    for label, kw in variants.items():
        eng = FleetEngine(cfg, None, rt=rt, fleet=FleetConfig(
            num_groups=groups, capacity=capacity, window=64,
            amoeba=amoeba, engine="vec", **kw))
        eng.submit(scale_trace(n_requests, groups, horizon, seed))
        s = eng.run()
        if s["completed"] != n_requests:
            raise RuntimeError(f"{label}: completed {s['completed']} of "
                               f"{n_requests} requests")
        out[label] = {k: s[k] for k in _KEEP}
        lat = s["latency"]
        print(f"{label:18s} ticks={s['wall_ticks']:6d} "
              f"wall={s['wall_s']:7.2f}s tps={s['ticks_per_sec']:8.1f} "
              f"eff={s['efficiency']:.3f} p50={lat['p50']:5.1f} "
              f"p99={lat['p99']:6.1f} done={s['completed']}")

    # object-engine baseline: identical dynamic config, steady-state
    # segment only (the warmup run absorbs the jit compiles)
    eng = FleetEngine(cfg, params, rt=rt,
                      fleet=FleetConfig(
                          num_groups=groups, capacity=capacity, window=64,
                          amoeba=amoeba, engine="object",
                          **variants["dynamic_threshold"]))
    eng.submit(scale_trace(n_requests, groups, horizon, seed))
    s1 = eng.run(max_ticks=obj_warmup_ticks)
    t0 = time.perf_counter()
    s2 = eng.run(max_ticks=obj_warmup_ticks + obj_measure_ticks)
    dt = time.perf_counter() - t0
    obj_ticks = s2["wall_ticks"] - s1["wall_ticks"]
    obj_tps = obj_ticks / max(dt, 1e-9)
    out["object_baseline"] = {
        "measured_ticks": obj_ticks, "wall_s": round(dt, 3),
        "ticks_per_sec": round(obj_tps, 2),
        "note": "steady-state segment after a warmup run absorbed "
                "the jit compiles; same dynamic config as the vec run"}
    print(f"{'object_baseline':18s} ticks={obj_ticks:6d} "
          f"wall={dt:7.2f}s tps={obj_tps:8.2f} (steady-state)")

    vec_tps = out["dynamic_threshold"]["ticks_per_sec"]
    vec_wall = sum(out[k]["wall_s"] for k in variants)
    speedup = vec_tps / max(obj_tps, 1e-9)
    out["validation"] = {
        "vec_ticks_per_sec": vec_tps,
        "object_ticks_per_sec": round(obj_tps, 2),
        "vec_speedup_ticks_per_sec": round(speedup, 1),
        "vec_total_wall_s": round(vec_wall, 2),
        "all_traces_drained": True,
        "budget_s": budget_s,
        "within_budget": bool(budget_s is None or vec_wall <= budget_s),
    }
    print(f"vec vs object (dynamic, {groups} groups): "
          f"{speedup:,.1f}x ticks/sec; vec swept "
          f"{len(variants)}x{n_requests:,} requests in {vec_wall:.1f}s")
    if budget_s is not None and vec_wall > budget_s:
        raise RuntimeError(f"fleet_scale vec sweep took {vec_wall:.1f}s "
                           f"> budget {budget_s:.0f}s")
    if min_speedup is not None and speedup < min_speedup:
        raise RuntimeError(f"vec speedup {speedup:.1f}x < required "
                           f"{min_speedup:.0f}x")
    return out


def obs_overhead_sweep(cfg, rt, *, groups: int = 20, capacity: int = 8,
                       n_requests: int = 20_000, seed: int = 0,
                       repeats: int = 3) -> Dict:
    """Ticks-per-second cost of the obs event stream on the vec engine.

    Three modes over the identical fleet_scale dynamic config, best of
    ``repeats`` runs each to suppress scheduler noise:

    * ``baseline`` — ``obs="off"``, the reference;
    * ``off`` — ``obs="off"`` again: same code path, so the measured
      "overhead" is the noise floor the ≤ 2% acceptance bound must
      absorb (off-mode adds only ``log.enabled`` attribute checks);
    * ``full`` — ring buffer + per-tick metrics sampling, bounded
      against ``off`` at ≤ 15%.
    """
    from repro.configs.base import AmoebaConfig, FleetConfig
    from repro.fleet import FleetEngine

    amoeba = AmoebaConfig(split_threshold=0.3, fuse_threshold=0.05,
                          min_phase_steps=2)
    mean_len = 0.35 * 4 + 0.3 * 8 + 0.2 * 16 + 0.1 * 32 + 0.05 * 48
    horizon = max(int(n_requests * mean_len / (groups * capacity * 0.7)), 1)

    def best_tps(obs_mode: str) -> float:
        tps = []
        for _ in range(repeats):
            eng = FleetEngine(cfg, None, rt=rt, fleet=FleetConfig(
                num_groups=groups, capacity=capacity, window=64,
                amoeba=amoeba, engine="vec", mode="dynamic",
                router="least_loaded", obs=obs_mode))
            eng.submit(scale_trace(n_requests, groups, horizon, seed))
            s = eng.run()
            if s["completed"] != n_requests:
                raise RuntimeError(
                    f"obs={obs_mode}: completed {s['completed']} of "
                    f"{n_requests}")
            tps.append(s["ticks_per_sec"])
        return max(tps)

    baseline = best_tps("off")
    off = best_tps("off")
    full = best_tps("full")
    out = {
        "config": {"groups": groups, "capacity": capacity,
                   "n_requests": n_requests, "horizon": horizon,
                   "seed": seed, "repeats": repeats},
        "ticks_per_sec": {"baseline": baseline, "off": off, "full": full},
        "off_overhead_frac": round(1.0 - off / max(baseline, 1e-9), 4),
        "full_overhead_frac": round(1.0 - full / max(off, 1e-9), 4),
    }
    out["validation"] = {
        "off_within_2pct": out["off_overhead_frac"] <= 0.02,
        "full_within_15pct": out["full_overhead_frac"] <= 0.15,
    }
    print(f"obs overhead: baseline={baseline:.1f} off={off:.1f} "
          f"full={full:.1f} tps -> off {out['off_overhead_frac']:+.2%}, "
          f"full {out['full_overhead_frac']:+.2%}")
    return out


# -- suggest_split micro-benchmark ---------------------------------------------

def _legacy_counts(B, topo):
    """partition()'s per-part counts, pre-cache (recomputed every call)."""
    k = len(topo)
    if k <= 1 or B < 2:
        return (B,) + (0,) * max(k - 1, 0)
    C = sum(topo)
    quota = [B * s / C for s in topo]
    counts = [int(q) for q in quota]
    extras = B - sum(counts)
    by_frac = sorted(range(k), key=lambda i: (quota[i] - counts[i], i),
                     reverse=True)
    for i in by_frac[:extras]:
        counts[i] += 1
    if B <= C:
        for i in range(k):
            while counts[i] > topo[i]:
                j = min((m for m in range(k) if counts[m] < topo[m]),
                        key=lambda m: (abs(m - i), m))
                counts[j] += 1
                counts[i] -= 1
    if B >= k:
        for i in range(k):
            while counts[i] == 0:
                j = max(range(k), key=lambda m: (counts[m], -m))
                counts[j] -= 1
                counts[i] += 1
    return tuple(counts)


def _legacy_cost(sp, r, t, policy):
    """The O(parts x capacity) per-candidate evaluation: full re-sort +
    re-partition + fancy-indexed per-part max — the formulation the
    shared-ordering evaluator replaced."""
    import numpy as np

    from repro.core.regroup import POLICIES

    topo = sp.as_topology(t)
    idx = list(range(r.size))
    if len(topo) <= 1 or len(idx) < 2:
        parts = [idx] + [[] for _ in range(len(topo) - 1)]
    else:
        fast, slow = POLICIES[policy](idx, r)
        order = fast + slow
        parts, pos = [], 0
        for c in _legacy_counts(len(idx), topo):
            parts.append(order[pos:pos + c])
            pos += c
    return float(sum(s * r[np.asarray(p, np.int64)].max()
                     for s, p in zip(topo, parts) if len(p)))


def _legacy_suggest_improve(sp, cur, r, policy):
    c = sp.as_topology(cur)
    cands = [t for t in sp.split_moves(c) + sp.resize_moves(c)
             if len(t) <= r.size]
    if not cands:
        return None
    best = min(cands, key=lambda t: (_legacy_cost(sp, r, t, policy),
                                     len(t), t))
    if _legacy_cost(sp, r, best, policy) \
            < _legacy_cost(sp, r, c, policy) - 1e-12:
        return best
    return None


def suggest_split_microbench(capacity: int = 16, max_ways: int = 8,
                             trials: int = 200, seed: int = 0) -> Dict:
    """Legacy vs shipped candidate scan on identical inputs.

    Benchmarks ``suggest_improve`` from 1-5-part start topologies — the
    states the controller actually scans from, where the candidate set
    (every single-part cut plus every neighboring re-cut) is largest.
    """
    import numpy as np

    from repro.control import ConfigSpace

    sp = ConfigSpace(capacity=capacity, max_ways=max_ways, hetero=True)
    rng = np.random.default_rng(seed)
    starts = [t for t in sp.compositions() if len(t) <= 5]
    cases = [(starts[rng.integers(0, len(starts))],
              rng.integers(1, 60, capacity).astype(np.float64))
             for _ in range(trials)]
    for cur, r in cases[:20]:           # argmins must be identical
        assert sp.suggest_improve(cur, r) == _legacy_suggest_improve(
            sp, cur, r, "warp_regroup"), (cur, r)
    t0 = time.perf_counter()
    for cur, r in cases:
        _legacy_suggest_improve(sp, cur, r, "warp_regroup")
    legacy_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for cur, r in cases:
        sp.suggest_improve(cur, r)
    fast_s = time.perf_counter() - t0
    out = {"capacity": capacity, "max_ways": max_ways, "trials": trials,
           "bench": "suggest_improve from 1-5 part topologies",
           "legacy_us_per_call": round(legacy_s / trials * 1e6, 1),
           "fast_us_per_call": round(fast_s / trials * 1e6, 1),
           "speedup": round(legacy_s / max(fast_s, 1e-12), 1)}
    print(f"suggest_improve microbench (capacity={capacity}, "
          f"max_ways={max_ways}): legacy {out['legacy_us_per_call']}us "
          f"-> fast {out['fast_us_per_call']}us "
          f"({out['speedup']}x)")
    return out


def write_timing_sidecar(result: Dict, path: str = TIMING_OUT) -> None:
    """Compact wall-clock sidecar uploaded by CI next to the full artifact."""
    timing = {"validation": result["validation"],
              "per_variant_wall_s": {
                  k: result[k]["wall_s"] for k in
                  ("static_fused", "static_split", "dynamic_threshold")},
              "object_baseline": result["object_baseline"]}
    with open(path, "w") as f:
        json.dump(timing, f, indent=1)


def main() -> Dict:
    import sys
    sys.path.insert(0, os.path.join(ROOT, "src"))
    ap = argparse.ArgumentParser()
    ap.add_argument("--groups", type=int, default=100)
    ap.add_argument("--capacity", type=int, default=8)
    ap.add_argument("--requests", type=int, default=100_000)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--budget-s", type=float, default=None,
                    help="fail if the vec sweep exceeds this wall budget")
    ap.add_argument("--min-speedup", type=float, default=None,
                    help="fail below this vec/object ticks-per-sec ratio")
    ap.add_argument("--out", default=OUT)
    ap.add_argument("--timing-out", default=TIMING_OUT)
    args = ap.parse_args()

    import jax

    from repro.configs import get_config
    from repro.models import transformer as T

    cfg = get_config("qwen3-14b", reduced=True)
    params, _ = T.init_model(jax.random.PRNGKey(0), cfg)
    rt = T.Runtime(production=False, remat=False)

    print(f"== fleet_scale sweep ({args.groups} groups x "
          f"{args.requests:,} requests) ==")
    result = fleet_scale_sweep(
        cfg, params, rt, groups=args.groups, capacity=args.capacity,
        n_requests=args.requests, seed=args.seed,
        budget_s=args.budget_s, min_speedup=args.min_speedup)
    result["suggest_split_microbench"] = suggest_split_microbench()

    # merge into the shared artifact rather than clobbering other sweeps
    merged = {}
    if os.path.exists(args.out):
        with open(args.out) as f:
            merged = json.load(f)
    merged["fleet_scale"] = result
    with open(args.out, "w") as f:
        json.dump(merged, f, indent=1)
    write_timing_sidecar(result, args.timing_out)
    print(f"wrote {os.path.abspath(args.out)} and "
          f"{os.path.abspath(args.timing_out)}")
    return result


if __name__ == "__main__":
    main()
