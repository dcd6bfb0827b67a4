"""Fleet-scale AMOEBA benchmark: static configurations, dynamic, policies.

Three chip-level sweeps, the serving translation of Fig 12:

**Mode sweep** — the three chip configurations the paper compares:

* ``static_fused``   — every pair permanently fused (big-SM-only chip),
* ``static_split``   — every pair permanently split (small-SM-only chip),
* ``amoeba_dynamic`` — every pair free to split/fuse on its own
  divergence signal, with length-aware routing onto the resulting
  heterogeneous mix.

**Policy sweep** — all-dynamic fleets differing only in the
``repro.control`` decision stack:

* ``threshold`` — fixed-ratio hysteresis (the paper's Fig 10/11 rule),
* ``predictor`` — §4.1.3's logistic model over live telemetry,
* ``online``    — predictor with periodic refits from the replay buffer,
* ``oracle``    — true slot-cost argmax: the upper bound.

**Composition sweep** — the heterogeneous-topology headline (§5,
Fig 12): identical all-dynamic oracle fleets on a *skewed* long-tail
trace, differing only in the topology space — the balanced equal-ways
ladders (2-way, 4-way) vs the full composition lattice with per-part
moves (``(5, 3)``-style cuts).  Validation records whether
heterogeneous topologies beat the best equal ladder on p99 latency or
slot efficiency, plus the compositions actually visited.

**Work-stealing sweep** — the chip-level migration subsystem
(``repro.fleet.migrate``): identical shard-skewed traces
(``imbalanced_trace`` — one hot router shard hammers one group under
sticky routing) replayed with cross-group stealing disabled and
enabled at equal capacity.  Validation records the p99 speedup and the
steal/live-migration/stall counters.

**Slack-lease sweep** — the sub-reconfiguration capacity-sharing tier
(``repro.fleet.lease``): a rotating transient-burst trace (hot phases
too brief for a topology change to amortize) replayed with
reconfiguration only, with work stealing, and with slack leases on top
of stealing.  Validation pins the lease p99 against steal-only and the
zero-stall contract (no reconfig stall is ever attributable to a
lease grant).

All runs replay byte-identical traces (same seed) and share the
process-wide compiled decode, so differences are purely scheduling.
Results (slot-step efficiency, p50/p95/p99 request latency, throughput,
churn, utilization, the Fig 20 per-feature ablation of the serve
predictor) go to ``BENCH_fleet.json`` at the repo root.

    PYTHONPATH=src python -m benchmarks.run fleet
    PYTHONPATH=src python benchmarks/fleet_bench.py --quick   # CI smoke
"""
from __future__ import annotations

import json
import os
from typing import Dict

ROOT = os.path.join(os.path.dirname(__file__), "..")
OUT = os.path.join(ROOT, "BENCH_fleet.json")


def composition_sweep(cfg, params, rt, *, groups: int,
                      capacity: int, horizon: int, seed: int) -> Dict:
    """Equal-ways ladders vs the heterogeneous composition lattice.

    Every run is an all-dynamic oracle fleet (the policy variable is
    pinned to the upper bound so the only difference is the *topology
    space*) replaying one skewed long-tail trace.
    """
    from repro.configs.base import AmoebaConfig, FleetConfig
    from repro.fleet import FleetEngine, skewed_longtail_trace

    base = AmoebaConfig(split_threshold=0.3, fuse_threshold=0.05,
                        min_phase_steps=2, policy="oracle")
    variants = {"equal_2way": base.replace(hetero=False, max_ways=2)}
    if capacity >= 4:
        variants["equal_4way"] = base.replace(hetero=False, max_ways=4)
    variants["hetero"] = base.replace(hetero=True,
                                      max_ways=min(capacity, 8))
    out: Dict = {}
    for label, amoeba in variants.items():
        trace = skewed_longtail_trace(horizon=horizon,
                                      vocab_size=cfg.vocab_size, seed=seed)
        eng = FleetEngine(cfg, params, rt=rt,
                          fleet=FleetConfig(
                              num_groups=groups, capacity=capacity,
                              router="length_aware", mode="dynamic",
                              amoeba=amoeba))
        eng.submit(trace)
        s = eng.run()
        if s["completed"] != len(trace):
            raise RuntimeError(f"{label}: completed {s['completed']} of "
                               f"{len(trace)} requests")
        out[label] = s
        lat = s["latency"]
        print(f"{label:12s} ticks={s['wall_ticks']:4d} "
              f"eff={s['efficiency']:.3f} p50={lat['p50']:5.1f} "
              f"p99={lat['p99']:5.1f} "
              f"hetero_topos={s['control'].get('hetero_topologies_visited', 0)}")
    equal = {k: v for k, v in out.items() if k.startswith("equal")}
    best_equal = min(equal, key=lambda k: (equal[k]["latency"]["p99"],
                                           -equal[k]["efficiency"]))
    be, he = out[best_equal], out["hetero"]
    out["validation"] = {
        "best_equal_ladder": best_equal,
        "hetero_p99_speedup_vs_equal": round(
            be["latency"]["p99"] / max(he["latency"]["p99"], 1e-9), 3),
        "hetero_efficiency_gain_vs_equal": round(
            he["efficiency"] / max(be["efficiency"], 1e-9), 3),
        "hetero_beats_equal": bool(
            he["latency"]["p99"] < be["latency"]["p99"]
            or he["efficiency"] > be["efficiency"]),
        "hetero_topologies_visited": he["control"].get(
            "topologies_visited", []),
    }
    return out


def work_stealing_sweep(cfg, params, rt, *, groups: int,
                        capacity: int, horizon: int, seed: int,
                        trace_out: str = None) -> Dict:
    """Cross-group work stealing on a shard-skewed trace, on vs off.

    Both runs use sticky (shard-affinity) routing on the imbalanced
    trace — one hot shard hammers one group while the rest starve —
    at equal capacity; the only difference is whether the
    ``repro.fleet.migrate`` planner may steal queued requests (and
    live-migrate KV-costed tails) across groups.
    """
    from repro.configs.base import AmoebaConfig, FleetConfig, MigrationConfig
    from repro.fleet import FleetEngine, imbalanced_trace

    amoeba = AmoebaConfig(split_threshold=0.3, fuse_threshold=0.05,
                          min_phase_steps=2)
    variants = {"no_stealing": MigrationConfig(enabled=False),
                "stealing": MigrationConfig(enabled=True)}
    out: Dict = {}
    for label, mig in variants.items():
        trace = imbalanced_trace(horizon=horizon, vocab_size=cfg.vocab_size,
                                 seed=seed, shards=groups)
        # the stealing run carries the full event stream when a trace
        # path was requested (repro.obs) — steals/reconfigs/decisions
        # land in the exported JSONL the CI round-trip check consumes
        obs_mode = "full" if trace_out and label == "stealing" else "off"
        eng = FleetEngine(cfg, params, rt=rt,
                          fleet=FleetConfig(
                              num_groups=groups, capacity=capacity,
                              router="sticky", mode="dynamic",
                              rebalance_every=4, migrate=mig,
                              amoeba=amoeba, obs=obs_mode))
        eng.submit(trace)
        s = eng.run()
        if obs_mode == "full":
            from repro.obs import write_jsonl
            n_ev = write_jsonl(trace_out, eng.obs.events(),
                               meta=eng.obs.meta)
            print(f"wrote {n_ev} events to {os.path.abspath(trace_out)}")
        if s["completed"] != len(trace):
            raise RuntimeError(f"{label}: completed {s['completed']} of "
                               f"{len(trace)} requests")
        out[label] = s
        lat = s["latency"]
        mig_s = s.get("migration", {})
        print(f"{label:12s} ticks={s['wall_ticks']:4d} "
              f"p50={lat['p50']:5.1f} p99={lat['p99']:5.1f} "
              f"steals={mig_s.get('steals', 0)} "
              f"live={mig_s.get('live_migrations', 0)} "
              f"stall={mig_s.get('stall_ticks', 0)}")
    off, on = out["no_stealing"], out["stealing"]
    mig_s = on.get("migration", {})
    out["validation"] = {
        "steal_p99_speedup": round(
            off["latency"]["p99"] / max(on["latency"]["p99"], 1e-9), 3),
        "stealing_beats_no_stealing": bool(
            on["latency"]["p99"] < off["latency"]["p99"]),
        "steals": mig_s.get("steals", 0),
        "live_migrations": mig_s.get("live_migrations", 0),
        "stall_ticks": mig_s.get("stall_ticks", 0),
        "rejected_amortization": mig_s.get("rejected_amortization", 0),
    }
    return out


def slack_lease_sweep(cfg, params, rt, *, groups: int,
                      capacity: int, horizon: int, seed: int) -> Dict:
    """Slack leases vs stealing vs re-cutting on a transient burst.

    The transient-burst trace rotates a short hot phase across shards —
    bursts too brief for a topology change to amortize, which is exactly
    the gap the lease planner fills.  Three identical-capacity sticky
    fleets replay the same trace:

    * ``reconfig_only`` — dynamic split/fuse is the only adaptation,
    * ``steal_only``    — plus cross-group work stealing,
    * ``lease``         — plus slack leases on top of stealing.

    Validation pins the tentpole contract: leases grant, the lease p99
    is no worse than steal-only, and not one reconfig stall tick is ever
    attributable to a lease grant.
    """
    from repro.configs.base import (AmoebaConfig, FleetConfig, LeaseConfig,
                                    MigrationConfig)
    from repro.fleet import FleetEngine, transient_burst_trace

    # a realistic dwell clock: the topology layer holds each phase long
    # enough that a burst_len-tick burst is gone before a re-cut can
    # amortize — the regime the lease tier exists for
    amoeba = AmoebaConfig(split_threshold=0.3, fuse_threshold=0.05,
                          min_phase_steps=8)
    burst_len = max(6, horizon // (2 * groups))
    variants = {
        "reconfig_only": (MigrationConfig(enabled=False),
                          LeaseConfig(enabled=False)),
        "steal_only": (MigrationConfig(enabled=True),
                       LeaseConfig(enabled=False)),
        "lease": (MigrationConfig(enabled=True), LeaseConfig(enabled=True)),
    }
    out: Dict = {}
    for label, (mig, lease) in variants.items():
        trace = transient_burst_trace(horizon=horizon,
                                      vocab_size=cfg.vocab_size,
                                      seed=seed, shards=groups,
                                      burst_len=burst_len)
        eng = FleetEngine(cfg, params, rt=rt,
                          fleet=FleetConfig(
                              num_groups=groups, capacity=capacity,
                              router="sticky", mode="dynamic",
                              rebalance_every=4, migrate=mig,
                              lease=lease, amoeba=amoeba))
        eng.submit(trace)
        s = eng.run()
        if s["completed"] != len(trace):
            raise RuntimeError(f"{label}: completed {s['completed']} of "
                               f"{len(trace)} requests")
        out[label] = s
        lat = s["latency"]
        ls = s.get("lease", {})
        print(f"{label:14s} ticks={s['wall_ticks']:4d} "
              f"p50={lat['p50']:5.1f} p99={lat['p99']:5.1f} "
              f"grants={ls.get('grants', 0)} "
              f"revokes={ls.get('revokes', 0)} "
              f"expires={ls.get('expires', 0)} "
              f"slot_ticks_lent={ls.get('slot_ticks_lent', 0)}")
    rec, steal, lea = out["reconfig_only"], out["steal_only"], out["lease"]
    ls = lea["lease"]
    out["validation"] = {
        "lease_p99_speedup_vs_steal_only": round(
            steal["latency"]["p99"] / max(lea["latency"]["p99"], 1e-9), 3),
        "lease_p99_speedup_vs_reconfig_only": round(
            rec["latency"]["p99"] / max(lea["latency"]["p99"], 1e-9), 3),
        "lease_no_worse_than_steal_only": bool(
            lea["latency"]["p99"] <= steal["latency"]["p99"]),
        "lease_p50_speedup_vs_steal_only": round(
            steal["latency"]["p50"] / max(lea["latency"]["p50"], 1e-9), 3),
        "grants": ls["grants"],
        "revokes": ls["revokes"],
        "expires": ls["expires"],
        "slot_ticks_lent": ls["slot_ticks_lent"],
        "rejected_amortization": ls["rejected_amortization"],
        # the zero-stall contract: a lease is pure bookkeeping — no
        # topology move, no dwell clock, no reconfig stall, ever
        "lease_stall_ticks_charged": ls["stall_ticks_charged"],
        "zero_stall_contract_holds": bool(ls["stall_ticks_charged"] == 0),
        "leases_granted_and_returned": bool(
            ls["grants"] > 0
            and ls["grants"] == ls["revokes"] + ls["expires"]
            and ls["active"] == 0),
    }
    return out


def cluster_hierarchy_sweep(cfg, params, rt, *, capacity: int,
                            horizon: int, seed: int, chips: int = 2,
                            groups_per_chip: int = 2) -> Dict:
    """Hierarchical vs distance-blind control on a 2D chip mesh.

    Both runs drive the same multi-chip imbalanced trace (one hot chip
    bursts fat-tailed work while the others trickle) through identical
    capacity on the same tiered physics — slow, high-latency inter-chip
    links under a near-free NoC.  The only difference is the planner's
    *cost model*: ``hierarchical`` plans chip-first and authorizes
    crossings only when the tiered cost amortizes, while ``flat_blind``
    (``ClusterConfig.distance_blind``) plans over one flat pool as if
    every pair were NoC-close — and execution charges it the physical
    prices anyway, which is how blind stealing thrashes slow links.  A
    third run re-prices the inter-chip tiers at zero bandwidth to pin
    the veto contract: every cross-chip move is refused while intra-chip
    migration keeps flowing.
    """
    from repro.configs.base import (AmoebaConfig, ClusterConfig, FleetConfig,
                                    MigrationConfig)
    from repro.cluster import ClusterEngine
    from repro.fleet import multichip_imbalanced_trace

    groups = chips * groups_per_chip
    amoeba = AmoebaConfig(split_threshold=0.3, fuse_threshold=0.05,
                          min_phase_steps=2)
    mig = MigrationConfig(enabled=True, live=True)
    # slow high-latency links under a near-free NoC — the regime where
    # ignoring geometry costs the most — with enough cross-steal budget
    # that the amortization bar, not the cap, separates the two planners
    tiers = ClusterConfig(groups_per_chip=groups_per_chip,
                          noc_bandwidth=4e9, noc_latency=0.0,
                          link_bandwidth=256.0, link_latency=12.0,
                          net_bandwidth=64.0, net_latency=24.0,
                          max_cross_steals=4)
    variants = {"flat_blind": tiers.replace(distance_blind=True),
                "hierarchical": tiers,
                "zero_interchip": tiers.replace(link_bandwidth=0.0,
                                                net_bandwidth=0.0)}
    out: Dict = {"config": {"chips": chips,
                            "groups_per_chip": groups_per_chip,
                            "capacity": capacity,
                            "link_bandwidth": tiers.link_bandwidth,
                            "link_latency": tiers.link_latency}}
    for label, ccfg in variants.items():
        trace = multichip_imbalanced_trace(
            horizon=horizon, vocab_size=cfg.vocab_size, seed=seed,
            chips=chips, groups_per_chip=groups_per_chip)
        eng = ClusterEngine(cfg, params, rt=rt,
                            fleet=FleetConfig(
                                num_groups=groups, capacity=capacity,
                                router="sticky", mode="dynamic",
                                rebalance_every=4, migrate=mig,
                                amoeba=amoeba, cluster=ccfg))
        eng.submit(trace)
        s = eng.run()
        if s["completed"] != len(trace):
            raise RuntimeError(f"{label}: completed {s['completed']} of "
                               f"{len(trace)} requests")
        out[label] = s
        lat, m = s["latency"], s["migration"]
        print(f"{label:14s} ticks={s['wall_ticks']:4d} "
              f"p50={lat['p50']:5.1f} p99={lat['p99']:5.1f} "
              f"steals={m['steals']} (noc={m['intra_chip_steals']} "
              f"x={m['cross_chip_steals']}) "
              f"live={m['live_migrations']} (noc={m['intra_chip_live']} "
              f"x={m['cross_chip_live']}) "
              f"vetoed={m['vetoed_cross_chip']} "
              f"link_stall={s['cluster']['tier_stall_ticks']['link']}")
    flat, hier = out["flat_blind"], out["hierarchical"]
    zero = out["zero_interchip"]
    zm = zero["migration"]
    out["validation"] = {
        "hierarchical_p99_speedup_vs_flat": round(
            flat["latency"]["p99"] / max(hier["latency"]["p99"], 1e-9), 3),
        "hierarchical_beats_flat": bool(
            hier["latency"]["p99"] <= flat["latency"]["p99"]),
        "flat_interchip_stall_ticks":
            flat["cluster"]["tier_stall_ticks"]["link"]
            + flat["cluster"]["tier_stall_ticks"]["net"],
        "hier_interchip_stall_ticks":
            hier["cluster"]["tier_stall_ticks"]["link"]
            + hier["cluster"]["tier_stall_ticks"]["net"],
        "hier_cross_chip_steals": hier["migration"]["cross_chip_steals"],
        "hier_vetoed_cross_chip": hier["migration"]["vetoed_cross_chip"],
        # the veto contract: dead inter-chip tiers stop every crossing
        # while the NoC keeps migrating
        "zero_bw_cross_moves": zm["cross_chip_steals"]
            + zm["cross_chip_live"],
        "zero_bw_intra_moves": zm["intra_chip_steals"]
            + zm["intra_chip_live"],
        "zero_bw_vetoes_crossings_intra_flows": bool(
            zm["cross_chip_steals"] + zm["cross_chip_live"] == 0
            and zm["intra_chip_steals"] + zm["intra_chip_live"] > 0),
    }
    return out


def fleet_bench(groups: int = 4, capacity: int = 8, horizon: int = 120,
                seed: int = 0, out_path: str = OUT,
                scale_groups: int = 100,
                scale_requests: int = 100_000,
                trace_out: str = None) -> Dict:
    import jax

    from repro.configs import get_config
    from repro.configs.base import AmoebaConfig
    from repro.control import (build_serve_corpus, serve_feature_ablation,
                               train_serve_predictor)
    from repro.fleet import (bursty_longtail_trace, replay_modes,
                             replay_policies)
    from repro.models import transformer as T

    cfg = get_config("qwen3-14b", reduced=True)
    params, _ = T.init_model(jax.random.PRNGKey(0), cfg)
    rt = T.Runtime(production=False, remat=False)
    amoeba = AmoebaConfig(split_threshold=0.3, fuse_threshold=0.05,
                          min_phase_steps=2)
    trace_factory = lambda: bursty_longtail_trace(
        horizon=horizon, vocab_size=cfg.vocab_size, seed=seed)

    # the policy sweep runs the full k-way topology ladder (1x8/2x4/4x2
    # for capacity 8) — the learned policies' edge over the fixed-ratio
    # rule comes precisely from knowing when the deeper splits pay
    ladder = amoeba.replace(max_ways=4 if capacity >= 4 else 2)
    out: Dict = {"config": {"groups": groups, "capacity": capacity,
                            "horizon": horizon, "seed": seed,
                            "trace": "bursty_longtail",
                            "policy_sweep_max_ways": ladder.max_ways}}

    print("== mode sweep (Fig 12 chip configurations) ==")
    out.update(replay_modes(cfg, params, rt, trace_factory,
                            groups=groups, capacity=capacity, amoeba=amoeba))

    print("\n== policy sweep (repro.control decision stacks) ==")
    model, minfo = train_serve_predictor(capacity=capacity,
                                         max_ways=ladder.max_ways,
                                         label_margin=ladder.label_margin)
    pol = replay_policies(cfg, params, rt, trace_factory,
                          groups=groups, capacity=capacity, amoeba=ladder,
                          model=model)
    out["policies"] = pol
    # the Fig 20 ablation: which serve feature carries the decision?
    Xc, yc = build_serve_corpus(n_samples=512, capacity=capacity,
                                max_ways=ladder.max_ways,
                                label_margin=ladder.label_margin)
    ablation = serve_feature_ablation(model, Xc, yc, steps=250)
    # sibling key, not inside "policies": keeps that mapping homogeneous
    # (one run summary per policy name) for downstream consumers
    out["predictor_model"] = {
        "train_accuracy": round(minfo["train_accuracy"], 4),
        "n": minfo["n"],
        "final_nll": round(minfo["final_nll"], 5),
        "feature_ablation": ablation,
    }
    top_feat = max(ablation, key=lambda k: ablation[k]["mean_abs_impact"])
    print("fig20 ablation: " + "  ".join(
        f"{k}={v['mean_abs_impact']:.2f}" for k, v in ablation.items())
        + f"  (dominant: {top_feat})")

    # drop compiled executables between sweeps: the accumulated jitted
    # shapes from dozens of engine replays can exhaust the CPU JIT's
    # mmap budget in one long-lived process (LLVM "Cannot allocate
    # memory"); each sweep recompiles what it needs
    jax.clear_caches()
    print("\n== composition sweep (heterogeneous vs equal ladders) ==")
    out["composition_sweep"] = composition_sweep(
        cfg, params, rt, groups=groups,
        capacity=capacity, horizon=horizon, seed=seed)

    jax.clear_caches()
    print("\n== work-stealing sweep (imbalanced trace, sticky routing) ==")
    out["work_stealing"] = work_stealing_sweep(
        cfg, params, rt, groups=groups,
        capacity=capacity, horizon=horizon, seed=seed,
        trace_out=trace_out)

    jax.clear_caches()
    print("\n== slack lease sweep (transient bursts, sticky routing) ==")
    out["slack_lease"] = slack_lease_sweep(
        cfg, params, rt, groups=groups,
        capacity=capacity, horizon=horizon, seed=seed)

    jax.clear_caches()
    print("\n== cluster hierarchy sweep (2D mesh, tiered links) ==")
    out["cluster_hierarchy"] = cluster_hierarchy_sweep(
        cfg, params, rt, capacity=capacity,
        horizon=horizon, seed=seed)

    jax.clear_caches()
    print(f"\n== fleet_scale sweep ({scale_groups} groups x "
          f"{scale_requests:,} requests, vec engine) ==")
    try:                                    # package vs direct execution
        from benchmarks.fleet_scale_bench import (fleet_scale_sweep,
                                                  obs_overhead_sweep,
                                                  suggest_split_microbench,
                                                  write_timing_sidecar)
    except ImportError:
        from fleet_scale_bench import (fleet_scale_sweep,
                                       obs_overhead_sweep,
                                       suggest_split_microbench,
                                       write_timing_sidecar)
    out["fleet_scale"] = fleet_scale_sweep(
        cfg, params, rt, groups=scale_groups, capacity=capacity,
        n_requests=scale_requests, seed=seed)
    out["fleet_scale"]["suggest_split_microbench"] = \
        suggest_split_microbench()
    write_timing_sidecar(out["fleet_scale"])

    print("\n== obs overhead microbench (event stream off/summary/full) ==")
    out["obs_overhead"] = obs_overhead_sweep(
        cfg, rt, groups=min(scale_groups, 20), capacity=capacity,
        n_requests=min(scale_requests, 20_000), seed=seed)

    dyn, fus = out["amoeba_dynamic"], out["static_fused"]
    thr = pol["threshold"]
    learned = {n: pol[n] for n in ("predictor", "online") if n in pol}
    best_learned = min(
        learned, key=lambda n: (learned[n]["latency"]["p99"],
                                -learned[n]["efficiency"]))
    bl = learned[best_learned]
    out["validation"] = {
        "p99_speedup_vs_fused": round(
            fus["latency"]["p99"] / max(dyn["latency"]["p99"], 1e-9), 3),
        "efficiency_gain_vs_fused": round(
            dyn["efficiency"] / max(fus["efficiency"], 1e-9), 3),
        "dynamic_beats_fused": bool(
            dyn["latency"]["p99"] < fus["latency"]["p99"]
            and dyn["efficiency"] > fus["efficiency"]),
        # policy sweep: a learned policy must beat the threshold rule on
        # p99 latency or efficiency; the oracle is the upper bound
        "best_learned_policy": best_learned,
        "learned_p99_speedup_vs_threshold": round(
            thr["latency"]["p99"] / max(bl["latency"]["p99"], 1e-9), 3),
        "learned_efficiency_gain_vs_threshold": round(
            bl["efficiency"] / max(thr["efficiency"], 1e-9), 3),
        "learned_beats_threshold": bool(
            bl["latency"]["p99"] < thr["latency"]["p99"]
            or bl["efficiency"] > thr["efficiency"]),
        "oracle_p99": pol["oracle"]["latency"]["p99"],
        "oracle_efficiency": pol["oracle"]["efficiency"],
    }
    v = out["validation"]
    print(f"\nAMOEBA-dynamic vs static-fused: "
          f"p99 {v['p99_speedup_vs_fused']:.2f}x, "
          f"efficiency {v['efficiency_gain_vs_fused']:.2f}x, "
          f"wins both: {v['dynamic_beats_fused']}")
    print(f"{best_learned} vs threshold: "
          f"p99 {v['learned_p99_speedup_vs_threshold']:.2f}x, "
          f"efficiency {v['learned_efficiency_gain_vs_threshold']:.2f}x, "
          f"wins either: {v['learned_beats_threshold']} "
          f"(oracle bound: p99={v['oracle_p99']:.1f}, "
          f"eff={v['oracle_efficiency']:.3f})")
    cv = out["composition_sweep"]["validation"]
    print(f"hetero vs {cv['best_equal_ladder']}: "
          f"p99 {cv['hetero_p99_speedup_vs_equal']:.2f}x, "
          f"efficiency {cv['hetero_efficiency_gain_vs_equal']:.2f}x, "
          f"wins either: {cv['hetero_beats_equal']}")
    wv = out["work_stealing"]["validation"]
    print(f"stealing vs no-stealing: p99 {wv['steal_p99_speedup']:.2f}x, "
          f"steals={wv['steals']} live={wv['live_migrations']}, "
          f"wins: {wv['stealing_beats_no_stealing']}")
    lv = out["slack_lease"]["validation"]
    print(f"lease vs steal-only: "
          f"p99 {lv['lease_p99_speedup_vs_steal_only']:.2f}x "
          f"(vs reconfig-only "
          f"{lv['lease_p99_speedup_vs_reconfig_only']:.2f}x), "
          f"grants={lv['grants']} lent={lv['slot_ticks_lent']} "
          f"slot-ticks, zero-stall: {lv['zero_stall_contract_holds']}")
    hv = out["cluster_hierarchy"]["validation"]
    print(f"hierarchical vs flat-blind: "
          f"p99 {hv['hierarchical_p99_speedup_vs_flat']:.2f}x, "
          f"interchip stall {hv['flat_interchip_stall_ticks']} -> "
          f"{hv['hier_interchip_stall_ticks']} ticks, "
          f"wins: {hv['hierarchical_beats_flat']}; zero-bw veto holds: "
          f"{hv['zero_bw_vetoes_crossings_intra_flows']}")
    sv = out["fleet_scale"]["validation"]
    print(f"vec engine at scale: {sv['vec_speedup_ticks_per_sec']:,}x "
          f"ticks/sec vs object ({sv['vec_ticks_per_sec']:,} vs "
          f"{sv['object_ticks_per_sec']}), "
          f"vec sweep wall {sv['vec_total_wall_s']}s")
    ov = out["obs_overhead"]
    print(f"obs overhead: off {ov['off_overhead_frac']:+.2%} "
          f"(<=2%: {ov['validation']['off_within_2pct']}), "
          f"full {ov['full_overhead_frac']:+.2%} "
          f"(<=15%: {ov['validation']['full_within_15pct']})")
    with open(out_path, "w") as f:
        json.dump(out, f, indent=1)
    print(f"wrote {os.path.abspath(out_path)}")
    return out


if __name__ == "__main__":
    import argparse
    import sys
    sys.path.insert(0, os.path.join(ROOT, "src"))
    ap = argparse.ArgumentParser()
    ap.add_argument("--groups", type=int, default=4)
    ap.add_argument("--capacity", type=int, default=8)
    ap.add_argument("--horizon", type=int, default=120)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=OUT)
    ap.add_argument("--trace-out",
                    default=os.path.join(ROOT, "BENCH_fleet_trace.jsonl"),
                    help="JSONL event trace from the work_stealing sweep "
                         "(empty string disables)")
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke: small fleet, short trace")
    args = ap.parse_args()
    scale_groups, scale_requests = 100, 100_000
    if args.quick:
        args.groups, args.capacity, args.horizon = 2, 4, 40
        scale_groups, scale_requests = 12, 5_000
    fleet_bench(groups=args.groups, capacity=args.capacity,
                horizon=args.horizon, seed=args.seed, out_path=args.out,
                scale_groups=scale_groups, scale_requests=scale_requests,
                trace_out=args.trace_out or None)
