"""Heterogeneous-topology demo: the composition lattice end to end.

Walks the paper's §5 headline capability — "dynamic creation of
heterogeneous SMs through independent fusing or splitting" — at the
three levels of this reproduction:

1. **ConfigSpace lattice** — enumerate the composition topologies of a
   capacity-8 group, show the skew-aware partitioner picking the
   ``(5, 3)`` cut that no equal-ways ladder can express.

2. **GroupController walk** — feed a skewed batch through the oracle
   policy and watch the controller climb the lattice one amortization-
   checked per-part move at a time.

3. **gpusim static chips (Fig 12)** — rank heterogeneous chip
   compositions (n fused pairs + rest split) and see workloads whose
   best static chip is a *mix*, not either homogeneous end.

4. **Fleet A/B** — replay one skewed long-tail trace through an
   equal-ladder fleet and a heterogeneous-composition fleet and compare
   p99 latency / slot efficiency.

    PYTHONPATH=src python examples/hetero_topology.py --horizon 60
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--groups", type=int, default=2)
    ap.add_argument("--capacity", type=int, default=8)
    ap.add_argument("--horizon", type=int, default=60)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--arch", default="qwen3-14b")
    ap.add_argument("--skip-fleet", action="store_true",
                    help="lattice + gpusim only (no model init)")
    args = ap.parse_args()

    import numpy as np

    from repro.control import (ConfigSpace, FeatureVector, GroupController,
                               OraclePolicy, topology_name)

    # -- 1: the composition lattice -----------------------------------------
    print("== ConfigSpace: composition lattice ==")
    sp = ConfigSpace(capacity=args.capacity, max_ways=args.capacity)
    comps = sp.compositions()
    print(f"capacity={args.capacity}: {len(comps)} topologies "
          f"(ladder had {len(sp.topologies())})")
    skew = np.array([2.0, 2.0, 2.0, 2.0, 2.0, 90.0, 90.0, 90.0]
                    [:args.capacity])
    best, gain = sp.best_topology(skew)
    print(f"skewed batch {skew.astype(int).tolist()}:")
    print(f"  best topology   {topology_name(best, args.capacity):10s} "
          f"gain={gain:.3f}")
    print(f"  balanced pair   {topology_name(2, args.capacity):10s} "
          f"gain={sp.gain(skew, 2):.3f}")
    parts = sp.partition(list(range(skew.size)), skew, best)
    for slots, p in zip(best, parts):
        lens = [int(skew[i]) for i in p]
        print(f"  part x{slots} slots <- remaining {lens}")

    # -- 2: the controller climbs the lattice -------------------------------
    print("\n== GroupController: per-part moves under the oracle ==")
    gc = GroupController(OraclePolicy(space=sp, margin=0.01), sp, dwell=1)
    fv = FeatureVector.from_group(skew, 0, 0.0, args.capacity)
    for _ in range(6):
        gc.observe(fv)
    for step, frm, to, g, reason in gc.state.transitions:
        print(f"  tick {step}: {sp.name(frm)} -> {sp.name(to)} "
              f"(gain {g:.3f}; {reason})")

    # -- 3: gpusim heterogeneous static chips (Fig 12) ----------------------
    print("\n== gpusim: static chip-composition ranking ==")
    from repro.core.gpusim import WORKLOADS, rank_chip_mixes
    for name in ("SM", "RAY", "CP"):
        rows = rank_chip_mixes(WORKLOADS[name], epochs=16)
        tag = " <- heterogeneous wins" \
            if 0 < rows[0]["n_fused"] < 24 else ""
        print(f"  {name:4s} best {rows[0]['mix']:8s} "
              f"ipc={rows[0]['ipc']:.1f}{tag}")

    if args.skip_fleet:
        return

    # -- 4: fleet A/B — ladder vs compositions ------------------------------
    print("\n== fleet: equal ladder vs heterogeneous compositions ==")
    import jax

    from repro.configs import get_config
    from repro.configs.base import AmoebaConfig, FleetConfig
    from repro.fleet import FleetEngine, skewed_longtail_trace
    from repro.models import transformer as T

    cfg = get_config(args.arch, reduced=True)
    params, _ = T.init_model(jax.random.PRNGKey(0), cfg)
    rt = T.Runtime(production=False, remat=False)
    base = AmoebaConfig(split_threshold=0.3, fuse_threshold=0.05,
                        min_phase_steps=2, policy="oracle",
                        max_ways=min(args.capacity, 8))
    for label, hetero in (("equal-ladder", False), ("heterogeneous", True)):
        trace = skewed_longtail_trace(horizon=args.horizon,
                                      vocab_size=cfg.vocab_size,
                                      seed=args.seed)
        eng = FleetEngine(cfg, params, rt=rt,
                          fleet=FleetConfig(
                              num_groups=args.groups,
                              capacity=args.capacity,
                              router="length_aware", mode="dynamic",
                              amoeba=base.replace(hetero=hetero)))
        eng.submit(trace)
        s = eng.run()
        lat = s["latency"]
        topos = s["control"].get("topologies_visited", [])
        print(f"  {label:14s} eff={s['efficiency']:.3f} "
              f"p50={lat['p50']:5.1f} p99={lat['p99']:5.1f} "
              f"topologies={['+'.join(map(str, t)) for t in topos]}")


if __name__ == "__main__":
    main()
