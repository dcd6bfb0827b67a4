"""Work-stealing demo: cross-group migration on an imbalanced fleet.

The chip-level scheduling story of ``repro.fleet.migrate``, end to end:

1. **KVTransferCost** — what moving a live request actually costs: KV
   bytes as a function of sequence length and the model config, turned
   into destination-part stall ticks by the link bandwidth.

2. **Fleet A/B** — replay one shard-skewed trace (``imbalanced_trace``:
   a hot router shard hammers one group under sticky routing while its
   neighbors starve) through the same fleet with migration disabled and
   enabled, and compare p99 latency plus the steal/migration counters.

    PYTHONPATH=src python examples/work_stealing.py --horizon 60
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--groups", type=int, default=4)
    ap.add_argument("--capacity", type=int, default=8)
    ap.add_argument("--horizon", type=int, default=60)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--arch", default="qwen3-14b")
    args = ap.parse_args()

    import jax

    from repro.configs import get_config
    from repro.configs.base import (AmoebaConfig, FleetConfig,
                                    MigrationConfig)
    from repro.fleet import FleetEngine, KVTransferCost, imbalanced_trace
    from repro.models import transformer as T

    cfg = get_config(args.arch, reduced=True)

    # -- 1: the transfer-cost model -----------------------------------------
    print("== KVTransferCost: what a live migration costs ==")
    cost = KVTransferCost(link_bandwidth=4e9)
    for seq in (16, 64, 256):
        b = cost.kv_bytes(seq, cfg)
        print(f"  seq_len={seq:4d}: {b/1e6:7.3f} MB "
              f"-> stall {cost.stall_ticks(seq, cfg):.0f} tick(s)")
    print(f"  zero-bandwidth link: stall = "
          f"{KVTransferCost(link_bandwidth=0).stall_ticks(64, cfg)} "
          f"(live migration never amortizes; steals still flow)")

    # -- 2: fleet A/B — stealing off vs on ----------------------------------
    print("\n== fleet: sticky routing on a shard-skewed trace ==")
    params, _ = T.init_model(jax.random.PRNGKey(0), cfg)
    rt = T.Runtime(production=False, remat=False)
    amoeba = AmoebaConfig(split_threshold=0.3, fuse_threshold=0.05,
                          min_phase_steps=2)
    for label, mig in (("no_stealing", MigrationConfig(enabled=False)),
                       ("stealing", MigrationConfig(enabled=True))):
        trace = imbalanced_trace(horizon=args.horizon,
                                 vocab_size=cfg.vocab_size,
                                 seed=args.seed, shards=args.groups)
        eng = FleetEngine(cfg, params, rt=rt,
                          fleet=FleetConfig(
                              num_groups=args.groups,
                              capacity=args.capacity,
                              router="sticky", mode="dynamic",
                              rebalance_every=4, migrate=mig,
                              amoeba=amoeba))
        eng.submit(trace)
        s = eng.run()
        lat = s["latency"]
        line = (f"  {label:12s} ticks={s['wall_ticks']:4d} "
                f"p50={lat['p50']:5.1f} p99={lat['p99']:5.1f} "
                f"util={s['utilization']:.2f}")
        mig_s = s.get("migration")
        if mig_s:
            line += (f"  steals={mig_s['steals']} "
                     f"live={mig_s['live_migrations']} "
                     f"stall={mig_s['stall_ticks']}")
        print(line)


if __name__ == "__main__":
    main()
