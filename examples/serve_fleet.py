"""Fleet serving demo: N reconfigurable pairs vs the static chips.

Replays one bursty long-tail multi-tenant trace through three fleet
configurations (all-fused, all-split, AMOEBA-dynamic with length-aware
routing) and prints the fleet-wide telemetry plus a per-group breakdown
for the dynamic run — the chip-level view the single-pair demo
(``serve_amoeba.py``) cannot show.

    PYTHONPATH=src python examples/serve_fleet.py --groups 4 --horizon 120
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--groups", type=int, default=4)
    ap.add_argument("--capacity", type=int, default=8)
    ap.add_argument("--horizon", type=int, default=120)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--arch", default="qwen3-14b")
    args = ap.parse_args()

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    from repro.configs import get_config
    from repro.configs.base import AmoebaConfig
    from repro.fleet import bursty_longtail_trace, replay_modes
    from repro.models import transformer as T

    cfg = get_config(args.arch, reduced=True)
    params, _ = T.init_model(jax.random.PRNGKey(0), cfg)
    rt = T.Runtime(production=False, remat=False)

    summaries = replay_modes(
        cfg, params, rt,
        lambda: bursty_longtail_trace(horizon=args.horizon,
                                      vocab_size=cfg.vocab_size,
                                      seed=args.seed),
        groups=args.groups, capacity=args.capacity,
        amoeba=AmoebaConfig(split_threshold=0.3, fuse_threshold=0.05,
                            min_phase_steps=2))

    dyn = summaries["amoeba_dynamic"]
    print("\namoeba_dynamic per-group:")
    for g in dyn["groups"]:
        print(f"  g{g['gid']} split={str(g['is_split']):5s} "
              f"eff={g['efficiency']:.3f} "
              f"splits={g['splits']} fuses={g['fuses']} "
              f"completed={g['completed']}")
    if "per_tenant" in dyn:
        for t, ts in dyn["per_tenant"].items():
            print(f"  tenant {t:6s} n={ts['n']:3d} "
                  f"p50={ts['p50']:5.1f} p99={ts['p99']:5.1f}")
    fus = summaries["static_fused"]
    print(f"\ndynamic vs static-fused: "
          f"p99 {fus['latency']['p99'] / max(dyn['latency']['p99'], 1e-9):.2f}x, "
          f"efficiency {dyn['efficiency'] / max(fus['efficiency'], 1e-9):.2f}x")


if __name__ == "__main__":
    main()
