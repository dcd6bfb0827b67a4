"""Serving launcher: AMOEBA policy comparison on a real decode workload.

Runs the engine three times on the identical request trace — fused
baseline, direct_split, warp_regroup — and reports slot-efficiency,
makespan, and the split/fuse dynamics (paper Fig 12/19 at the mesh level).

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-14b \
        --requests 24 --capacity 8
"""
from __future__ import annotations

import argparse
import json

import jax
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import ARCH_IDS, get_config
from repro.configs.base import AmoebaConfig
from repro.models import transformer as T
from repro.serve import Request, ServeEngine


def make_requests(cfg, n: int, seed: int):
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        plen = int(rng.choice([8, 16, 32]))
        mx = int(rng.choice([4, 8, 16, 64], p=[0.3, 0.3, 0.2, 0.2]))
        reqs.append(Request(i, list(map(int, rng.integers(
            0, cfg.vocab_size, plen))), mx))
    return reqs


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-14b", choices=ARCH_IDS)
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--capacity", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch, reduced=True)
    params, _ = T.init_model(jax.random.PRNGKey(0), cfg)

    report = {}
    for name, dynamic, policy in [("fused_baseline", False, "warp_regroup"),
                                  ("direct_split", True, "direct_split"),
                                  ("warp_regroup", True, "warp_regroup")]:
        eng = ServeEngine(cfg, params, amoeba=AmoebaConfig(
            regroup_policy=policy, split_threshold=0.3,
            fuse_threshold=0.05, min_phase_steps=2),
            capacity=args.capacity)
        eng.submit(make_requests(cfg, args.requests, args.seed))
        st = eng.run(dynamic=dynamic)
        report[name] = {
            "ticks": st.ticks, "slot_steps": st.slot_steps,
            "useful_tokens": st.useful_tokens,
            "efficiency": round(st.efficiency, 4),
            "splits": st.splits, "fuses": st.fuses,
            "completed": st.completed,
        }
    base = report["fused_baseline"]["efficiency"]
    for k in report:
        report[k]["vs_fused"] = round(report[k]["efficiency"] / base, 3)
    print(json.dumps(report, indent=1))


if __name__ == "__main__":
    main()
