"""Benchmark driver: one harness per paper table/figure + the mesh-level
AMOEBA analyses.

    PYTHONPATH=src python -m benchmarks.run            # everything
    PYTHONPATH=src python -m benchmarks.run fig12 fleet

Writes machine-readable results to experiments/bench_results.json.
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from benchmarks import figures, fleet_bench, mesh_amoeba  # noqa: E402
from repro.compile_cache import enable_compile_cache  # noqa: E402

OUT = os.path.join(os.path.dirname(__file__), "..", "experiments",
                   "bench_results.json")

BENCHES = {
    "fig12": figures.fig12_performance,
    "fig13": figures.fig13_stalls,
    "fig14_16": figures.fig14_16_memory,
    "fig17_18": figures.fig17_18_noc,
    "fig19": figures.fig19_dynamics,
    "fig20": figures.fig20_predictor,
    "fig21": figures.fig21_dws,
    "mesh_plan_selection": mesh_amoeba.plan_selection,
    "serving_regroup": mesh_amoeba.serving_regroup,
    "fleet": fleet_bench.fleet_bench,
}


def main() -> None:
    enable_compile_cache()
    wanted = sys.argv[1:] or list(BENCHES)
    results = {}
    for name in wanted:
        fn = BENCHES[name]
        print(f"\n======== {name} ========")
        t0 = time.time()
        results[name] = {"result": fn(), "seconds": round(time.time() - t0, 2)}
        print(f"[{name}: {results[name]['seconds']}s]")

    # headline validation summary (reproduction vs paper)
    if "fig12" in results and "fig21" in results:
        v = results["fig12"]["result"]["validation"]
        d = results["fig21"]["result"]
        print("\n======== validation vs paper ========")
        print(f"SM speedup        {v['SM_speedup']:.2f}  (paper 4.25)")
        print(f"MUM speedup       {v['MUM_speedup']:.2f}  (paper 2.11)")
        print(f"geomean           {v['geomean']:.3f} (paper ~1.47)")
        print(f"regroup/direct    {v['regroup_over_direct']:.3f} (paper ~1.16)")
        print(f"AMOEBA/DWS        {d['geomean']:.3f} (paper ~1.27)")
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "w") as f:
        json.dump(results, f, indent=1, default=str)
    print(f"\nwrote {OUT}")


if __name__ == "__main__":
    main()
