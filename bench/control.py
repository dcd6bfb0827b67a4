"""Readings from which a cell's ``max_logit_gap`` limit is set.

    python3 bench/control.py --workload <cell> --seeds 11,12,... \
        --seconds 51 --controls int8,fp8

For each seed, in one process: the cell's serving at its own load for a
window, its drain, and the comparison, as a benchmark run makes them
(``run.serve_cell``), plus each control put in the program's place: the
reference with its weights rounded to a lower precision picks the token
at each served position, on the same inputs, and the same comparison
judges those tokens.  The warm-up runs once, for the first seed.

The program's readings over a dozen seeds give the limit's lower reading;
the least of the controls' readings its upper one.  Each seed's line and a
summary are written to ``chiprun_out/control/<cell>.jsonl``.  The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--controls", default="int8,fp8")
    args = ap.parse_args(argv)
    spec = run.load_cell(args.workload)
    devs, peaks = run.require_device(spec["cell"]["chips"])
    import system  # noqa: F401  (puts the program on the path)
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    out_dir = os.path.join(run.ROOT, "chiprun_out", "control")
    os.makedirs(out_dir, exist_ok=True)
    controls = [c for c in args.controls.split(",") if c]
    rows = []
    with open(os.path.join(out_dir, f"{args.workload}.jsonl"), "w") as f:
        for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
            line = run.serve_cell(spec, seed, args.seconds, False, devs,
                                  peaks, out_dir, warm=i == 0,
                                  controls=controls)
            row = {"seed": seed, "correct": line["correct"],
                   "attempted": line["attempted"], "failed": line["failed"],
                   "program_gap": line["compared"]["max_logit_gap"]["value"],
                   "control_gaps": {
                       q: c["compared"]["max_logit_gap"]["value"]
                       for q, c in line["controls"].items()},
                   "control_correct": {q: c["correct"] for q, c
                                       in line["controls"].items()},
                   "reconfigs": line["reconfigs"],
                   "metrics": {k: v["value"]
                               for k, v in line["metrics"].items()},
                   "reference_s": line["reference_s"],
                   "memory_peak_bytes": line["device"]["memory_peak_bytes"]}
            rows.append(row)
            f.write(json.dumps(row) + "\n")
            f.flush()
            print(json.dumps(row), flush=True)
        summary = {"workload": args.workload, "seeds": len(rows),
                   "program_max": max(r["program_gap"] for r in rows),
                   "control_min": {c: min(r["control_gaps"][c] for r in rows)
                                   for c in controls},
                   "control_ever_correct": {
                       c: any(r["control_correct"][c] for r in rows)
                       for c in controls}}
        f.write(json.dumps(summary) + "\n")
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
