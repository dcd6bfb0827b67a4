"""Find the highest rate a cell sustains: a sweep of arrival rates on the
chip, in one process (the warm-up runs once).

    python3 bench/sweep.py --workload <cell> --rates 0.4,0.6,0.8 \
        --seconds 51 --seed <n> [--drain 60]

For each rate the cell's traffic file is served open loop at that rate
for ``--seconds``; the line printed per rate holds the end-to-end metrics,
the queue (requests routed but not yet admitted) at the window's end and
its mean over the window, and how long the drain took.  A rate's backlog
grows when the queue at the window's end exceeds one and a half times its
mean over the window, plus one request (a growing queue ends near twice
its mean; a steady one near it).  The knee is the highest rate below
which no swept rate grows.  A cell's fixed ``rate_rps`` is about four
fifths of the knee; ``--write-rate`` writes that, rounded to 0.05, into
the cell's traffic file.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import copy
import json
import os
import sys

import run


def _serve_rates(sut, fleet, spec: dict, args) -> list:
    """Serve the cell at each rate of ``--rates``, one engine each, after
    one warm-up; returns a row per rate."""
    import jax

    import generator
    import openloop
    import system
    cfg_file, mix0 = spec["cfg"], spec["mix"]
    params = jax.block_until_ready(sut.make_params(args.seed))
    sut.warm_up(params, fleet, mix0["prompt_buckets"],
                cfg_file["vocab_size"])
    rows = []
    for rate in (float(r) for r in args.rates.split(",")):
        mix = copy.deepcopy(mix0)
        mix["arrivals"]["rate_rps"] = rate
        eng = sut.make_engine(params, fleet)
        arrivals = generator.schedule(mix, args.seconds, args.seed,
                                      cfg_file["vocab_size"])
        res = openloop.drive(eng, arrivals, args.seconds, args.drain,
                             system.make_request)
        inside = [t.queue for t in res.ticks if t.t <= args.seconds]
        e2e = openloop.end_to_end(res)
        row = {"rate_rps": rate, "sent": len(res.tracked),
               "unfinished": len(openloop.failures(res)),
               "queue_end": inside[-1] if inside else 0,
               "queue_mean": sum(inside) / max(1, len(inside)),
               "drain_s": res.end_s - args.seconds,
               "offered_tokens_per_s": sum(a.max_new_tokens
                                           for a in arrivals) / args.seconds,
               **{k: v for k, v in e2e.items() if k != "samples"}}
        row["grows"] = row["queue_end"] > 1.5 * row["queue_mean"] + 1
        rows.append(row)
        print(json.dumps(row), flush=True)
        del eng
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--drain", type=float, default=60.0)
    ap.add_argument("--write-rate", action="store_true")
    args = ap.parse_args(argv)
    spec = run.load_cell(args.workload)
    devs, _ = run.require_device(spec["cell"]["chips"])
    import generator
    import system
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    out_dir = os.path.join(run.ROOT, "chiprun_out", "sweep")
    os.makedirs(out_dir, exist_ok=True)
    sut = system.System(spec["cfg"], spec["cell"]["chips"])
    fleet = system.fleet_config(spec["cfg"], generator.ring_window(
        spec["mix"]))
    with sut.serving():
        rows = _serve_rates(sut, fleet, spec, args)
    knee = None
    for r in sorted(rows, key=lambda r: r["rate_rps"]):
        if r["grows"]:
            break
        knee = r["rate_rps"]
    rate = None if knee is None else round(0.8 * knee / 0.05) * 0.05
    summary = {"workload": args.workload, "knee_rps": knee,
               "rate_rps": rate, "rows": rows}
    if args.write_rate and rate:
        path = os.path.join(run.BENCH_DIR, "traffic",
                            f"{spec['cell']['traffic']}.json")
        with open(path) as f:
            mix = json.load(f)
        mix["arrivals"]["rate_rps"] = round(rate, 2)
        with open(path, "w") as f:
            json.dump(mix, f, indent=2)
            f.write("\n")
    with open(os.path.join(out_dir, f"{args.workload}.json"), "w") as f:
        json.dump(summary, f, indent=1)
    print(json.dumps({"workload": args.workload, "knee_rps": knee,
                      "rate_rps": rate}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
