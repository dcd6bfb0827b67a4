"""Serve one benchmark cell on the chip, open loop on the wall clock.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is an entry of ``workloads`` in ``BENCHMARK.json``: a configuration
(``bench/configs/<config>.json``) under a traffic mix
(``bench/traffic/<traffic>.json``).  Nothing here depends on which cell it
is.  A run:

1. checks the device first: it exits non-zero, printing no result, when
   JAX finds no TPU, fewer chips than the cell asks for, or a device kind
   missing from ``bench/peaks.json``;
2. enables the program's persistent compilation cache;
3. makes the weights on the device from ``--seed`` (``system.System``:
   on a cell of several chips, straight into their shards on a
   ``(data=1, model=chips)`` mesh, served with the program's sharding);
4. warms up every program shape the cell's traffic can reach;
5. serves the seed's schedule through ``FleetEngine`` for ``--seconds``,
   then drains every request sent (``openloop.py``); with ``--trace 1`` the
   profiler records a part of the window, named in the traffic file;
6. reads the peak device memory (of the fullest chip), frees the serving
   state, and compares a sample of the served tokens with the float32
   reference of the configuration's architecture (``check.py``,
   ``bench/archs``);
7. prints the compared numbers with their limits on standard error, and
   one JSON line on standard output: the cell's end-to-end metrics with
   ``--trace 0``, its per-layer metrics (``bench/metrics/<name>.py``) with
   ``--trace 1``.

``setup_s`` runs from the process's start to the first due arrival.
"""
from __future__ import annotations

import os
import time


def _process_start_epoch() -> float:
    """Wall-clock time at which this process started (from /proc)."""
    with open("/proc/self/stat") as f:
        fields = f.read().rsplit(")", 1)[1].split()
    start_ticks = int(fields[19])            # field 22 of stat(5)
    with open("/proc/stat") as f:
        btime = next(int(ln.split()[1]) for ln in f if ln.startswith("btime"))
    return btime + start_ticks / os.sysconf("SC_CLK_TCK")


PROCESS_START = _process_start_epoch()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import Sequence  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, BENCH_DIR)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_cell(name: str) -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"run.py: unknown workload {name!r}; have "
                         f"{sorted(cells)}")
    cell = cells[name]
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, config["file"])) as f:
        cfg = json.load(f)
    with open(os.path.join(BENCH_DIR, "traffic",
                           f"{cell['traffic']}.json")) as f:
        mix = json.load(f)
    per_layer = [m for m in bench["per_layer"]
                 if name in m.get("workloads", [name])]
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    return {"cell": cell, "cfg": cfg, "mix": mix, "per_layer": per_layer,
            "end_to_end": e2e}


def require_device(chips: int) -> tuple:
    """The device check, before any other work: no CPU fallback."""
    import jax
    with open(os.path.join(BENCH_DIR, "peaks.json")) as f:
        peaks = json.load(f)["devices"]
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"run.py: needs a TPU; JAX found platform "
                         f"{devs[0].platform!r}")
    if len(devs) < chips:
        raise SystemExit(f"run.py: the cell needs {chips} chips; JAX found "
                         f"{len(devs)}")
    kind = devs[0].device_kind
    if kind not in peaks:
        raise SystemExit(f"run.py: device kind {kind!r} is not in "
                         f"bench/peaks.json")
    return devs, peaks[kind]


class CompileCounter:
    """XLA executables built (compiled, or read from the persistent cache)
    and the seconds spent building them, from JAX's monitoring events."""

    def __init__(self):
        self.count = 0
        self.seconds = 0.0
        self.cache_hits = 0

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += duration

    def _event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


class Tracer:
    """Starts the profiler at the first tick past ``start`` seconds into
    the window and stops it at the first tick past ``stop``, so that the
    trace holds whole ticks only."""

    def __init__(self, logdir: str, start: float, stop: float):
        self.logdir, self.start, self.stop = logdir, start, stop
        self.ticks = [None, None]
        self.n = 0
        self._span = None

    def on_tick(self, t: float) -> None:
        import jax
        self.n += 1
        if self.ticks[0] is None and t >= self.start:
            jax.profiler.start_trace(self.logdir)
            self._span = jax.profiler.TraceAnnotation("traced")
            self._span.__enter__()
            self.ticks[0] = self.n
        elif self.ticks[0] is not None and self.ticks[1] is None \
                and t >= self.stop:
            self.close()
            self.ticks[1] = self.n

    def close(self) -> None:
        import jax
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None
            jax.profiler.stop_trace()


def serve_cell(spec: dict, seed: int, seconds: float, trace: bool,
               devs, peaks: dict, out_dir: str, warm: bool = True,
               controls: Sequence[str] = ()) -> dict:
    """Everything from the weights to the comparison; returns the result
    line's parts.  ``warm=False`` skips the warm-up (for a process that
    has already run every shape); ``controls`` names lower precisions of
    the reference whose widest gaps are read beside the program's
    (``bench/control.py``)."""
    import jax

    import counts
    import generator
    import openloop
    import system
    from check import draw_sample, logit_gaps
    from layer import LayerContext, load_reader

    cell, cfg_file, mix = spec["cell"], spec["cfg"], spec["mix"]
    sut = system.System(cfg_file, cell["chips"])
    window = generator.ring_window(mix)
    fleet = system.fleet_config(cfg_file, window)
    with CompileCounter() as compiles, sut.serving():
        t = time.perf_counter()
        params = jax.block_until_ready(sut.make_params(seed))
        log(f"weights: {sum(x.size for x in jax.tree.leaves(params)) / 1e9:.3f}"
            f" B parameters in {time.perf_counter() - t:.2f} s; "
            f"{weight_bytes_per_chip(params, devs[:cell['chips']])} bytes "
            f"per chip")
        t = time.perf_counter()
        if warm:
            n = sut.warm_up(params, fleet, mix["prompt_buckets"],
                            cfg_file["vocab_size"])
            log(f"warm-up: {n} in {time.perf_counter() - t:.2f} s; "
                f"{compiles.count} executables built ({compiles.cache_hits} "
                f"from the cache) in {compiles.seconds:.2f} s")
        eng = sut.make_engine(params, fleet)
        arrivals = generator.schedule(mix, seconds, seed,
                                      cfg_file["vocab_size"])
        log(f"schedule: {generator.describe(arrivals)}")
        tracer = None
        if trace:
            lo, length = mix["trace_window_s"]
            tracer = Tracer(os.path.join(out_dir, "trace"), lo,
                            min(lo + length, seconds))
        built_before = compiles.count
        setup_s = time.time() - PROCESS_START
        window_compiles = []

        def on_tick(t_win: float) -> None:
            if not window_compiles and t_win >= seconds:
                window_compiles.append(compiles.count - built_before)
            if tracer is not None:
                tracer.on_tick(t_win)

        try:
            res = openloop.drive(eng, arrivals, seconds, mix["drain_s"],
                                 system.make_request, on_tick=on_tick,
                                 spans=trace)
        finally:
            if tracer is not None:
                tracer.close()
        if not window_compiles:
            window_compiles.append(compiles.count - built_before)
    log(f"window: {len(res.tracked)} requests sent, {len(res.ticks)} ticks, "
        f"{res.reconfigs} splits+fuses, drain ended at {res.end_s:.2f} s; "
        f"{window_compiles[0]} executables built in the window")
    mem = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
              for d in devs[:cell["chips"]])
    # the harness's own cost per tick: run()'s finalize and summary,
    # timed on a call that ticks nothing
    t = time.perf_counter()
    with sut.serving():
        for _ in range(20):
            eng.run(max_ticks=eng.wall)
    harness_ms = (time.perf_counter() - t) / 20 * 1000
    log(f"harness: run() finalize + telemetry summary {harness_ms:.3f} ms "
        f"per call ({len(eng.requests)} requests held)")

    shape = sut.arch.Shape.from_config(cfg_file)
    metrics = {}
    breakdown = None
    if trace:
        import devtrace as tr
        trace_dir = os.path.join(out_dir, "trace")
        red = tr.reduce(tr.align(tr.load(tr.find_xplane(trace_dir))))
        # the raw trace is large; what the run reads of it is kept
        shutil.rmtree(trace_dir, ignore_errors=True)
        with open(os.path.join(out_dir, "trace_summary.json"), "w") as f:
            json.dump(red.__dict__, f)
        lo_tick, hi_tick = tracer.ticks
        ctx = LayerContext(
            drive=res, shape=shape, peak_flops=peaks["bf16_flops_per_s"],
            peak_bw=peaks["hbm_bytes_per_s"], chips=cell["chips"],
            window_compiles=window_compiles[0], trace=red,
            traced_ticks=slice(lo_tick, hi_tick))
        if not counts.decode_bandwidth_bound(
                shape, fleet.capacity, window, ctx.peak_flops, ctx.peak_bw):
            raise RuntimeError("a decode call can be compute bound: the "
                               "decode roofline's sum is not per call")
        for m in spec["per_layer"]:
            v = load_reader(BENCH_DIR, m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        breakdown = {"device_ops": [[n, s] for n, s in red.top_ops],
                     "idle_gaps": [[n, s] for n, s in red.idle_gaps]}
        log(f"trace: window {red.window_s:.3f} s, busy {red.busy_s:.3f} s, "
            f"programs {red.program_calls}")
    else:
        e2e = openloop.end_to_end(res)
        log(f"samples: {e2e['samples']}")
        for m in spec["end_to_end"]:
            v = setup_s if m["name"] == "setup_s" else e2e.get(m["name"])
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    failed = openloop.failures(res)

    # free the program's serving state before the reference runs
    del eng, res.ticks[:]
    for trk in res.tracked:
        trk.req = _Served(trk.req)
    gc.collect()
    t = time.perf_counter()
    check = cfg_file["check"]
    sample = draw_sample(res.tracked, mix["check_requests"], seed)
    Reference = sut.arch.Reference
    gaps = logit_gaps(Reference(cfg_file), params, sample, window,
                      generator.max_output(mix),
                      rows=max(1, check["reference_tokens"] // window),
                      controls={q: Reference(cfg_file, quant=q)
                                for q in controls})
    log(f"reference: {len(sample)} requests, {gaps.tokens} served tokens, "
        f"{time.perf_counter() - t:.2f} s")
    correct, compared = judge(check, sample, gaps.widest, failed)
    log(f"widest gap at (rid, token) {gaps.at}")
    # each control is put in the program's place: its tokens, judged by
    # the same comparison, must come out not correct
    control_lines = {q: judge(check, sample, g, failed)
                     for q, g in (gaps.controls or {}).items()}
    for q, (ok, cmp) in control_lines.items():
        log(f"control {q}: correct {ok}, max_logit_gap "
            f"{cmp['max_logit_gap']['value']}")
    for k, v in compared.items():
        log(f"compare {k}: {v['value']} limit {v['limit']}")
    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": mem}
    if trace:
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
    line = {"correct": correct, "attempted": len(res.tracked),
            "failed": len(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    if controls:
        line["controls"] = {q: {"correct": ok, "compared": cmp}
                            for q, (ok, cmp) in control_lines.items()}
        line["reference_s"] = time.perf_counter() - t
        line["reconfigs"] = res.reconfigs
    line["compared"] = compared
    return line


def weight_bytes_per_chip(params, devs) -> int:
    """Bytes of the weight tree on the fullest of ``devs``."""
    import jax
    held = dict.fromkeys(devs, 0)
    for leaf in jax.tree.leaves(params):
        for shard in leaf.addressable_shards:
            held[shard.device] += shard.data.nbytes
    return max(held.values())


def judge(check: dict, sample, widest: float, failed) -> tuple:
    """``correct`` and the numbers compared, each beside its limit: the
    widest logit gap of the served tokens, and the requests left
    unfinished at the drain's end."""
    compared = {
        "max_logit_gap": {"value": widest, "limit": check["max_logit_gap"]},
        "unfinished_requests": {"value": len(failed), "limit": 0},
    }
    correct = bool(sample) and widest <= check["max_logit_gap"] \
        and not failed
    return correct, compared


class _Served:
    """What the comparison keeps of a served request: its tokens."""

    def __init__(self, req):
        self.generated = list(req.generated)
        self.max_new_tokens = req.max_new_tokens


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec = load_cell(args.workload)
    devs, peaks = require_device(spec["cell"]["chips"])
    import system  # noqa: F401  (puts the program on the path)
    from repro.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    log(f"device: {devs[0].platform} {devs[0].device_kind} x{len(devs)}; "
        f"compile cache {cache}")
    out_dir = os.path.join(ROOT, "chiprun_out", "bench",
                           f"{args.workload}.{args.seed}.{args.trace}")
    shutil.rmtree(os.path.join(out_dir, "trace"), ignore_errors=True)
    os.makedirs(out_dir, exist_ok=True)
    line = serve_cell(spec, args.seed, args.seconds, bool(args.trace), devs,
                      peaks, out_dir)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
