"""Compile each cell's largest prefill and decode programs for a TPU v5e
that is described, not attached, and print their memory analysis.

    JAX_PLATFORMS=cpu python3 bench/aot_check.py [--workload <cell>]

The largest shapes of a cell are a full wave of its largest prompt bucket
(capacity rows) and a decode step of capacity rows over the cell's KV
ring.  The topology is described inside ``main``, never at import.
Nothing runs: a compile that passes is not a chip run.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)


def _sds(tree, sharding):
    import jax
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def check_cell(spec: dict, sharding) -> dict:
    import jax
    import jax.numpy as jnp

    import generator
    import system
    from repro.serve.engine import jit_decode, jit_prefill

    sut = system.System(spec["cfg"])
    cfg, rt = sut.cfg, sut.rt
    mix = spec["mix"]
    W = generator.ring_window(mix)
    cap = spec["cfg"]["fleet"]["capacity"]
    params = _sds(sut.layout(), sharding)
    toks = jax.ShapeDtypeStruct((cap, max(mix["prompt_buckets"])), jnp.int32,
                                sharding=sharding)
    out = {"window": W, "capacity": cap}
    pre = jit_prefill.lower(params, {"tokens": toks}, cfg=cfg,
                            rt=rt, window=W).compile()
    out["prefill"] = _mem(pre.memory_analysis())
    state = jax.eval_shape(
        lambda p, t: jit_prefill(p, {"tokens": t}, cfg=cfg,
                                 rt=rt, window=W)[1],
        params, toks)
    last = jax.ShapeDtypeStruct((cap, 1), jnp.int32, sharding=sharding)
    dec = jit_decode.lower(params, _sds(state, sharding), last, cfg=cfg,
                           rt=rt).compile()
    out["decode"] = _mem(dec.memory_analysis())
    return out


def _mem(ma) -> dict:
    keys = ("argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "alias_size_in_bytes",
            "generated_code_size_in_bytes")
    return {k: int(getattr(ma, k)) for k in keys if hasattr(ma, k)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", action="append")
    args = ap.parse_args(argv)
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import run
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        names = [w["name"] for w in json.load(f)["workloads"]]
    for name in args.workload or names:
        res = check_cell(run.load_cell(name), one_chip)
        print(json.dumps({"workload": name, **res}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
