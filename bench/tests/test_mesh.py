"""A cell of four chips, rehearsed on four virtual CPU devices in a child
process (the device count is fixed when JAX starts): the weights are made
into the shards of ``T.model_pspecs`` on a (data=1, model=4) mesh with the
same bits as on one device, the reference reads the same numbers from the
sharded tree as from the unsharded one, and the tiny cell is served on the
mesh and judged correct."""
import json
import os
import subprocess
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CHILD = r'''
import json, sys, tempfile
sys.path[:0] = [{bench!r}, {tests!r}]
import jax
import numpy as np
from jax.sharding import PartitionSpec

import run
import system
from conftest import tiny, tiny_spec
from reference import Reference
from repro.models import transformer as T

SEED = 2 ** 31 + 5
cfg_file = tiny()
one, four = system.System(cfg_file, 1), system.System(cfg_file, 4)
p1, p4 = one.make_params(SEED), four.make_params(SEED)
_, pspecs = T.model_pspecs(four.cfg)
specs = jax.tree.leaves(pspecs, is_leaf=lambda x: isinstance(x, PartitionSpec))
out = {{"mesh": dict(four.mesh.shape), "leaves": []}}
for a, b, s in zip(jax.tree.leaves(p1), jax.tree.leaves(p4), specs):
    out["leaves"].append({{
        "same_bits": bool(np.array_equal(np.asarray(a).view(np.uint16),
                                         np.asarray(b).view(np.uint16))),
        "spec_kept": b.sharding.spec == s,
        "devices": len(b.sharding.device_set),
        "split": "model" in jax.tree.leaves(tuple(s)),
        "shard_elems": int(b.addressable_shards[0].data.size),
        "elems": int(b.size)}})
ref = Reference(cfg_file)
rng = np.random.default_rng(3)
tokens = rng.integers(0, cfg_file["vocab_size"], (2, 16)).astype(np.int32)
pos = np.tile(np.arange(8, 16, dtype=np.int32), (2, 1))
tgt = rng.integers(0, cfg_file["vocab_size"], (2, 8)).astype(np.int32)
r1 = [np.asarray(a) for a in ref.reduce(p1, ref.hidden(p1, tokens), pos, tgt)]
r4 = [np.asarray(a) for a in ref.reduce(p4, ref.hidden(p4, tokens), pos, tgt)]
out["reference_equal"] = all(np.array_equal(a, b) for a, b in zip(r1, r4))
spec = tiny_spec()
spec["cell"]["chips"] = 4
line = run.serve_cell(spec, SEED, 2.0, False, jax.devices(), {{}},
                      tempfile.mkdtemp())
out["served"] = {{"correct": line["correct"], "count": line["device"]["count"],
                  "attempted": line["attempted"], "failed": line["failed"]}}
print("RESULT " + json.dumps(out))
'''


def test_four_chip_weights_reference_and_serving():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = CHILD.format(bench=BENCH, tests=os.path.join(BENCH, "tests"))
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=BENCH,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = next(ln for ln in proc.stdout.splitlines()
                if ln.startswith("RESULT "))
    out = json.loads(line[len("RESULT "):])
    assert out["mesh"] == {"data": 1, "model": 4}
    leaves = out["leaves"]
    assert all(x["same_bits"] and x["spec_kept"] and x["devices"] == 4
               for x in leaves)
    split = [x for x in leaves if x["split"]]
    assert split and all(4 * x["shard_elems"] == x["elems"] for x in split)
    assert out["reference_equal"]
    assert out["served"] == {"correct": True, "count": 4,
                             "attempted": out["served"]["attempted"],
                             "failed": 0}
    assert out["served"]["attempted"] > 0
