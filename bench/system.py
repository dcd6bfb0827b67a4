"""The system under test, built from a configuration file: the program's
model configuration, its placement on the cell's chips, its weights, its
serving fleet, and the warm-up of every program shape a cell's traffic
can reach.

This is the only file of the benchmark that imports the program
(``repro``); the reference and the work counts read the configuration
file themselves.
"""
from __future__ import annotations

import dataclasses
import itertools
import os
import sys
from typing import Dict, List

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
if os.path.join(ROOT, "src") not in sys.path:
    sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec  # noqa: E402

import archs  # noqa: E402
import weights  # noqa: E402
from repro.configs.base import (  # noqa: E402
    AmoebaConfig, FleetConfig, ModelConfig, MoEConfig, RGLRUConfig, SSMConfig)
from repro.core.fusion import MeshPlan  # noqa: E402
from repro.fleet import FleetEngine  # noqa: E402
from repro.launch.mesh import make_plan_mesh  # noqa: E402
from repro.models import transformer as T  # noqa: E402
from repro.parallel import shardctx  # noqa: E402
from repro.serve import state_utils as su  # noqa: E402
from repro.serve.engine import Request, jit_prefill  # noqa: E402

WARM_SEED = 0x5EED_0A12   # token ids of the warm-up stream, never a run's

ONE_CHIP_RT = T.Runtime(production=False, remat=False)
MESH_RT = T.Runtime(production=True, remat=False)

# nested groups of ``ModelConfig`` that an architecture's program fields
# give as plain dicts
NESTED = {"moe": MoEConfig, "ssm": SSMConfig, "rglru": RGLRUConfig}


def model_config(c: dict) -> ModelConfig:
    """The program's ``ModelConfig`` for a configuration file: the dense
    fields mapped here, then the architecture's ``program_fields`` over
    them (``bench/archs``)."""
    arch = c["architecture"]
    fields = dict(
        name=c["name"], family="dense", num_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"], head_dim=c["head_dim"],
        activation=arch["mlp"], qk_norm=arch["qk_norm"],
        rope_theta=float(c["rope_theta"]),
        attn_window=c.get("sliding_window"),
        tie_embeddings=bool(c["tie_word_embeddings"]),
        norm_eps=float(c.get("rms_norm_eps", c.get("norm_epsilon"))),
        dtype=c["torch_dtype"])
    known = {f.name for f in dataclasses.fields(ModelConfig)}
    for k, v in archs.of(c).program_fields(c).items():
        if k not in known:
            raise ValueError(f"{c['model_type']}: program field {k!r} is not "
                             f"a field of ModelConfig")
        if k in NESTED and isinstance(v, dict):
            v = NESTED[k](**v)
        elif k == "block_pattern" and v is not None:
            v = tuple(v)
        fields[k] = v
    return ModelConfig(**fields)


def fleet_config(c: dict, window: int) -> FleetConfig:
    f, a = c["fleet"], c["amoeba"]
    return FleetConfig(num_groups=f["num_groups"], capacity=f["capacity"],
                       mode=f["mode"], router=f["router"],
                       long_threshold=f["long_threshold"], window=window,
                       amoeba=AmoebaConfig(**a))


def make_request(arrival, wall: int) -> Request:
    return Request(rid=arrival.rid, prompt=arrival.prompt.tolist(),
                   max_new_tokens=arrival.max_new_tokens, arrival=wall)


class System:
    """A configuration file as the program serves it on ``chips`` chips.

    One chip: no mesh, ``Runtime(production=False)``.  More: a
    ``(data=1, model=chips)`` mesh over the first ``chips`` devices, the
    program's own sharding (``Runtime(production=True)``), weights made
    into the shards of ``T.model_pspecs``.  Warm-up, engines and the drive
    run inside :meth:`serving`.
    """

    def __init__(self, cfg_file: dict, chips: int = 1):
        self.file = cfg_file
        self.arch = archs.of(cfg_file)
        self.cfg = model_config(cfg_file)
        self.chips = chips
        if chips == 1:
            self.mesh, self.rt = None, ONE_CHIP_RT
        else:
            self.mesh = make_plan_mesh(MeshPlan("bench", data=1,
                                                model=chips))
            self.rt = MESH_RT

    def layout(self):
        """The program's parameter tree as shapes (nothing is allocated)."""
        return jax.eval_shape(lambda k: T.init_model(k, self.cfg)[0],
                              jax.random.PRNGKey(0))

    def shardings(self):
        """Each leaf's ``NamedSharding`` on the mesh (None on one chip)."""
        if self.mesh is None:
            return None
        _, pspecs = T.model_pspecs(self.cfg)
        return jax.tree.map(lambda s: NamedSharding(self.mesh, s), pspecs,
                            is_leaf=lambda x: isinstance(x, PartitionSpec))

    def make_params(self, seed: int):
        return weights.make_params(self.layout(), seed,
                                   init_leaf=self.arch.init_leaf,
                                   shardings=self.shardings())

    def serving(self):
        """The context every serving call runs in: the mesh, if any."""
        return shardctx.use_mesh(self.mesh)

    def make_engine(self, params, fleet: FleetConfig) -> FleetEngine:
        return FleetEngine(self.cfg, params, rt=self.rt, fleet=fleet)

    def warm_up(self, params, fleet: FleetConfig, buckets: List[int],
                vocab: int) -> Dict[str, int]:
        """Run every device program the cell's traffic can reach once.

        1. A fixed stream served by a one-group engine built like the
           cell's (the same jitted prefill and decode, the same KV ring):
           for each prompt bucket and each wave size 1..capacity, that
           many requests of two tokens each, submitted to the idle engine
           and served to the end.  That is the wave's prefill, one decode
           step of its size, and every eager operation the engine makes
           around both.
        2. The state surgery of a split or fuse, which no stream can be
           made to reach at every size (the group's controller decides
           when and how it splits): ``state_utils.take`` of k of m rows
           and ``state_utils.concat`` of parts of a, b, ... rows (one per
           prompt bucket in a wave, one per part in a merge), with the
           same on the parts' next-token columns, at every size up to
           capacity.

        Returns the number of waves and of each surgery.
        """
        cap, W = fleet.capacity, fleet.window
        rng = np.random.default_rng(WARM_SEED)
        eng = self.make_engine(params, dataclasses.replace(
            fleet, num_groups=1, mode="fused"))
        n: Dict[str, int] = {"waves": 0, "take": 0, "concat": 0}
        rid = 0
        for plen in buckets:
            for rows in range(1, cap + 1):
                reqs = []
                for _ in range(rows):
                    reqs.append(Request(rid=rid, prompt=rng.integers(
                        0, vocab, plen).tolist(), max_new_tokens=2,
                        arrival=eng.wall))
                    rid += 1
                eng.submit(reqs)
                eng.run()
                if any(len(r.generated) != 2 for r in reqs):
                    raise RuntimeError("warm-up: a request was not served")
                n["waves"] += 1
        del eng
        toks = jnp.asarray(rng.integers(0, vocab, (cap, buckets[0])),
                           jnp.int32)
        full = jit_prefill(params, {"tokens": toks}, cfg=self.cfg,
                           rt=self.rt, window=W)[1]
        states = {m: su.take(full, range(m)) for m in range(1, cap + 1)}
        cols = {m: jnp.zeros((m, 1), jnp.int32) for m in range(1, cap + 1)}
        for m in range(2, cap + 1):
            for k in range(1, m + 1):
                ids = list(range(k))
                jax.block_until_ready(su.take(states[m], ids))
                jnp.take(cols[m], jnp.asarray(ids), axis=0)
                n["take"] += 1
        ways = max(len(buckets), fleet.amoeba.max_ways)
        for k in range(2, ways + 1):
            for sizes in itertools.product(range(1, cap), repeat=k):
                if sum(sizes) > cap:
                    continue
                jax.block_until_ready(
                    su.concat([states[a] for a in sizes]))
                jnp.concatenate([cols[a] for a in sizes], axis=0)
                n["concat"] += 1
        return n
