"""Real multi-device correctness (8 host CPU devices in a subprocess).

The dry-run proves lowering; this proves NUMERICS: the sharded production
paths (MoE shard_map, seq-sharded decode attention, pjit train step) must
produce the same values as the single-device oracle.
"""
import os
import subprocess
import sys

CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P
from repro.configs import get_config
from repro.configs.base import ShapeConfig, TrainConfig
from repro.models import transformer as T
from repro.parallel import shardctx, resolve
from repro.train import Trainer

assert len(jax.devices()) == 8
mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)

# --- MoE: sharded path on a real mesh == dense oracle --------------------
cfg = get_config("deepseek-moe-16b", reduced=True).replace(dtype="float32")
import dataclasses
cfg = cfg.replace(moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
params, _ = T.init_model(jax.random.PRNGKey(0), cfg)
batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0,
                                      cfg.vocab_size)}
l_oracle, _ = T.loss_fn(params, batch, cfg,
                        T.Runtime(production=False, remat=False))
with shardctx.use_mesh(mesh):
    l_prod, _ = jax.jit(lambda p, b: T.loss_fn(
        p, b, cfg, T.Runtime(production=True, remat=False)))(params, batch)
err = abs(float(l_oracle) - float(l_prod))
assert err < 2e-3, ("moe sharded-vs-dense", err)
print("moe ok", err)

# --- decode: seq-sharded KV attention == unsharded ------------------------
cfg2 = get_config("qwen3-14b", reduced=True).replace(dtype="float32")
params2, _ = T.init_model(jax.random.PRNGKey(0), cfg2)
rt = T.Runtime(production=False, remat=False)
toks = jax.random.randint(jax.random.PRNGKey(2), (4, 24), 0, cfg2.vocab_size)
lg, st = T.prefill(params2, {"tokens": toks}, cfg2, rt, window=32)
lg1, st1 = T.decode_step(params2, st, toks[:, :1], cfg2, rt)
with shardctx.use_mesh(mesh):
    rtp = T.Runtime(production=True, remat=False)
    lg_m, st_m = T.prefill(params2, {"tokens": toks}, cfg2, rtp, window=32)
    lg1_m, _ = T.decode_step(params2, st_m, toks[:, :1], cfg2, rtp)
err = float(jnp.max(jnp.abs(lg1 - lg1_m)))
assert err < 2e-3, ("decode sharded-vs-dense", err)
print("decode ok", err)

# --- trainer step under pjit mesh == single device -------------------------
shape = ShapeConfig("t", 32, 4, "train")
tcfg = TrainConfig(total_steps=3, warmup_steps=1, learning_rate=1e-3)
t_single = Trainer(cfg2, shape, tcfg,
                   rt=T.Runtime(production=False, remat=True))
h1 = t_single.train(3)["history"]
t_mesh = Trainer(cfg2, shape, tcfg, mesh=mesh,
                 rt=T.Runtime(production=True, remat=True))
h2 = t_mesh.train(3)["history"]
for a, b in zip(h1, h2):
    assert abs(a.loss - b.loss) < 2e-3, (a.step, a.loss, b.loss)
print("trainer ok", [round(m.loss, 4) for m in h2])

# --- compressed all-reduce on a real data axis ------------------------------
from repro.parallel import compression as C
from functools import partial
g = jax.random.normal(jax.random.PRNGKey(3), (8, 16, 64), jnp.float32)
def body(gl):
    mean, res = C.compressed_psum_mean({"g": gl}, "data")
    return mean["g"], res["g"]
mean, res = jax.jit(jax.shard_map(
    body, mesh=mesh, in_specs=P("data"), out_specs=P("data"),
    check_vma=False))(g)
# compare against the true mean over the data axis shards
gs = g.reshape(2, 4, 16, 64)
true = jnp.mean(gs, axis=0, keepdims=True)
true = jnp.broadcast_to(true, gs.shape).reshape(8, 16, 64)
err = float(jnp.max(jnp.abs(mean - true)))
bound = float(jnp.max(jnp.abs(g))) / 127.0 * 1.5
assert err <= bound, (err, bound)
print("compression ok", err)

# --- chip_smoke.py's four-chip phase, on 4 of the devices -------------------
import chip_smoke
cfg3 = get_config("qwen3-14b", reduced=True)
res = chip_smoke.sharded_check(cfg3, chip_smoke.init_params(cfg3, 0), 0,
                               chips=4)
print("smoke sharded ok", res)
print("ALL-MULTIDEVICE-OK")
"""


def test_multidevice_numerics():
    env = dict(os.environ)
    root = os.path.join(os.path.dirname(__file__), "..")
    env["PYTHONPATH"] = os.pathsep.join([os.path.join(root, "src"), root])
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", CHILD], env=env,
                       capture_output=True, text=True, timeout=1200)
    assert "ALL-MULTIDEVICE-OK" in r.stdout, \
        f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-4000:]}"
