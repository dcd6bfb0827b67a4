"""Ahead-of-time compiles for a TPU v5e chip at real widths.

The TPU compiler is installed with JAX and compiles for a chip that is
described, not attached, so these tests catch what interpret mode cannot:
a kernel tiling Mosaic refuses, too much fast memory, a program that does
not fit.  Nothing runs, so they say nothing about results or speed.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and a test file that
loaded it while being collected would make parallel workers disagree on
which tests exist.  Kernels are called with ``interpret=False`` directly,
because ``repro.kernels.ops`` picks interpret mode on a CPU backend.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import flash_attention as fa
from repro.kernels import quantize as qz
from repro.kernels import rmsnorm as rn
from repro.models import transformer as T
from repro.serve.engine import jit_decode

QWEN3 = get_config("qwen3-14b")          # d_model 5120, 40 heads, 8 KV


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile written to the persistent cache cannot be read back
    # without a chip (the next one warns and recompiles), so keep the
    # cache off while these tests compile
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("seq", [16, 512])
def test_flash_attention_compiles_for_v5e(one_chip, seq):
    hd = QWEN3.resolved_head_dim
    q = _spec((1, QWEN3.num_heads, seq, hd), jnp.bfloat16, one_chip)
    kv = _spec((1, QWEN3.num_kv_heads, seq, hd), jnp.bfloat16, one_chip)
    c = _compile(lambda q, k, v: fa.flash_attention_hm(
        q, k, v, causal=True, interpret=False), q, kv, kv)
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("tokens", [8, 512])
def test_rmsnorm_compiles_for_v5e(one_chip, tokens):
    x = _spec((tokens, QWEN3.d_model), jnp.bfloat16, one_chip)
    scale = _spec((QWEN3.d_model,), jnp.bfloat16, one_chip)
    c = _compile(lambda x, s: rn.rmsnorm_pallas(x, s, interpret=False),
                 x, scale)
    assert "tpu_custom_call" in c.as_text()


def test_quantize_int8_compiles_for_v5e(one_chip):
    x = _spec((256, QWEN3.d_model), jnp.float32, one_chip)
    c = _compile(lambda x: qz.quantize_int8_pallas(x, interpret=False), x)
    assert "tpu_custom_call" in c.as_text()


def test_qwen3_decode_step_compiles_for_v5e(one_chip):
    """One serving decode step of qwen3-14b at full width, 1 layer."""
    cfg = QWEN3.replace(num_layers=1)
    rt = T.Runtime(production=False, remat=False)
    shapes, _ = T.model_pspecs(cfg)
    state = jax.eval_shape(lambda: T.init_decode_state(cfg, 8, 256))
    on_chip = lambda t: jax.tree.map(
        lambda a: _spec(a.shape, a.dtype, one_chip), t)
    tokens = _spec((8, 1), jnp.int32, one_chip)
    c = jit_decode.lower(on_chip(shapes), on_chip(state), tokens,
                         cfg=cfg, rt=rt).compile()
    mem = c.memory_analysis()
    weights = cfg.param_count() * 2
    assert weights <= mem.argument_size_in_bytes < weights + 2**27


@pytest.mark.parametrize("layers", [1, 2])
def test_pool_decode_updates_its_state_in_place_on_v5e(one_chip, layers):
    """The fleet's pool decode at qwen3-14b width (32 rows, ring 1024):
    the donated state comes back aliased, so the program adds no second
    copy of the K/V (128 MiB a layer), the layer loop's included, and no
    copy of a layer's weights (a transposed ``wq`` is 50 MiB)."""
    cfg = QWEN3.replace(num_layers=layers)
    rt = T.Runtime(production=False, remat=False)
    shapes, _ = T.model_pspecs(cfg)
    state = jax.eval_shape(lambda: T.init_decode_state(cfg, 32, 1024))
    on_chip = lambda t: jax.tree.map(
        lambda a: _spec(a.shape, a.dtype, one_chip), t)
    tokens = _spec((32, 1), jnp.int32, one_chip)
    c = jit_decode.lower(on_chip(shapes), on_chip(state), tokens,
                         cfg=cfg, rt=rt).compile()
    mem = c.memory_analysis()
    kv = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(state))
    assert kv >= layers * 2**27
    assert mem.alias_size_in_bytes >= kv
    assert (mem.temp_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes) < 2**27
    assert mem.temp_size_in_bytes < 2**24
