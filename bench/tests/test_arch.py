"""A configuration enters through files of its own: the dense path gives
``qwen3-14b`` the program configuration, weights and counts it had before
architecture modules existed (golden values), and a module
``bench/archs/<model_type>.py`` is picked up with its program fields,
reference, counts and leaf values."""
import dataclasses
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.tree_util import keystr

import archs
import counts
import run
import system
from conftest import load_config, tiny, tiny_spec
from repro.configs.base import ModelConfig, MoEConfig
from weights import make_params

SEED = 2 ** 31 + 77

# Read from the harness before bench/archs existed.
QWEN3_14B = ModelConfig(
    name="qwen3-14b", family="dense", num_layers=8, d_model=5120,
    num_heads=40, num_kv_heads=8, d_ff=17408, vocab_size=151936,
    head_dim=128, activation="swiglu", qk_norm=True, rope_theta=1000000.0,
    attn_window=None, tie_embeddings=False, norm_eps=1e-06,
    dtype="bfloat16")
TINY_WEIGHTS_SHA256 = \
    "31ae5fb67a47ac9ac024912123c621c95db32e5050ae913e19c54c86c47d32a1"


def test_qwen3_takes_the_dense_path_unchanged():
    c = load_config("qwen3-14b")
    a = archs.of(c)
    assert a.Shape is counts.Shape and a.init_leaf is None
    assert system.model_config(c) == QWEN3_14B
    s = a.Shape.from_config(c)
    assert s.weight_bytes_per_call == 6_840_825_856
    assert s.kv_bytes_per_position == 32_768
    assert s.prefill_call(3, 512) == counts.Work(
        flops=8_186_706_001_920, bytes=6_907_797_760, calls=1)
    assert s.decode_call([600, 130, 1024]) == counts.Work(
        flops=20_809_318_400, bytes=6_899_243_264, calls=1)
    assert s.decode_rows([600, 130]) == counts.Work(
        flops=13_800_898_560, bytes=24_548_864, calls=0)


def test_dense_weights_are_bit_identical():
    params = system.System(tiny()).make_params(SEED)
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(params):
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest() == TINY_WEIGHTS_SHA256


@pytest.mark.parametrize("path,shape", [
    ("['reps'][0]['mixer']['D']", (2, 8)),      # a stacked vector
    ("['bias']", (8,)),                          # a vector
])
def test_a_leaf_no_rule_covers_is_an_error_naming_it(path, shape):
    sd = jax.ShapeDtypeStruct
    mixer = {"w": sd((2, 8, 4), jnp.bfloat16)}
    layout = {"reps": ({"mixer": mixer},)}
    if path.startswith("['reps']"):
        mixer["D"] = sd(shape, jnp.float32)
    else:
        layout["bias"] = sd(shape, jnp.bfloat16)
    with pytest.raises(ValueError, match=re.escape(path)):
        make_params(layout, SEED)

    def init_leaf(p, sd, key):
        return jnp.zeros(sd.shape, sd.dtype) if p == path else None

    p = make_params(layout, SEED, init_leaf=init_leaf)
    leaves = dict((keystr(k), v) for k, v in
                  jax.tree_util.tree_flatten_with_path(p)[0])
    assert not np.asarray(leaves[path]).any()
    assert np.asarray(leaves["['reps'][0]['mixer']['w']"]).std() > 0


TOY = '''
import jax.numpy as jnp

import counts
import reference

CALLS = []


def program_fields(cfg):
    CALLS.append("program_fields")
    return {"attn_window": cfg["toy_window"]}


class Reference(reference.Reference):
    def __init__(self, cfg, quant=None):
        CALLS.append(("Reference", quant))
        super().__init__(cfg, quant)


class Shape(counts.Shape):
    @classmethod
    def from_config(cls, c):
        CALLS.append("Shape")
        return super().from_config(c)


def init_leaf(path, sd, key):
    if path == "['final_norm']['scale']":
        CALLS.append("init_leaf")
        return jnp.full(sd.shape, 1.5, sd.dtype)
    return None
'''


@pytest.fixture
def toy(tmp_path, monkeypatch):
    d = tmp_path / "archs"
    d.mkdir()
    (d / "toyarch.py").write_text(TOY)
    monkeypatch.setattr(archs, "ARCH_DIR", str(d))
    spec = tiny_spec()
    spec["cfg"].update(model_type="toyarch", toy_window=4096)
    return spec


def test_an_arch_module_serves_the_tiny_cell(toy, tmp_path):
    line = run.serve_cell(toy, SEED, 2.0, False, jax.devices(), {},
                          str(tmp_path), controls=["fp8"])
    assert line["correct"] is True, line["compared"]
    calls = archs.of(toy["cfg"]).program_fields.__globals__["CALLS"]
    assert {"program_fields", "Shape", "init_leaf", ("Reference", None),
            ("Reference", "fp8")} <= set(calls)
    sut = system.System(toy["cfg"])
    assert sut.cfg == dataclasses.replace(system.model_config(tiny()),
                                          attn_window=4096)
    final = sut.make_params(SEED)["final_norm"]["scale"]
    assert (np.asarray(final, np.float32) == 1.5).all()


def test_program_fields_build_nested_groups_and_refuse_unknown_ones(
        toy, monkeypatch):
    mod = archs.of(toy["cfg"]).program_fields.__globals__
    monkeypatch.setitem(mod, "program_fields", lambda c: {
        "family": "moe", "moe": {"num_experts": 4, "top_k": 2,
                                 "d_ff_expert": 32},
        "block_pattern": ["attn"]})
    cfg = system.model_config(toy["cfg"])
    assert cfg.family == "moe" and cfg.block_pattern == ("attn",)
    assert cfg.moe == MoEConfig(num_experts=4, top_k=2, d_ff_expert=32)
    monkeypatch.setitem(mod, "program_fields", lambda c: {"experts": 4})
    with pytest.raises(ValueError, match="experts"):
        system.model_config(toy["cfg"])
