"""``chip_smoke.py`` rehearsed on the CPU at a tiny width.

The script itself refuses to run without a TPU; these tests call its
phases directly with the width-cut preset, so the control flow, the
checks and the tolerance are exercised here at no chip time.  Its
four-chip phase runs in ``test_multidevice.py`` on virtual devices.
"""
import importlib.util
import os

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.models import transformer as T

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def tiny(smoke):
    cfg = get_config("qwen3-14b", reduced=True)
    return cfg, smoke.init_params(cfg, 0)


def test_smoke_exits_nonzero_without_a_tpu(smoke, capsys):
    with pytest.raises(SystemExit) as e:
        smoke.main([])
    assert e.value.code not in (0, None)
    assert "cpu" in str(e.value.code)
    assert '"ok"' not in capsys.readouterr().out


def test_smoke_config_cuts_depth_only(smoke):
    cfg, pub = smoke.smoke_config(), get_config("qwen3-14b")
    assert cfg.num_layers == smoke.LAYERS < pub.num_layers
    assert cfg == pub.replace(num_layers=smoke.LAYERS)
    assert smoke.PROMPT_LEN + smoke.MAX_NEW <= smoke.WINDOW


def test_smoke_serving_splits_fuses_and_steady_window_compiles_nothing(
        smoke, tiny):
    cfg, params = tiny
    with smoke.CompileCounter() as cc:
        warm = smoke.serve(cfg, params, 0)
        n_warm = cc.count
        steady = smoke.serve(cfg, params, 0)
    assert 16 <= warm["requests"] <= 32
    assert warm["splits"] >= 1 and warm["fuses"] >= 1
    assert n_warm > 0 and cc.count == n_warm
    assert {k: v for k, v in steady.items() if k != "wall_s"} == \
        {k: v for k, v in warm.items() if k != "wall_s"}


def test_smoke_logit_check_admits_bf16_and_refuses_fp8(smoke, tiny):
    cfg, params = tiny
    assert smoke.logit_check(cfg, params, 0) <= smoke.LOGIT_TOL
    # the same served path on weights rounded to float8 (4 significant
    # bits) must fall outside the tolerance
    p8 = jax.tree.map(
        lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype), params)
    tokens = smoke.check_tokens(cfg, 0)
    ref = T.reference_logits(params, tokens, cfg)[:, smoke.PROMPT_LEN - 1:]
    err8 = smoke.rel_err(smoke.served_logits(cfg, p8, tokens), ref)
    assert err8 > smoke.LOGIT_TOL
