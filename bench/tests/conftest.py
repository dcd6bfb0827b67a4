"""CPU tests of the benchmark's own code.  Run them by path:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
import copy
import json
import os
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)


def load_config(name: str) -> dict:
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


def load_traffic(name: str) -> dict:
    with open(os.path.join(BENCH, "traffic", f"{name}.json")) as f:
        return json.load(f)


def tiny(config: str = "qwen3-14b", wide: bool = False) -> dict:
    """A configuration file of the same architecture at a CPU size;
    ``wide`` is four times as wide, with a 16 times larger vocabulary."""
    c = copy.deepcopy(load_config(config))
    c.update(num_hidden_layers=2, hidden_size=64, intermediate_size=128,
             num_attention_heads=4, num_key_value_heads=2, head_dim=16,
             vocab_size=256)
    if wide:
        c.update(hidden_size=256, intermediate_size=512,
                 num_attention_heads=8, vocab_size=4096)
    c["fleet"]["capacity"] = 4
    c["check"] = {"max_logit_gap": 0.05, "reference_tokens": 512}
    return c


TINY_MIX = {"arrivals": {"process": "poisson", "rate_rps": 8.0,
                         "base_seed": 3},
            "prompt_buckets": [8, 16], "bucket_p": [0.6, 0.4],
            "short": {"dist": "lognormal", "median": 6, "sigma": 0.5,
                      "min": 4, "max": 12},
            "long": {"dist": "uniform", "min": 20, "max": 30},
            "long_share": {"phase_s": 1, "shares": [0.1, 0.4]},
            "drain_s": 60, "check_requests": 6,
            "trace_window_s": [0.5, 1.0]}


def tiny_spec(config: str = "qwen3-14b", mix: dict = None,
              wide: bool = False) -> dict:
    names = ["ttft_p50_s", "ttft_p90_s", "itl_p95_s", "output_tokens_per_s",
             "setup_s"]
    return {"cell": {"name": "tiny", "chips": 1}, "cfg": tiny(config, wide),
            "mix": copy.deepcopy(mix or TINY_MIX), "per_layer": [],
            "end_to_end": [{"name": n, "unit": "s"} for n in names]}


@pytest.fixture
def spec():
    return tiny_spec()
