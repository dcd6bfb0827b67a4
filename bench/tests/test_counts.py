"""bench/counts.py against FLOPs and bytes worked out by hand for one
decode call (with a done row and live-KV positions) and one prefill call
of each configuration."""
import pytest

import counts
from conftest import load_config

# Qwen3-14B, 8 layers: per layer q 5120x5120, k and v 5120x1024 each,
# o 5120x5120, gate/up/down 3 x 5120x17408.
QWEN_LAYER = 26_214_400 + 2 * 5_242_880 + 26_214_400 + 3 * 89_128_960
QWEN_HEAD = 5120 * 151_936
QWEN_WEIGHT_BYTES = 2 * (8 * (QWEN_LAYER + 2 * 5120 + 2 * 128)
                         + 5120 + QWEN_HEAD)
QWEN_KV_POS = 2 * 8 * 2 * 8 * 128          # bf16, 8 layers, K and V
# StarCoder2-15B, 8 layers: q 6144x6144, k and v 6144x512 each,
# o 6144x6144, up/down 2 x 6144x24576 (no gate).
SC_LAYER = 37_748_736 + 2 * 3_145_728 + 37_748_736 + 2 * 150_994_944
SC_HEAD = 6144 * 49_152
SC_WEIGHT_BYTES = 2 * (8 * (SC_LAYER + 2 * 6144) + 6144 + SC_HEAD)
SC_KV_POS = 2 * 8 * 2 * 4 * 128


def shape(name):
    return counts.Shape.from_config(load_config(name))


def test_parameter_counts():
    q, s = shape("qwen3-14b"), shape("starcoder2-15b")
    assert q.layer_matmul_params == QWEN_LAYER == 330_301_440
    assert s.layer_matmul_params == SC_LAYER == 383_778_816
    assert q.weight_bytes_per_call == QWEN_WEIGHT_BYTES
    assert s.weight_bytes_per_call == SC_WEIGHT_BYTES
    assert q.kv_bytes_per_position == QWEN_KV_POS == 32_768
    assert s.kv_bytes_per_position == SC_KV_POS == 16_384


@pytest.mark.parametrize("name,layer,head,wbytes,kvpos,H,d,V", [
    ("qwen3-14b", QWEN_LAYER, QWEN_HEAD, QWEN_WEIGHT_BYTES, QWEN_KV_POS,
     40, 5120, 151_936),
    ("starcoder2-15b", SC_LAYER, SC_HEAD, SC_WEIGHT_BYTES, SC_KV_POS,
     48, 6144, 49_152),
])
def test_decode_call(name, layer, head, wbytes, kvpos, H, d, V):
    # a batch of three rows, one of them done: only the two live rows,
    # at 600 and 130 attended positions, count
    w = shape(name).decode_call([600, 130])
    attn = 8 * 4 * H * 128 * (600 + 130)
    assert w.flops == 2 * (2 * 8 * layer + 2 * head) + attn
    assert w.bytes == (wbytes + kvpos * (599 + 129) + 2 * kvpos
                       + 2 * (2 * d + 2 * V))
    assert w.calls == 1
    assert shape(name).decode_call([]).bytes == 0


@pytest.mark.parametrize("name,layer,head,wbytes,kvpos,H,d,V", [
    ("qwen3-14b", QWEN_LAYER, QWEN_HEAD, QWEN_WEIGHT_BYTES, QWEN_KV_POS,
     40, 5120, 151_936),
    ("starcoder2-15b", SC_LAYER, SC_HEAD, SC_WEIGHT_BYTES, SC_KV_POS,
     48, 6144, 49_152),
])
def test_prefill_call(name, layer, head, wbytes, kvpos, H, d, V):
    # 3 prompts of 128 tokens: causal attention over 128*129/2 pairs,
    # logits of the last position only
    w = shape(name).prefill_call(3, 128)
    assert w.flops == 3 * (128 * 2 * 8 * layer
                           + 8 * 4 * H * 128 * (128 * 129 // 2) + 2 * head)
    assert w.bytes == wbytes + 3 * 128 * kvpos + 3 * 128 * d * 2 + 3 * V * 2


def test_decode_rows_leave_out_the_weights():
    s = shape("qwen3-14b")
    full = s.decode_call([10, 20])
    rows = s.decode_rows([10, 20])
    assert rows.flops == full.flops
    assert rows.bytes == full.bytes - s.weight_bytes_per_call
    assert rows.calls == 0


def test_least_time_and_bound():
    s = shape("qwen3-14b")
    peak_f, peak_b = 197e12, 819e9
    small = s.prefill_call(1, 128)
    big = s.prefill_call(16, 512)
    assert small.least_seconds(peak_f, peak_b) == small.bytes / peak_b
    assert big.least_seconds(peak_f, peak_b) == big.flops / peak_f
    for name, ctx in (("qwen3-14b", 1024), ("starcoder2-15b", 2176)):
        assert counts.decode_bandwidth_bound(shape(name), 16, ctx,
                                             peak_f, peak_b)
