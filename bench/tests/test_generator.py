"""Traffic: determinism by seed, the same requests for every seed, and the
bucket, phase and clip rules."""
import collections

import numpy as np
import pytest

import generator
from conftest import load_traffic

MIXES = ["chat_mixed", "chat_short", "code_complete"]
BIG_SEED = 2 ** 31 + 12345


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_schedule(name):
    mix = load_traffic(name)
    a = generator.schedule(mix, 51, BIG_SEED, 1000)
    b = generator.schedule(mix, 51, BIG_SEED, 1000)
    assert len(a) == len(b) > 0
    for x, y in zip(a, b):
        assert x.due_s == y.due_s and x.max_new_tokens == y.max_new_tokens
        assert np.array_equal(x.prompt, y.prompt)


@pytest.mark.parametrize("name", MIXES)
def test_seeds_draw_the_same_work(name):
    """Only the token ids follow the seed: every due time, output length,
    long flag and prompt length is the mix's own."""
    mix = load_traffic(name)
    a = generator.schedule(mix, 51, 11, 1000)
    b = generator.schedule(mix, 51, BIG_SEED, 1000)
    assert [(x.due_s, x.max_new_tokens, x.long, x.prompt.size) for x in a] \
        == [(x.due_s, x.max_new_tokens, x.long, x.prompt.size) for x in b]
    assert not any(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


@pytest.mark.parametrize("name", MIXES)
def test_buckets_and_clip(name):
    mix = load_traffic(name)
    s = generator.schedule(mix, 51, 7, 1000)
    n = len(s)
    counts = collections.Counter(x.prompt.size for x in s)
    for b, p in zip(mix["prompt_buckets"][:-1], mix["bucket_p"][:-1]):
        assert counts[b] == round(p * n)
    assert set(counts) <= set(mix["prompt_buckets"])
    for x in s:
        spec = mix["long"] if x.long else mix["short"]
        assert spec["min"] <= x.max_new_tokens <= spec["max"]
        assert x.prompt.min() >= 0 and x.prompt.max() < 1000
        assert x.prompt.dtype == np.int32
    assert s[0].due_s == 0.0
    assert all(0 <= x.due_s < 51 for x in s)
    assert all(a.due_s <= b.due_s for a, b in zip(s, s[1:]))


def test_long_share_alternates_by_phase():
    mix = load_traffic("chat_mixed")
    s = generator.schedule(mix, 51, 5, 1000)
    phase_s = mix["long_share"]["phase_s"]
    shares = mix["long_share"]["shares"]
    by_phase = collections.defaultdict(list)
    for x in s:
        by_phase[int(x.due_s // phase_s)].append(x.long)
    for p, longs in by_phase.items():
        assert sum(longs) == round(shares[p % len(shares)] * len(longs))


def test_short_only_mix_has_no_long():
    s = generator.schedule(load_traffic("chat_short"), 51, 5, 1000)
    assert not any(x.long for x in s)


def test_rate_and_ring():
    mix = load_traffic("chat_mixed")
    t = generator.arrival_times(mix, 1000)
    rate = mix["arrivals"]["rate_rps"]
    assert abs(t.size / 1000 - rate) < 4 * np.sqrt(rate * 1000) / 1000
    assert generator.ring_window(mix) == 1024
    assert generator.ring_window(load_traffic("code_complete")) == 2176
    assert generator.ring_window(load_traffic("chat_short")) == 640
