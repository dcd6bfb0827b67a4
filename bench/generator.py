"""Open-loop request schedules on the wall clock, from a traffic file.

One generator reads every traffic mix.  A mix is a JSON file of
parameters (``bench/traffic/<name>.json``):

- ``arrivals``: ``{"process": "poisson", "rate_rps": r, "base_seed": s}``.
  Arrival times are one Poisson realisation at rate ``r`` over the window,
  drawn from the mix's fixed ``base_seed``; the first arrival is due at 0.
- ``prompt_buckets`` / ``bucket_p``: prompt lengths come from this small
  set, in these shares (counts are rounded, so every seed gets the same
  number of prompts of each length).
- ``short``: output lengths of the short class, a lognormal with the given
  median and sigma, rounded and clipped to ``[min, max]``.
- ``long`` (optional): output lengths of the long class, uniform integers
  in ``[min, max]``.
- ``long_share`` (optional): ``{"phase_s": p, "shares": [a, b, ...]}``: the
  share of long requests among the arrivals of each ``p``-second phase,
  cycling through ``shares`` from the window's start.  Without ``long``
  every request is short.

The run's seed never changes the work: the arrival times, which arrivals
are long, every output length and every prompt's length are fixed by the
mix and the window length.  The seed draws every prompt token id (and the
run draws its weights from it).  Under wave admission the order of long
and short requests decides who waits behind whom, so a seed that
reordered them changed the work: two runs of one seed agreed far more
closely than runs of three seeds did.  So runs with different seeds serve
the same requests, and their spread is that of the system, not of the
load.

The length and arrival arithmetic follows ``repro.fleet.traffic``
(lognormal and bimodal lengths, prompt lengths from a fixed set), moved
from ticks onto seconds.
"""
from __future__ import annotations

import json
import math
from dataclasses import dataclass
from typing import List, Optional

import numpy as np


@dataclass(frozen=True)
class Arrival:
    """One request of a schedule: due ``due_s`` after the window opens."""
    rid: int
    due_s: float
    prompt: np.ndarray          # int32 token ids, length = its bucket
    max_new_tokens: int
    long: bool


def load_mix(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def arrival_times(mix: dict, seconds: float) -> np.ndarray:
    """The mix's fixed Poisson arrival times in ``[0, seconds)``."""
    arr = mix["arrivals"]
    if arr["process"] != "poisson":
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    rate = float(arr["rate_rps"])
    rng = np.random.default_rng(int(arr["base_seed"]))
    # enough gaps to pass the window with near certainty, then cut
    n_max = int(rate * seconds + 10 * math.sqrt(rate * seconds + 1) + 10)
    gaps = rng.exponential(1.0 / rate, size=n_max)
    times = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
    return times[times < seconds]


def _short_pool(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    if spec["dist"] != "lognormal":
        raise ValueError(f"short class: unknown dist {spec['dist']!r}")
    x = rng.lognormal(math.log(spec["median"]), spec["sigma"], size=n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def _long_pool(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    if spec["dist"] != "uniform":
        raise ValueError(f"long class: unknown dist {spec['dist']!r}")
    return rng.integers(spec["min"], spec["max"] + 1, size=n)


def _bucket_pool(mix: dict, n: int) -> np.ndarray:
    """Exactly ``round(p * n)`` prompts of each bucket (the remainder goes
    to the last bucket)."""
    buckets, ps = mix["prompt_buckets"], mix["bucket_p"]
    if len(buckets) != len(ps) or abs(sum(ps) - 1.0) > 1e-9:
        raise ValueError("prompt_buckets and bucket_p must pair up and "
                         "bucket_p must sum to 1")
    counts = [int(round(p * n)) for p in ps[:-1]]
    counts.append(n - sum(counts))
    return np.repeat(np.asarray(buckets, np.int64), counts)


def long_counts(mix: dict, times: np.ndarray) -> np.ndarray:
    """Number of long requests among the arrivals of each phase."""
    share = mix.get("long_share")
    if "long" not in mix or share is None:
        return np.zeros(0, np.int64)
    phase = (times // share["phase_s"]).astype(np.int64)
    shares = share["shares"]
    out = []
    for p in range(int(phase.max()) + 1 if times.size else 0):
        n_p = int((phase == p).sum())
        out.append(int(round(shares[p % len(shares)] * n_p)))
    return np.asarray(out, np.int64)


def schedule(mix: dict, seconds: float, seed: int,
             vocab: int) -> List[Arrival]:
    """The run's requests, in order of their due time."""
    times = arrival_times(mix, seconds)
    n = times.size
    base = np.random.default_rng(int(mix["arrivals"]["base_seed"]) + 1)
    is_long = np.zeros(n, bool)
    counts = long_counts(mix, times)
    n_long = int(counts.sum())
    long_pool = _long_pool(mix["long"], n_long, base) if n_long else \
        np.zeros(0, np.int64)
    short_pool = _short_pool(mix["short"], n - n_long, base)
    buckets = _bucket_pool(mix, n)

    order = np.random.default_rng(int(mix["arrivals"]["base_seed"]) + 2)
    if n_long:
        phase = (times // mix["long_share"]["phase_s"]).astype(np.int64)
        for p, k in enumerate(counts):
            idx = np.flatnonzero(phase == p)
            is_long[order.permutation(idx)[:k]] = True
    lengths = np.empty(n, np.int64)
    lengths[is_long] = order.permutation(long_pool)
    lengths[~is_long] = order.permutation(short_pool)
    buckets = order.permutation(buckets)
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        prompt = rng.integers(0, vocab, size=int(buckets[i]), dtype=np.int32)
        out.append(Arrival(rid=i, due_s=float(times[i]), prompt=prompt,
                           max_new_tokens=int(lengths[i]),
                           long=bool(is_long[i])))
    return out


def ring_window(mix: dict, multiple: int = 128) -> int:
    """KV ring length: the largest prompt plus the largest output, rounded
    up to ``multiple``, so no request wraps the ring."""
    top = mix["long"]["max"] if "long" in mix else mix["short"]["max"]
    need = max(mix["prompt_buckets"]) + top
    return -(-need // multiple) * multiple


def max_output(mix: dict) -> int:
    return mix["long"]["max"] if "long" in mix else mix["short"]["max"]


def describe(arrivals: List[Arrival]) -> Optional[dict]:
    if not arrivals:
        return None
    lens = np.asarray([a.max_new_tokens for a in arrivals])
    return {"requests": len(arrivals), "long": int(sum(a.long for a in arrivals)),
            "output_tokens": int(lens.sum()),
            "prompt_tokens": int(sum(a.prompt.size for a in arrivals))}
