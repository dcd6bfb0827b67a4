"""The comparison that decides ``correct``.

Once the window has closed and the program's state is freed, a sample of
the finished requests, drawn from the seed, is run through the plain
float32 reference (``reference.py``) over each prompt followed by the
tokens the program served.  At every served position the reference's
logits give its best logit and the logit of the served token; the number
compared is the widest gap between the two over the sample.  The served
tokens are greedy, so a sound program serves a token whose reference logit
lies within rounding of the best.

The sample holds the longest finished request, at least one request of
each prompt bucket, the requests whose state crossed a split or fuse (up
to half of the sample), and requests drawn at random for the rest.

Every request sent must also have been served exactly its
``max_new_tokens`` tokens by the drain's end.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence

import numpy as np

from reference import Reference


@dataclass
class Sampled:
    rid: int
    prompt: np.ndarray
    served: List[int]
    crossed_reconfig: bool


def draw_sample(tracked, n: int, seed: int) -> List[Sampled]:
    done = [tr for tr in tracked
            if len(tr.req.generated) == tr.req.max_new_tokens]
    if not done:
        return []
    rng = np.random.default_rng([int(seed), 0x5EED])
    picked = {}

    def take(tr):
        if tr.arrival.rid not in picked and len(picked) < n:
            picked[tr.arrival.rid] = tr

    take(max(done, key=lambda tr: (len(tr.req.generated), -tr.arrival.rid)))
    for plen in sorted({tr.arrival.prompt.size for tr in done}):
        pool = [tr for tr in done if tr.arrival.prompt.size == plen]
        take(pool[int(rng.integers(len(pool)))])
    crossed = [tr for tr in done if tr.crossed_reconfig]
    for i in rng.permutation(len(crossed))[:max(1, n // 2)]:
        take(crossed[int(i)])
    for i in rng.permutation(len(done)):
        take(done[int(i)])
    return [Sampled(rid=tr.arrival.rid, prompt=tr.arrival.prompt,
                    served=list(tr.req.generated),
                    crossed_reconfig=tr.crossed_reconfig)
            for tr in picked.values()]


@dataclass
class Batch:
    tokens: np.ndarray      # (B, W) prompt + served[:-1], zero padded
    pos: np.ndarray         # (B, P) position whose logits chose each token
    tgt: np.ndarray         # (B, P) the served token
    valid: np.ndarray       # (B, P) bool


def batches(sample: Sequence[Sampled], window: int, max_out: int,
            rows: int) -> List[Batch]:
    """Fixed-shape blocks of ``rows`` sequences, so that each cell compiles
    the reference once."""
    out = []
    for b0 in range(0, len(sample), rows):
        blk = list(sample[b0:b0 + rows])
        tok = np.zeros((rows, window), np.int32)
        pos = np.zeros((rows, max_out), np.int32)
        tgt = np.zeros((rows, max_out), np.int32)
        val = np.zeros((rows, max_out), bool)
        for i, s in enumerate(blk):
            p = s.prompt.size
            seq = np.concatenate([s.prompt, np.asarray(s.served[:-1],
                                                       np.int32)])
            if seq.size > window:
                raise ValueError(f"request {s.rid}: {seq.size} positions > "
                                 f"ring {window}")
            tok[i, :seq.size] = seq
            n = len(s.served)
            pos[i, :n] = np.arange(p - 1, p - 1 + n)
            tgt[i, :n] = s.served
            val[i, :n] = True
        out.append(Batch(tok, pos, tgt, val))
    return out


@dataclass
class Gaps:
    widest: float
    at: Optional[tuple]            # (rid, token index) of the widest
    tokens: int                    # served tokens compared
    controls: Optional[Dict[str, float]] = None


def logit_gaps(ref: Reference, params, sample: Sequence[Sampled],
               window: int, max_out: int, rows: int,
               controls: Optional[Dict[str, Reference]] = None) -> Gaps:
    """Widest gap between the reference's best logit and its logit of the
    served token.  For each of ``controls`` (the reference in a lower
    precision), also the widest gap of the token that the control puts
    first, at the same positions and on the same inputs."""
    controls = controls or {}
    widest, at, count = -np.inf, None, 0
    cw = {k: -np.inf for k in controls}
    for bi, b in enumerate(batches(sample, window, max_out, rows)):
        x = ref.hidden(params, b.tokens)
        best, _, at_tgt = (np.asarray(a) for a in ref.reduce(
            params, x, b.pos, b.tgt))
        gap = np.where(b.valid, best - at_tgt, -np.inf)
        i, j = np.unravel_index(np.argmax(gap), gap.shape)
        if gap[i, j] > widest:
            widest, at = float(gap[i, j]), (sample[bi * rows + i].rid, int(j))
        count += int(b.valid.sum())
        for name, control in controls.items():
            xc = control.hidden(params, b.tokens)
            _, carg, _ = control.reduce(params, xc, b.pos, b.tgt)
            del xc
            _, _, at_c = (np.asarray(a) for a in ref.reduce(
                params, x, b.pos, np.asarray(carg)))
            cg = np.where(b.valid, best - at_c, -np.inf)
            cw[name] = max(cw[name], float(cg.max()))
        del x
    return Gaps(widest=float(widest), at=at, tokens=count,
                controls={k: float(v) for k, v in cw.items()} or None)
