"""A run with the timed path broken underneath must come out not
correct.  Each fault is planted in the program's jitted decode or
prefill (the engine looks both up when it is called), or for half of the
batch in the slot pool's decode, which knows the rows that hold live
requests; the rest of the run is the benchmark's own: driver, drain,
sample and reference.  The device check is skipped by calling the
test-only entry ``run.serve_cell``.

Faults a served cell can have: a token altered where it is produced (in
prefill, in decode), half of the batch given other rows' results, and a
decode step that returns its state unchanged (a copy of the state it
was given, which the call donates).  A one-chip cell has no exchange
between chips to leave out.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import run
import system  # noqa: F401  (puts the program on the path)
from conftest import tiny_spec
from repro.serve import engine

SEED = 2 ** 31 + 404
ORIG_DECODE = engine.jit_decode
ORIG_PREFILL = engine.jit_prefill
ORIG_POOL_DECODE = engine.SlotPool.decode


def _flip_first_row(logits):
    """Row 0's best token becomes its worst."""
    return logits.at[0].set(-logits[0])


def decode_token_altered(params, state, tokens, cfg, rt):
    logits, st = ORIG_DECODE(params, state, tokens, cfg=cfg, rt=rt)
    return _flip_first_row(logits), st


def prefill_token_altered(params, batch, cfg, rt, window):
    logits, st = ORIG_PREFILL(params, batch, cfg=cfg, rt=rt, window=window)
    return _flip_first_row(logits), st


def decode_half_batch(pool, now, **ids):
    """The pool's decode with half of the rows that hold live requests
    left out, rounded up: each gets another row's results, the first
    half's in order, then those of rows that hold none.  At the tiny
    mix's load most decodes hold one live request, which is then the
    half left out."""
    live = sorted(row for _, part in pool._marked
                  for row, r in zip(part.rows, part.requests) if not r.done)
    h = len(live) // 2
    out = live[h:]
    src = (live[:h] + [r for r in range(pool.rows)
                       if r not in live])[:len(out)]

    def decode(params, state, tokens, cfg, rt):
        logits, st = ORIG_DECODE(params, state, tokens, cfg=cfg, rt=rt)
        return logits.at[np.asarray(out)].set(logits[np.asarray(src)]), st

    engine.jit_decode = decode if out else ORIG_DECODE
    try:
        return ORIG_POOL_DECODE(pool, now, **ids)
    finally:
        engine.jit_decode = ORIG_DECODE


def decode_state_unchanged(params, state, tokens, cfg, rt):
    # a copy, since the call donates (deletes) the state it is given
    kept = jax.tree.map(jnp.copy, state)
    logits, _ = ORIG_DECODE(params, state, tokens, cfg=cfg, rt=rt)
    return logits, kept


FAULTS = {
    "decode_token_altered": (engine, "jit_decode", decode_token_altered),
    "prefill_token_altered": (engine, "jit_prefill", prefill_token_altered),
    "decode_half_batch": (engine.SlotPool, "decode", decode_half_batch),
    "decode_state_unchanged": (engine, "jit_decode", decode_state_unchanged),
}


def _run(tmp_path):
    return run.serve_cell(tiny_spec(), SEED, 2.0, False, jax.devices(), {},
                          str(tmp_path))


def test_sound_run_is_correct(tmp_path):
    assert _run(tmp_path)["correct"] is True


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_is_not_correct(fault, monkeypatch, tmp_path):
    owner, name, fn = FAULTS[fault]
    monkeypatch.setattr(owner, name, fn)
    line = _run(tmp_path)
    assert line["correct"] is False, line["compared"]
    gap = line["compared"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]
