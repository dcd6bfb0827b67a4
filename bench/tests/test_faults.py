"""A run with the timed path broken underneath must come out not
correct.  Each fault is planted in the program's jitted decode or
prefill (the engine looks both up when it is built), and the rest of the
run is the benchmark's own: driver, drain, sample and reference.  The
device check is skipped by calling the test-only entry
``run.serve_cell``.

Faults a served cell can have: a token altered where it is produced (in
prefill, in decode), half of the batch given other rows' results, and a
decode step that returns its state unchanged.  A one-chip cell has no
exchange between chips to leave out.
"""
import jax
import pytest

import run
import system  # noqa: F401  (puts the program on the path)
from conftest import tiny_spec
from repro.serve import engine

SEED = 2 ** 31 + 404
ORIG_DECODE = engine.jit_decode
ORIG_PREFILL = engine.jit_prefill


def _flip_first_row(logits):
    """Row 0's best token becomes its worst."""
    return logits.at[0].set(-logits[0])


def decode_token_altered(params, state, tokens, cfg, rt):
    logits, st = ORIG_DECODE(params, state, tokens, cfg=cfg, rt=rt)
    return _flip_first_row(logits), st


def prefill_token_altered(params, batch, cfg, rt, window):
    logits, st = ORIG_PREFILL(params, batch, cfg=cfg, rt=rt, window=window)
    return _flip_first_row(logits), st


def decode_half_batch(params, state, tokens, cfg, rt):
    logits, st = ORIG_DECODE(params, state, tokens, cfg=cfg, rt=rt)
    B = logits.shape[0]
    if B < 2:
        return _flip_first_row(logits), st
    h = B // 2
    # the second half of the rows is left out: it gets the first half's
    # results
    return logits.at[h:2 * h].set(logits[:h]), st


def decode_state_unchanged(params, state, tokens, cfg, rt):
    logits, _ = ORIG_DECODE(params, state, tokens, cfg=cfg, rt=rt)
    return logits, state


FAULTS = {
    "decode_token_altered": ("jit_decode", decode_token_altered),
    "prefill_token_altered": ("jit_prefill", prefill_token_altered),
    "decode_half_batch": ("jit_decode", decode_half_batch),
    "decode_state_unchanged": ("jit_decode", decode_state_unchanged),
}


def _run(tmp_path):
    return run.serve_cell(tiny_spec(), SEED, 2.0, False, jax.devices(), {},
                          str(tmp_path))


def test_sound_run_is_correct(tmp_path):
    assert _run(tmp_path)["correct"] is True


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_is_not_correct(fault, monkeypatch, tmp_path):
    name, fn = FAULTS[fault]
    monkeypatch.setattr(engine, name, fn)
    line = _run(tmp_path)
    assert line["correct"] is False, line["compared"]
    gap = line["compared"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]
