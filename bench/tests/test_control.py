"""The control of the comparison, at a size a CPU test can hold: the
reference with its weights rounded to float8_e4m3 reads a widest logit
gap above the limit on every seed, where the program (bf16) reads one
below it.  Readings at this size (CPU, 4 seeds): program 0.0014-0.0088;
fp8 control 0.111-0.155; int8 control 0-0.057, which does not separate
(its rounding is no coarser than the program's bf16 activations)."""
import jax
import pytest

import run
from conftest import tiny_spec

LIMIT = 0.04


@pytest.mark.parametrize("seed", [1, 2, 2 ** 31 + 3])
def test_fp8_control_fails_where_the_program_passes(seed, tmp_path):
    spec = tiny_spec(wide=True)
    spec["cfg"]["check"]["max_logit_gap"] = LIMIT
    line = run.serve_cell(spec, seed, 2.0, False, jax.devices(), {},
                          str(tmp_path), controls=["fp8"])
    assert line["correct"] is True
    assert line["compared"]["max_logit_gap"]["value"] <= LIMIT
    control = line["controls"]["fp8"]
    assert control["correct"] is False
    assert control["compared"]["max_logit_gap"]["value"] > LIMIT
