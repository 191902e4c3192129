"""The control, kept at a size a test run can hold: the reference
computed in fp8 (the nearest precision below the configuration's
bfloat16) and put in the program's place has to come out as not
correct under the committed limits, while the program itself passes.
On the chip, at the cells' own sizes: ``benchmarks/control.py``."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import harness  # noqa: E402

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


@pytest.mark.parametrize("cell_name,seconds,number", [
    ("lm_train", 0.5, "grad_norm_gap"),
    ("img_train", 0.5, "grad_norm_gap_rms"),
    ("lm_decode", 6.0, "token_logit_gap"),
])
def test_control_fails_where_the_program_passes(cell_name, seconds, number):
    cell = harness.load_cell(cell_name)
    result = harness.run_cell(cell, seed=3_200_000_003, seconds=seconds,
                              trace=False, rehearse=True, t_start=0.0,
                              device=dict(CPU), control="fp8")
    assert result["rehearsal_checks_ok"] is True
    # off the TPU the line never says correct and carries no metric
    assert result["correct"] is False and result["metrics"] == {}
    assert result["rehearsal"] is True and result["failed"] == 0
    assert result["device"]["memory_peak_bytes"] is None
    program, control = result["checks"], result["control_checks"]
    # at toy width on the CPU the limits are the file's rehearsal ones
    limit = {**cell.limits, **cell.limits["rehearsal"]}[number]
    assert program[number] <= limit < control[number]
    assert control[number] > 3 * program[number]
