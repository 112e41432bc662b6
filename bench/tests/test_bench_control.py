"""The fp8 control of the training check comes out not correct.

The control is the plain reference one precision step below the bfloat16
the configurations state, put in the program's place; the program's own
checked steps, compared the same way, come out correct. Both at the test
size of ``tiny``, against the limits read at that size.
"""
import pytest

from bench.calibrate import training
from bench.tests import tiny

NUMBERS = ("loss_gap", "grad_gap", "update_gap")


def _correct(gaps: dict, limits: dict) -> bool:
    return all(gaps[k] <= limits[k] for k in NUMBERS)


@pytest.mark.parametrize("name", ["phi3-medium-1l.train_ckpt", "mamba2-1.3b-24l.train_ckpt"])
def test_program_is_correct_and_fp8_control_is_not(name):
    cell = tiny.cell(name)
    limits = cell["workload"]["limits"]
    assert _correct(training(cell, 7, "program"), limits)
    assert not _correct(training(cell, 7, "control"), limits)
