"""Whole runs of the training cells at the test size, with the chip check
skipped and a fault planted under the timed path: the number that should
catch it exceeds its limit and ``correct`` is false."""
import pytest

from bench.faults import FAULTS
from bench.tests import tiny

TRAIN = ["phi3-medium-1l.train_ckpt", "mamba2-1.3b-24l.train_ckpt"]


@pytest.mark.parametrize("name,fault,number", [
    *[(n, "state_unchanged", "update_gap") for n in TRAIN],
    *[(n, "half_batch", "grad_gap") for n in TRAIN],
    *[(n, "altered_save", "ckpt_leaves_differing") for n in TRAIN],
])
def test_planted_fault_is_not_correct(name, fault, number):
    out = tiny.run(name, fault=FAULTS[fault])
    assert out["correct"] is False
    assert out["compared"][number]["value"] > out["compared"][number]["limit"]
