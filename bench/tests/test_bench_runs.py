"""Whole runs of each cell at the test size, with the chip check skipped:
sound, they are correct; a restore that alters what it read, or the resume
cell's bf16 control, is not."""
import pytest

from bench.faults import FAULTS
from bench.tests import tiny


@pytest.mark.parametrize("name", ["phi3-medium-1l.train_ckpt", "mamba2-1.3b-24l.train_ckpt",
                                  "phi3-medium-1l.resume"])
def test_sound_run_is_correct(name):
    out = tiny.run(name)
    assert out["correct"] is True, out["compared"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert "setup_s" in out["metrics"]
    assert list(out)[-1] == "compared"


@pytest.mark.parametrize("fault", ["altered_restore", "bf16_restore"])
def test_resume_fault_is_not_correct(fault):
    out = tiny.run("phi3-medium-1l.resume", fault=FAULTS[fault])
    assert out["correct"] is False
    number = out["compared"]["restore_leaves_differing"]
    assert number["value"] > number["limit"]
