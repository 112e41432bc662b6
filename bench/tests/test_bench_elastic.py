"""The elastic restore cell: found by name, its readers, and whole runs of it
at the test size on four host devices (in a child process, which alone can
have them), sound and with its control planted."""
import copy
import json
import os
import subprocess
import sys
import time

import pytest

from bench import files
from bench.tests import tiny

CELL = "phi3-medium-2l.elastic_resume_4chip"
METRICS = ("elastic_read_amp", "elastic_load_s_per_gib", "elastic_place_s_per_gib",
           "shard_save_s_per_gib")
ROOT = files.ROOT


def cell() -> dict:
    """The cell at the widths of ``tiny`` (its phi3 sizes), two layers kept."""
    full = files.resolve(CELL)
    c = copy.deepcopy(full["config"])
    c.update(tiny.SIZES["phi3-medium-1l"])
    return dict(full, config=c, workload=dict(full["workload"], seq_len=32, batch=4))


def _runs() -> dict:
    """Sound, sound traced, and the control; run where JAX has four devices."""
    from bench import harness
    from bench.shard_fault import SWAPPED_SHARDS

    out = {}
    for name, trace, fault in [("sound", False, None), ("traced", True, None),
                               ("control", False, SWAPPED_SHARDS)]:
        out[name] = harness.run_cell(cell(), 123456789012, 0.5, trace, time.monotonic(),
                                     tiny.CPU, None, fault)
    return out


@pytest.fixture(scope="module")
def runs():
    env = dict(os.environ, PYTHONPATH=os.pathsep.join([str(ROOT), str(ROOT / "src")]),
               JAX_PLATFORMS="cpu", XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = ("import json\nfrom bench.tests import test_bench_elastic as t\n"
            "print(json.dumps(t._runs()))\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True, env=env,
                         cwd=str(ROOT), timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_cell_resolves_by_name():
    c = files.resolve(CELL)
    assert c["entry"]["chips"] == 4 and c["config"]["reference"] == "dense_lm"
    assert c["config"]["num_hidden_layers"] == 2
    assert c["config"]["meshes"]["saved"] == [2, 2] and c["config"]["meshes"]["restored"] == [1, 4]
    assert hasattr(c["traffic"], "run") and hasattr(c["reference"], "loss")
    assert {m["name"] for m in c["end_to_end"]} == {"restore_gib_s", "setup_s"}
    assert set(METRICS) == set(c["metric_readers"])


GIB = 2**30
READING = {"restores": 2, "state_bytes": 3 * GIB, "gib_restored": 6.0, "device_wait_s": 1.5,
           "span_s": {"ckpt.read": 4.0, "ckpt.scatter": 2.0, "ckpt.place": 0.3},
           "ckpt_read_bytes": 6 * GIB, "save_s": [9.0], "gib_saved": 3.0}
# the same reading from a program with no sharded restore: its spans and
# counters are not there
PARENT = dict(READING, span_s={"ckpt.read": 4.0, "ckpt.place": 0.3}, ckpt_read_bytes=None)


@pytest.mark.parametrize("metric,value,parent", [
    ("elastic_read_amp", 1.0, None),
    ("elastic_load_s_per_gib", 1.0, None),
    ("elastic_place_s_per_gib", 0.3, 0.3),
    ("shard_save_s_per_gib", 3.0, 3.0),
])
def test_reader_on_a_hand_made_reading(metric, value, parent):
    read = files.metric_reader(metric).read
    assert read({"counters": READING}) == pytest.approx(value)
    assert read({"counters": PARENT}) == (parent if parent is None else pytest.approx(parent))


def test_sound_run_on_four_host_devices_is_correct(runs):
    sound, traced = runs["sound"], runs["traced"]
    for out in (sound, traced):
        assert out["correct"] is True, out["compared"]
        assert out["attempted"] > 0 and out["failed"] == 0
        assert out["info"]["compiles_in_window"] == 0
    assert set(sound["metrics"]) == {"restore_gib_s", "setup_s"}
    assert set(traced["metrics"]) == set(METRICS)
    assert traced["metrics"]["elastic_read_amp"]["value"] == 1.0


def test_control_shards_on_wrong_devices_is_not_correct(runs):
    out = runs["control"]
    assert out["correct"] is False
    number = out["compared"]["restore_leaves_differing"]
    assert number["value"] > number["limit"]
