import json

import pytest

from bench import files


def test_every_cell_resolves_by_name():
    spec = files.benchmark()
    for w in spec["workloads"]:
        cell = files.resolve(w["name"])
        assert cell["config"]["reference"]
        assert hasattr(cell["traffic"], "run") and hasattr(cell["reference"], "loss")
        names = {m["name"] for m in cell["per_layer"]}
        assert names and names == set(cell["metric_readers"])
        assert "setup_s" in {m["name"] for m in cell["end_to_end"]}


def test_new_cell_config_and_metric_need_no_edit(tmp_path):
    """A cell, a configuration and a metric added as files only."""
    bench = tmp_path / "bench"
    for kind in ("configs", "workloads", "traffic", "references", "metrics"):
        (bench / kind).mkdir(parents=True)
    (bench / "configs" / "toy-2l.json").write_text(json.dumps({"reference": "toy_ref", "n": 2}))
    (bench / "workloads" / "toy-2l.echo.json").write_text(json.dumps({"batch": 3}))
    (bench / "traffic" / "echo.py").write_text("def run(run):\n    return run\n")
    (bench / "references" / "toy_ref.py").write_text("def loss(*a):\n    return 0.0\n")
    (bench / "metrics" / "toy.count-per_s.py").write_text(
        "def read(run):\n    return run['counters']['n'] / 2\n")
    (bench / "metrics" / "toy_idle.py").write_text("def read(run):\n    return 1.5\n")
    spec = {
        "configs": [{"name": "toy-2l"}],
        "workloads": [{"name": "toy-2l.echo", "config": "toy-2l", "traffic": "echo", "chips": 1}],
        "end_to_end": [{"name": "setup_s"}],
        "per_layer": [{"name": "toy.count-per_s", "workloads": ["toy-2l.echo"]},
                      {"name": "toy_idle.echo", "workloads": ["toy-2l.echo"]},
                      {"name": "elsewhere", "workloads": ["other"]}],
    }
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = files.resolve("toy-2l.echo", root=tmp_path)
    assert cell["config"]["n"] == 2 and cell["workload"]["batch"] == 3
    assert cell["traffic"].run("x") == "x"
    assert cell["reference"].loss() == 0.0
    assert list(cell["metric_readers"]) == ["toy.count-per_s", "toy_idle.echo"]
    assert cell["metric_readers"]["toy.count-per_s"].read({"counters": {"n": 8}}) == 4
    # a split metric with no file of its own is read by its stem's reader
    assert cell["metric_readers"]["toy_idle.echo"].read({}) == 1.5


def test_unknown_names_raise(tmp_path):
    with pytest.raises(KeyError):
        files.resolve("no-such.cell")
    with pytest.raises(FileNotFoundError):
        files.load_json("workloads", "no-such.cell")
    with pytest.raises(FileNotFoundError):
        files.load_module("metrics", "no_such_metric")
