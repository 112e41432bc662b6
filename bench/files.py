"""Find a cell's files by name.

``BENCHMARK.json`` at the root of the checkout names the cells, their
configurations and the metrics. Everything else is a file under ``bench/``
named after the thing it describes:

* ``configs/<config>.json``: a configuration as it is run;
* ``workloads/<cell>.json``: a cell's traffic and the limits of its check;
* ``traffic/<traffic>.py``: the generator that runs one kind of traffic;
* ``references/<reference>.py``: a configuration's plain reference;
* ``metrics/<metric>.py``: the reader of one per-layer metric, or
  ``metrics/<stem>.py`` for a metric ``<stem>.<split>`` whose splits,
  each moving another end-to-end metric, are read alike.

A new cell, configuration or metric is a new file, with no edit here.
"""
from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_json(kind: str, name: str, bench: Path = BENCH) -> dict:
    path = bench / kind / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no file for {kind[:-1]} {name!r}: {path}")
    return json.loads(path.read_text())


def load_module(kind: str, name: str, bench: Path = BENCH):
    """Import ``<bench>/<kind>/<name>.py``; names may hold '.' and '-'."""
    path = bench / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no file for {kind[:-1]} {name!r}: {path}")
    mod_name = f"bench_{kind}_{name}".replace(".", "_").replace("-", "_")
    if mod_name in sys.modules and getattr(sys.modules[mod_name], "__file__", None) == str(path):
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, bench: Path = BENCH):
    """The reader of metric ``name``: its own file, else its stem's."""
    stem = name.split(".", 1)[0]
    if (bench / "metrics" / f"{name}.py").is_file() or stem == name:
        return load_module("metrics", name, bench)
    return load_module("metrics", stem, bench)


def benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(cell: str, root: Path = ROOT, bench: Path | None = None) -> dict:
    """Everything one run of ``cell`` needs, found by name."""
    bench = bench or root / "bench"
    spec = benchmark(root)
    entries = {w["name"]: w for w in spec["workloads"]}
    if cell not in entries:
        raise KeyError(f"unknown workload {cell!r}; BENCHMARK.json has {sorted(entries)}")
    entry = entries[cell]
    config = load_json("configs", entry["config"], bench)
    return {
        "name": cell,
        "entry": entry,
        "config": config,
        "workload": load_json("workloads", cell, bench),
        "traffic": load_module("traffic", entry["traffic"], bench),
        "reference": load_module("references", config["reference"], bench),
        "end_to_end": [m for m in spec["end_to_end"] if _applies(m, cell)],
        "per_layer": [m for m in spec["per_layer"] if _applies(m, cell)],
        "metric_readers": {
            m["name"]: metric_reader(m["name"], bench)
            for m in spec["per_layer"] if _applies(m, cell)
        },
    }
