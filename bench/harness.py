"""One run of one cell: set-up, a measured window, the check, the result line.

The generator of the cell's traffic does the work (``traffic/<traffic>.py``);
this module gives it a :class:`Run` that keeps the clock, the trace and the
spans, and turns what the generator returns into the result line: the
end-to-end metrics with ``--trace 0``, the per-layer metrics (each read by
``metrics/<name>.py``) with ``--trace 1``.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import math
import os
import shutil
import sys
import tempfile
import time

import jax

from bench import files, tracing
from bench.peaks import peaks

SPANS = {"train_step", "maybe_save", "digest", "evict", "store_open", "restore", "device_wait"}
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")
_compiles = [0]


def _on_event(name: str, *_args, **_kw) -> None:
    if name in COMPILE_EVENTS:
        _compiles[0] += 1


jax.monitoring.register_event_duration_secs_listener(_on_event)


class Run:
    """What a traffic generator sees of the harness."""

    def __init__(self, cell: dict, seed: int, seconds: float, trace: bool, t_start: float,
                 fault=None):
        self.cell = cell
        self.config, self.workload = cell["config"], cell["workload"]
        self.reference = cell["reference"]
        self.seed, self.seconds, self.trace, self.fault = seed, seconds, trace, fault
        self.t_start = t_start
        self.scratch = tempfile.mkdtemp(prefix="bench_run_")
        self.setup_s = None
        self.window_s = None
        self.trace_summary = None
        self.compiles_in_window = 0
        self._t0 = None
        self._span = None
        self._trace_dir = None

    @staticmethod
    def span(name: str):
        return jax.profiler.TraceAnnotation(name)

    def open_window(self) -> None:
        if self.trace:
            self._trace_dir = os.path.join(self.scratch, "trace")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # the spans are enough; every Python call is not
            jax.profiler.start_trace(self._trace_dir, profiler_options=opts)
        self._span = self.span(tracing.WINDOW_SPAN)
        self._span.__enter__()
        self._compiles0 = _compiles[0]
        self._t0 = time.monotonic()
        self.setup_s = self._t0 - self.t_start

    def in_window(self) -> bool:
        return time.monotonic() - self._t0 < self.seconds

    def close_window(self) -> float:
        self.window_s = time.monotonic() - self._t0
        self.compiles_in_window = _compiles[0] - self._compiles0
        self._span.__exit__(None, None, None)
        if self.trace:
            jax.profiler.stop_trace()
        return self.window_s

    def reduce_trace(self) -> None:
        if self._trace_dir:
            self.trace_summary = tracing.reduce_dir(self._trace_dir, SPANS)
            shutil.rmtree(self._trace_dir, ignore_errors=True)

    def close(self) -> None:
        shutil.rmtree(self.scratch, ignore_errors=True)


def run_cell(cell: dict, seed: int, seconds: float, trace: bool, t_start: float,
             device: dict, peak: dict | None, fault=None) -> dict:
    """Run one cell once and return the result line as a dict. ``fault``
    (from :mod:`bench.faults`) plants a fault under the timed path."""
    run = Run(cell, seed, seconds, trace, t_start, fault)
    try:
        with (fault.patch() if fault is not None else contextlib.nullcontext()):
            out = cell["traffic"].run(run)
        run.reduce_trace()
    finally:
        run.close()
    compared = out["compared"]
    correct = all(math.isfinite(v["value"]) and v["value"] <= v["limit"] for v in compared.values())
    counters = dict(out["counters"], window_s=run.window_s)
    if trace:
        reading = {"counters": counters, "trace": run.trace_summary, "peaks": peak}
        values = {m["name"]: cell["metric_readers"][m["name"]].read(reading) for m in cell["per_layer"]}
        metrics = {m["name"]: {"value": float(values[m["name"]]), "unit": m["unit"]}
                   for m in cell["per_layer"] if values[m["name"]] is not None}
    else:
        e2e = dict(out["end_to_end"], setup_s=run.setup_s)
        metrics = {m["name"]: {"value": float(e2e[m["name"]]), "unit": m["unit"]}
                   for m in cell["end_to_end"] if e2e.get(m["name"]) is not None}
    dev = dict(device, memory_peak_bytes=out["memory_peak_bytes"])
    result = {"correct": bool(correct), "attempted": out["attempted"], "failed": out["failed"],
              "metrics": metrics, "device": dev}
    if trace and run.trace_summary:
        dev.update(busy_s=run.trace_summary["busy_s"], window_s=run.trace_summary["window_s"])
        result["breakdown"] = {k: run.trace_summary[k] for k in ("device_ops", "idle_gaps")}
    result["info"] = dict(out.get("info", {}), setup_s=run.setup_s, window_s=run.window_s,
                          compiles_in_window=run.compiles_in_window)
    result["compared"] = compared
    return result


def chip(want: int) -> dict:
    """The device identity, or SystemExit when this is no TPU with ``want`` chips."""
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SystemExit(f"bench: no TPU here: JAX found platform {d.platform!r} "
                         f"({d.device_kind}); the benchmark runs only on a TPU")
    if len(devs) < want:
        raise SystemExit(f"bench: the cell needs {want} chips, JAX found {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind, "count": want}


def memory_peak_bytes() -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in jax.devices())


def main(argv=None, t_start: float | None = None) -> int:
    t_start = time.monotonic() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a whole number >= 0")
    cell = files.resolve(args.workload)
    device = chip(cell["entry"]["chips"])
    try:
        peak = peaks(device["kind"])
    except KeyError as e:
        raise SystemExit(f"bench: {e}")
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), t_start, device, peak)
    for k, v in result["info"].items():
        print(f"info {k}: {v}", file=sys.stderr)
    for k, v in result["compared"].items():
        print(f"check {k}: {v['value']!r} limit {v['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
