"""Reduction of a profiler trace of the measured window to device metrics.

Busy time is the union of the intervals in which an operation ran on a
device (the ``XLA Ops`` line of each ``/device:`` plane), clipped to the
window; it is averaged over the devices. Each idle gap is split among the
benchmark's host spans (``jax.profiler.TraceAnnotation``) that overlap it,
so that the device's idle time is attributed to what the host was doing.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict

WINDOW_SPAN = "window"
OPS_LINE = "XLA Ops"
NO_SPAN = "(no host span)"


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(intervals, lo: int, hi: int):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def op_name(event_name: str) -> str:
    """``%fusion.12 = bf16[..] fusion(..)`` → ``%fusion.12``."""
    return event_name.split(" = ", 1)[0][:120]


def reduce_profile(profile, span_names: set[str], top: int = 10) -> dict | None:
    """``profile``: a ``jax.profiler.ProfileData``. Returns None when the
    trace holds no window span or no device plane with an ``XLA Ops`` line:
    the other lines of a device plane (modules, steps) span whole programs
    and are no measure of busy time."""
    host_spans, window = [], None
    devices = []  # each device's ops, from its "XLA Ops" line and no other
    for plane in profile.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            ops = [ev for line in plane.lines if line.name == OPS_LINE for ev in line.events]
            if ops:
                devices.append(ops)
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == WINDOW_SPAN:
                    window = (int(ev.start_ns), int(ev.end_ns))
                elif ev.name in span_names:
                    host_spans.append((ev.name, int(ev.start_ns), int(ev.end_ns)))
    if window is None or not devices:
        return None
    lo, hi = window
    op_time: dict[str, float] = defaultdict(float)
    busy_total, gaps_by_span = 0.0, defaultdict(float)
    for evs in devices:
        for ev in evs:
            s, e = max(int(ev.start_ns), lo), min(int(ev.end_ns), hi)
            if e > s:
                op_time[op_name(ev.name)] += (e - s) / 1e9
        busy = _clip(_union([(int(ev.start_ns), int(ev.end_ns)) for ev in evs]), lo, hi)
        busy_total += sum(e - s for s, e in busy) / 1e9
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 <= g0:
                continue
            covered = 0
            for name, s, e in host_spans:
                o = min(e, g1) - max(s, g0)
                if o > 0:
                    gaps_by_span[name] += o / 1e9 / len(devices)
                    covered += o
            rest = (g1 - g0) - covered
            if rest > 0:
                gaps_by_span[NO_SPAN] += rest / 1e9 / len(devices)
    rank = lambda d: sorted(([k, v] for k, v in d.items()), key=lambda kv: -kv[1])[:top]
    return {
        "busy_s": busy_total / len(devices),
        "window_s": (hi - lo) / 1e9,
        "devices": len(devices),
        "device_ops": rank({k: v / len(devices) for k, v in op_time.items()}),
        "idle_gaps": rank(gaps_by_span),
    }


def reduce_dir(trace_dir: str, span_names: set[str]) -> dict | None:
    """Reduce the newest ``.xplane.pb`` that ``jax.profiler`` wrote under ``trace_dir``."""
    from jax.profiler import ProfileData

    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not found:
        return None
    return reduce_profile(ProfileData.from_file(max(found, key=os.path.getmtime)), span_names)


def idle_share(summary: dict | None) -> float | None:
    """Percent of the window with no operation on the device."""
    if not summary or summary["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - summary["busy_s"] / summary["window_s"])
