"""The trace reduction against a small trace of known numbers."""
from pathlib import Path

import pytest
from jax.profiler import ProfileData

from bench import harness, tracing

FIXTURE = Path(__file__).with_name("trace_window.txtpb")


def _profile():
    return ProfileData.from_text_proto(FIXTURE.read_text())


def test_busy_idle_and_breakdown_by_hand():
    out = tracing.reduce_profile(_profile(), harness.SPANS)
    # window [80, 3000] ns; ops on "XLA Ops" clipped to it: [100,450]
    # (two overlapping), [1000,1400], [2500,2700]; the op at [0,50] and
    # the other lines are not counted
    assert out["window_s"] == pytest.approx(2920e-9)
    assert out["busy_s"] == pytest.approx(950e-9)
    assert out["devices"] == 1
    assert out["device_ops"] == [["%fusion.1", pytest.approx(500e-9)],
                                 ["%fusion.2", pytest.approx(400e-9)],
                                 ["%copy-start", pytest.approx(100e-9)]]
    # idle gaps [80,100] [450,1000] [1400,2500] [2700,3000] split over the
    # host spans that overlap them
    gaps = dict(out["idle_gaps"])
    assert gaps == {"maybe_save": pytest.approx(1100e-9), "train_step": pytest.approx(560e-9),
                    tracing.NO_SPAN: pytest.approx(210e-9), "digest": pytest.approx(100e-9)}
    assert sum(gaps.values()) == pytest.approx(out["window_s"] - out["busy_s"])
    assert tracing.idle_share(out) == pytest.approx(100 * 1970 / 2920)


def test_nothing_to_read_gives_none():
    assert tracing.idle_share(None) is None
    no_window = ProfileData.from_text_proto(FIXTURE.read_text().replace('name: "window"', 'name: "w"'))
    assert tracing.reduce_profile(no_window, harness.SPANS) is None
    # a device plane with no "XLA Ops" line: its other lines span whole
    # programs, so busy time is not read from them
    no_ops = ProfileData.from_text_proto(FIXTURE.read_text().replace('name: "XLA Ops"', 'name: "X"'))
    assert tracing.reduce_profile(no_ops, harness.SPANS) is None
    assert tracing.reduce_dir("/nonexistent-trace-dir", harness.SPANS) is None
