"""Production mesh definitions.

A function (never a module-level constant) so importing this module does not
touch jax device state. Single pod = 256 chips as (data=16, model=16);
multi-pod = 2 pods × 256 as (pod=2, data=16, model=16) with the ``pod``
axis crossing DCN.
"""
from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(shape=None, axes=("data", "model")):
    """Mesh over whatever devices exist (tests / smoke runs)."""
    n = len(jax.devices())
    if shape is None:
        shape = (1, n) if n > 1 else (1, 1)
    return _make_mesh(shape, axes)


# TPU v5e-ish hardware model used by the roofline analysis (given constants).
HW = {
    "peak_flops_bf16": 197e12,  # per chip
    "hbm_bw": 819e9,  # bytes/s per chip
    "ici_bw": 50e9,  # bytes/s per link
    "dcn_bw": 6.25e9,  # bytes/s per chip across pods (assumption, see DESIGN)
    "hbm_bytes": 16 * 2**30,
}
