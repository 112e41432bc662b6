"""Published peaks of one chip, keyed by JAX's ``device_kind``.

A device that is not in ``peaks.json`` is an error, never a default: a
share of a peak taken against the wrong chip's peak is no measurement.
"""
from __future__ import annotations

import json
from pathlib import Path

TABLE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str, table: Path = TABLE) -> dict:
    known = json.loads(table.read_text())
    if device_kind not in known:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r} in {table.name}; "
            f"known: {sorted(known)}"
        )
    return known[device_kind]
