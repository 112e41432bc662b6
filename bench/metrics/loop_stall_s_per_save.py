"""Seconds the train loop was blocked per checkpoint save issued in the
window: ``CheckpointManager.stall_seconds`` (the snapshot, and any wait on
the save before it) over the saves."""


def read(run):
    c = run["counters"]
    return c["stall_s"] / c["saves"] if c.get("saves") else None
