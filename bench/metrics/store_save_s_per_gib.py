"""Seconds the save thread spent in ``BVCheckpointStore.save`` per GiB of
state, over the saves issued in the window (``CheckpointManager.save_seconds``;
the snapshot to the host is not in it)."""


def read(run):
    c = run["counters"]
    return sum(c["save_s"]) / c["gib_saved"] if c.get("gib_saved") else None
