"""Seconds per GiB saved of the set-up's sharded save on the save thread
(``CheckpointManager.save_seconds``: ``BVCheckpointStore.save``, the
device-to-host snapshot not in it)."""


def read(run):
    c = run["counters"]
    return sum(c["save_s"]) / c["gib_saved"] if c.get("gib_saved") else None
