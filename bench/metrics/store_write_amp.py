"""Bytes the store wrote (WAL, flushes, compaction, BValue files) per byte
of user payload, from the engine's ``EngineStats`` over the window and its
save."""


def read(run):
    c = run["counters"]
    return c["device_bytes"] / c["user_bytes"] if c.get("user_bytes") else None
