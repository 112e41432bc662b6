"""Chunk bytes the elastic restores read from the store (the program's
``ckpt_read_bytes``) over the state bytes they restored: 1.0 where every
chunk is read once. None where the program does not count them."""


def read(run):
    c = run["counters"]
    if c.get("ckpt_read_bytes") is None or not c.get("restores"):
        return None
    return c["ckpt_read_bytes"] / (c["restores"] * c["state_bytes"])
