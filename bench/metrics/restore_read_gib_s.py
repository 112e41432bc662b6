"""GiB per second of the store-to-host part of each restore: the time
inside ``BVCheckpointStore.load``, which reads every chunk and builds the
host arrays, before they are placed on the chip."""


def read(run):
    c = run["counters"]
    return c["gib_restored"] / c["load_s"] if c.get("load_s") else None
