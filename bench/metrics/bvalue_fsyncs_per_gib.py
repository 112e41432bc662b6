"""fsyncs of the BValue files per GiB of state saved in the window, from
the engine's ``EngineStats``."""


def read(run):
    c = run["counters"]
    return c["bvalue_fsyncs"] / c["gib_saved"] if c.get("gib_saved") else None
