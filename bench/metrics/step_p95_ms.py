"""95th percentile of the loop's iterations in the window, each timed on
the host from the batch's dispatch to its loss on the host, ``maybe_save``
included: the I/O jitter a trainer feels while a save runs beside it."""


def read(run):
    return run["counters"].get("step_p95_ms")
