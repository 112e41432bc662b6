"""Share of the window in which no operation ran on the chip, from the
profiler trace of the window (``bench.tracing``). Read alike for each of
its splits: ``device_idle_share.train`` moves the training cells'
throughput, ``device_idle_share.resume`` the restore rate."""
from bench.tracing import idle_share


def read(run):
    return idle_share(run["trace"])
