"""On-chip benchmark of BVLSM's device consumers.

One run of one cell: ``python3 bench/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>``. Everything that belongs to one cell, one
configuration, one reference or one per-layer metric is a file of its own
that :mod:`bench.files` finds by name.
"""
