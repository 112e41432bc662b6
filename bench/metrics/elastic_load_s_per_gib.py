"""Seconds per GiB restored that the elastic restores spent getting chunks
from the store (``ckpt.read``) and copying each chunk's overlap into the
target devices' host buffers (``ckpt.scatter``), from the program's spans.
None where the program has no ``ckpt.scatter``."""


def read(run):
    c = run["counters"]
    spans = c.get("span_s") or {}
    if "ckpt.scatter" not in spans or not c.get("gib_restored"):
        return None
    return (spans.get("ckpt.read", 0.0) + spans["ckpt.scatter"]) / c["gib_restored"]
