"""Seconds per GiB restored that placement took: the program's ``ckpt.place``
spans (host buffers handed to their devices) and the traffic's own timing of
``device_wait`` (``block_until_ready`` on the restored state: the transfers
still running). None where the program has no ``ckpt.place``."""


def read(run):
    c = run["counters"]
    spans = c.get("span_s") or {}
    if "ckpt.place" not in spans or not c.get("gib_restored"):
        return None
    return (spans["ckpt.place"] + c["device_wait_s"]) / c["gib_restored"]
