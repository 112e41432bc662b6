"""The elastic restore cell's control: a restore that puts one leaf's shards
on the wrong devices.

``SWAPPED_SHARDS`` patches ``BVCheckpointStore.load_distributed`` so that
the restored head (``['params']['out_embed']``, sharded over the
vocabulary) has its shards rotated by one device: every byte is there, in
the wrong place. Both numbers of the cell's check should catch it: the
digest, which weighs each word by its position, and the restored mesh's
loss, whose vocabulary blocks now hold other rows.

    python3 bench/shard_fault.py --seeds 1,2 [--seconds 1]

runs the cell once per seed with the fault planted and prints the check's
numbers, one JSON line each, on whatever machine JAX finds (the cell needs
four devices).
"""
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path[0] = str(ROOT)
    sys.path.insert(1, str(ROOT / "src"))

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402

from bench.faults import Fault  # noqa: E402

CELL = "phi3-medium-2l.elastic_resume_4chip"
LEAF = "['params']['out_embed']"


def rotate_shards(x):
    """``x`` with each device's shard moved to the next device."""
    import jax

    shards = x.addressable_shards
    devices = [s.device for s in shards]
    moved = [jax.device_put(s.data, devices[(i + 1) % len(devices)]) for i, s in enumerate(shards)]
    return jax.make_array_from_single_device_arrays(x.shape, x.sharding, moved)


class _SwappedShards(Fault):
    name = "swapped_shards"

    @contextlib.contextmanager
    def patch(self):
        import jax

        from repro.checkpoint.bvstore import BVCheckpointStore

        real = BVCheckpointStore.load_distributed

        def patched(store, *a, **kw):
            state, meta = real(store, *a, **kw)
            flat, treedef = jax.tree_util.tree_flatten_with_path(state)
            leaves = [rotate_shards(x) if jax.tree_util.keystr(kp) == LEAF else x for kp, x in flat]
            return jax.tree_util.tree_unflatten(treedef, leaves), meta

        BVCheckpointStore.load_distributed = patched
        try:
            yield
        finally:
            BVCheckpointStore.load_distributed = real


SWAPPED_SHARDS = _SwappedShards()


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args()

    import jax

    from bench import files
    from bench.harness import run_cell
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    cell = files.resolve(CELL)
    d = jax.devices()[0]
    dev = {"platform": d.platform, "kind": d.device_kind, "count": len(jax.devices())}
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.monotonic()
        out = run_cell(cell, seed, args.seconds, False, t, dev, None, SWAPPED_SHARDS)
        print(json.dumps({"cell": CELL, "what": SWAPPED_SHARDS.name, "seed": seed,
                          "secs": time.monotonic() - t, "correct": out["correct"],
                          **{k: v["value"] for k, v in out["compared"].items()}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
