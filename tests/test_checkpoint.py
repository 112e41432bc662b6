"""BVLSM checkpoint store: roundtrip, incremental reuse, retention,
corruption detection, elastic resharding, and commit-protocol crash
consistency."""
import importlib.util
import os

import jax
import jax.numpy as jnp
import ml_dtypes
import msgpack
import numpy as np
import pytest

from repro.checkpoint.bvstore import CHUNK, BVCheckpointStore, _leaf_paths, content_hash
from repro.checkpoint.manager import CheckpointManager


def _state(seed=0, scale=1.0):
    k = jax.random.key(seed)
    return {
        "params": {
            "w1": jax.random.normal(k, (64, 128)) * scale,
            "emb": jax.random.normal(jax.random.fold_in(k, 1), (1000, 32)) * scale,
        },
        "opt": {"m": jnp.zeros((64, 128)), "count": jnp.zeros((), jnp.int32)},
        "step": jnp.asarray(7, jnp.int32),
    }


def test_save_load_roundtrip(tmp_path):
    store = BVCheckpointStore(str(tmp_path / "ck"))
    try:
        st = _state()
        store.save(10, st, {"pipeline": {"step": 10, "seed": 0}})
        out, meta = store.load(template=st)
        assert meta["step"] == 10
        assert meta["extra"]["pipeline"]["step"] == 10
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)), st, out)
    finally:
        store.close()


def _leaf(case: str) -> np.ndarray:
    """A leaf of more than one chunk, in each form a save has to take."""
    a = np.arange(CHUNK // 4 + 3000, dtype=np.float32)
    if case == "float32":
        return a.reshape(-1, 8)
    if case == "bfloat16":
        return (a / 7).astype(ml_dtypes.bfloat16).reshape(8, -1)
    return a.reshape(-1, 8).T  # a transposed view: not C-contiguous


@pytest.mark.parametrize("case", ["float32", "bfloat16", "transposed"])
def test_save_takes_leaf_bytes_exactly(tmp_path, case):
    """The save hashes and puts a view of each host array: the load is byte
    exact, each manifest hash is ``content_hash`` of ``tobytes()`` (the
    format checkpoints written before had), and only a non-contiguous leaf
    takes a copy."""
    leaf = _leaf(case)
    st = {"leaf": leaf, "w": np.arange(5000, dtype=np.float32), "step": np.int32(3)}
    store = BVCheckpointStore(str(tmp_path / "ck"))
    try:
        store.save(1, st)
        out, meta = store.load(1, template=st)
        for path, want in _leaf_paths(st):
            want = np.asarray(want)
            got = dict(_leaf_paths(out))[path]
            assert got.dtype == want.dtype and got.shape == want.shape, path
            assert got.tobytes() == want.tobytes(), path
        for ent, (path, want) in zip(meta["manifest"], _leaf_paths(st)):
            raw = np.asarray(want).tobytes()
            assert ent["path"] == path
            assert ent["hash"] == content_hash(raw)
            assert ent["chunks"] == max(1, -(-len(raw) // CHUNK))
        total = sum(np.asarray(x).nbytes for x in jax.tree.leaves(st))
        copied = leaf.nbytes if case == "transposed" else 0
        stats = store.stats()
        assert stats["ckpt_copy_bytes"] == copied
        assert stats["ckpt_view_bytes"] == total - copied
    finally:
        store.close()


def _save_from_tobytes(store: BVCheckpointStore, step: int, state) -> dict:
    """A checkpoint as ``save`` wrote one when it serialized each leaf with
    ``tobytes()`` and put ``bytes`` slices of it."""
    manifest, hashes = [], {}
    for path, leaf in _leaf_paths(state):
        arr = np.asarray(leaf)
        buf = arr.tobytes()
        h = content_hash(buf)
        n = max(1, -(-len(buf) // CHUNK))
        manifest.append({"path": path, "shape": list(arr.shape), "dtype": str(arr.dtype),
                         "chunks": n, "hash": h})
        for ci in range(n):
            store.db.put(store._chunk_key(step, path, ci), buf[ci * CHUNK : (ci + 1) * CHUNK])
        hashes[path] = (h, step)
    store._value_barrier()
    meta = {"step": step, "time": 0.0, "manifest": manifest, "extra": {}, "reused_tensors": 0}
    store.db.put(store._meta_key(step), msgpack.packb(meta, use_bin_type=True))
    store.db.flush()
    return hashes


def test_incremental_reuse_over_tobytes_checkpoint(tmp_path):
    """Hashes of a checkpoint written from ``tobytes`` buffers still match
    the copy-free save's, so its unchanged leaves are reused."""
    st = {"a": _leaf("float32"), "b": _leaf("bfloat16"), "t": _leaf("transposed"),
          "step": np.int32(1)}
    store = BVCheckpointStore(str(tmp_path / "ck"))
    try:
        old = _save_from_tobytes(store, 1, st)
        st2 = {**st, "step": np.int32(2)}
        store.save(2, st2, prev_hashes=old)
        meta2 = store.load_meta(2)
        reused = {e["path"] for e in meta2["manifest"] if e.get("reuse_step") == 1}
        assert reused == {"['a']", "['b']", "['t']"}
        written = {e["path"]: e for e in store.load_meta(1)["manifest"]}
        for ent in meta2["manifest"]:
            if ent["path"] in reused:  # the manifest entry as the old save wrote it
                assert {k: v for k, v in ent.items() if k != "reuse_step"} == written[ent["path"]]
        out, _ = store.load(2, template=st2)
        for path, want in _leaf_paths(st2):
            assert dict(_leaf_paths(out))[path].tobytes() == np.asarray(want).tobytes()
    finally:
        store.close()


def test_latest_and_multiple_steps(tmp_path):
    store = BVCheckpointStore(str(tmp_path / "ck"))
    try:
        for s in (5, 10, 15):
            store.save(s, _state(s))
        assert store.steps() == [5, 10, 15]
        assert store.latest_step() == 15
        out, meta = store.load(10, template=_state())
        assert meta["step"] == 10
    finally:
        store.close()


def test_incremental_reuse(tmp_path):
    store = BVCheckpointStore(str(tmp_path / "ck"))
    try:
        st = _state()
        h1 = store.save(1, st)
        st2 = {**st, "step": jnp.asarray(8, jnp.int32)}  # params unchanged
        store.save(2, st2, prev_hashes=h1)
        meta2 = store.load_meta(2)
        reused = [e for e in meta2["manifest"] if "reuse_step" in e]
        assert len(reused) >= 2  # the unchanged big tensors
        out, _ = store.load(2, template=st2)
        np.testing.assert_array_equal(np.asarray(out["params"]["w1"]), np.asarray(st["params"]["w1"]))
        assert int(out["step"]) == 8
    finally:
        store.close()


def test_corruption_detected_on_read(tmp_path):
    store = BVCheckpointStore(str(tmp_path / "ck"))
    st = _state()
    store.save(1, st)
    store.close()
    # flip a byte in a BValue file
    bdir = os.path.join(str(tmp_path / "ck"), "bvalue")
    target = sorted(
        (os.path.join(bdir, f) for f in os.listdir(bdir)),
        key=os.path.getsize,
    )[-1]
    with open(target, "r+b") as f:
        f.seek(100)
        b = f.read(1)
        f.seek(100)
        f.write(bytes([b[0] ^ 0xFF]))
    # reopen (cold BVCache) with CRC verification on
    store2 = BVCheckpointStore(str(tmp_path / "ck"))
    store2.db.cfg.paranoid_checks = True
    try:
        with pytest.raises(IOError):
            store2.load(1, template=st)
    finally:
        store2.close()


def test_retention_keeps_referenced_chunks(tmp_path):
    store = BVCheckpointStore(str(tmp_path / "ck"))
    mgr = CheckpointManager(store, interval_steps=1, keep_last=2, async_save=False, incremental=True)
    try:
        st = _state()
        for s in range(1, 6):
            st = {**st, "step": jnp.asarray(s, jnp.int32)}
            mgr.save_now(s, st)
        steps = store.steps()
        assert steps[-2:] == [4, 5]
        out, _ = store.load(5, template=st)  # chunks may live in step 1 (reused)
        assert int(out["step"]) == 5
    finally:
        mgr.close()
        store.close()


@pytest.mark.skipif(
    importlib.util.find_spec("repro.dist") is None,
    reason="repro.dist missing from the seed",
)
def test_elastic_reshard_roundtrip(tmp_path):
    """Save on the 'old mesh' (host), restore sharded onto a 1-device mesh."""
    from repro.dist import Axes
    from repro.launch.mesh import make_host_mesh

    store = BVCheckpointStore(str(tmp_path / "ck"))
    try:
        st = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}
        axes = {"w": Axes("param_embed", "mlp")}
        store.save(3, st)
        mesh = make_host_mesh((1, 1))
        out, meta = store.load_distributed(mesh, st, axes)
        np.testing.assert_array_equal(np.asarray(out["w"]), np.asarray(st["w"]))
        assert out["w"].sharding.mesh.shape == dict(mesh.shape)
    finally:
        store.close()


def test_commit_protocol_crash_before_meta(tmp_path):
    """Chunks written but META not committed → checkpoint invisible, store
    healthy (the WAL-time separation commit point)."""
    path = str(tmp_path / "ck")
    store = BVCheckpointStore(path)
    st = _state()
    store.save(1, st)
    # simulate crash mid-save of step 2: write chunks only, no META, crash
    leaf = np.asarray(st["params"]["w1"])
    store.db.put(store._chunk_key(2, "['params']['w1']", 0), leaf.tobytes())
    store.db.close(crash=True)

    store2 = BVCheckpointStore(path)
    try:
        assert store2.latest_step() == 1  # step-2 orphan chunks are invisible
        out, _ = store2.load(template=st)
        np.testing.assert_array_equal(np.asarray(out["params"]["w1"]), leaf)
    finally:
        store2.close()


def test_async_manager_overlap(tmp_path):
    store = BVCheckpointStore(str(tmp_path / "ck"))
    mgr = CheckpointManager(store, interval_steps=1, keep_last=3, async_save=True)
    try:
        st = _state()
        mgr.save_now(1, st)
        mgr.save_now(2, st)  # waits for 1, then async 2
        mgr.wait()
        assert store.latest_step() == 2
        assert mgr.save_count == 2
    finally:
        mgr.close()
        store.close()


def test_retention_uses_range_tombstones(tmp_path):
    store = BVCheckpointStore(str(tmp_path / "store"), num_queues=2)
    state = {"w": np.arange(4096, dtype=np.float32)}
    for step in (1, 2, 3):
        store.save(step, state)
    assert store.steps() == [1, 2, 3]
    store.delete_step(1)
    assert store.steps() == [2, 3]
    # chunks of the deleted step are unreadable, survivors untouched
    assert store.db.get(store._chunk_key(1, "['w']", 0)) is None
    loaded, _ = store.load(3)
    np.testing.assert_array_equal(loaded["['w']"], state["w"])
    with pytest.raises(KeyError):
        store.delete_step(99)
    store.close()


def test_online_backup_opens_as_store(tmp_path):
    store = BVCheckpointStore(str(tmp_path / "store"), num_queues=2)
    state = {"w": np.arange(8192, dtype=np.float32),
             "b": np.ones(16, dtype=np.float32)}
    store.save(10, state)
    bdir = store.backup(str(tmp_path / "bak"))
    # mutate the source AFTER the backup: the image must not move
    store.save(20, {"w": state["w"] * 2, "b": state["b"]})
    store.delete_step(10)
    bak = BVCheckpointStore(bdir, num_queues=2)
    assert bak.latest_step() == 10
    loaded, meta = bak.load(10)
    np.testing.assert_array_equal(loaded["['w']"], state["w"])
    bak.close()
    assert store.steps() == [20]
    store.close()


def test_manager_backup_waits_for_inflight_save(tmp_path):
    store = BVCheckpointStore(str(tmp_path / "store"), num_queues=2)
    mgr = CheckpointManager(store, interval_steps=1, async_save=True)
    state = {"w": np.arange(4096, dtype=np.float32)}
    mgr.maybe_save(1, state)  # async: may still be in flight
    bdir = mgr.backup(str(tmp_path / "bak"))
    bak = BVCheckpointStore(bdir, num_queues=2)
    assert bak.latest_step() == 1  # the in-flight save is IN the image
    bak.close()
    mgr.close()
    store.close()
