"""BVLSM checkpoint store: roundtrip, incremental reuse, retention,
corruption detection, elastic resharding, and commit-protocol crash
consistency."""
import importlib.util
import os
import time

import jax
import jax.numpy as jnp
import ml_dtypes
import msgpack
import numpy as np
import pytest

from repro.checkpoint.bvstore import CHUNK, BVCheckpointStore, _leaf_paths, content_hash, reuse_steps
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


def test_load_without_place_returns_host_arrays(tmp_path):
    """``load`` without ``place`` gives host ``np.ndarray`` leaves, bit-equal
    to the saved state, and places nothing."""
    store = BVCheckpointStore(str(tmp_path / "ck"))
    try:
        st = _state()
        store.save(10, st)
        out, _ = store.load(10, template=st)
        for got, want in zip(jax.tree.leaves(out), jax.tree.leaves(st)):
            assert type(got) is np.ndarray
            assert got.dtype == want.dtype and got.shape == want.shape
            assert got.tobytes() == np.asarray(want).tobytes()
        assert store.stats()["ckpt_place_bytes"] == 0
    finally:
        store.close()


def test_meshless_restore_places_each_leaf_as_it_is_read(tmp_path, monkeypatch):
    """A mesh-less ``Trainer`` restore puts each leaf on the device before
    the next leaf's chunks are read, and restores device arrays bit-equal to
    the saved state, with the template's dtypes and shapes."""
    from repro.configs import get_config
    from repro.training.trainer import Trainer, TrainerConfig

    cfg = get_config("llama3-8b").reduced(d_model=64, n_layers=1, vocab=512, vocab_pad_multiple=64)
    tcfg = TrainerConfig(global_batch=2, seq_len=16, ckpt_dir=str(tmp_path / "ck"))
    tr = Trainer(cfg, tcfg)
    tr._init_or_restore()
    want = jax.tree.map(np.asarray, tr.state)
    template = jax.eval_shape(lambda: tr.state)
    tr.ckpt.save_now(5, tr.state, {"pipeline": tr.pipeline.state_dict()})
    tr.close()

    tr = Trainer(cfg, tcfg)
    events = []  # ("get", leaf path) per chunk get, ("put", None) per device_put
    db_get, device_put = tr.store.db.get, jax.device_put

    def get(key, *a, **kw):
        if key.startswith(b"ckpt/"):
            events.append(("get", key.decode().split("/", 2)[2].rsplit("/", 1)[0]))
        return db_get(key, *a, **kw)

    def put(*a, **kw):
        events.append(("put", None))
        return device_put(*a, **kw)

    monkeypatch.setattr(tr.store.db, "get", get)
    monkeypatch.setattr(jax, "device_put", put)
    try:
        assert tr._init_or_restore() == 5
    finally:
        monkeypatch.undo()
        tr.close()
    gets = [leaf for kind, leaf in events if kind == "get"]
    assert len(set(gets)) == len(jax.tree.leaves(want)) > 1
    first_put = events.index(("put", None))
    assert first_put < events.index(("get", gets[-1]))
    for got, w, t in zip(jax.tree.leaves(tr.state), jax.tree.leaves(want), jax.tree.leaves(template)):
        assert isinstance(got, jax.Array)
        assert got.dtype == t.dtype and got.shape == t.shape
        assert np.asarray(got).tobytes() == w.tobytes()


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
    exact, each leaf is one shard of the whole array whose hash is
    ``content_hash`` of ``tobytes()`` (the format checkpoints written before
    had), and only a non-contiguous leaf takes a copy."""
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
            (shard,) = ent["shards"]
            assert shard["index"] == [[0, n] for n in np.shape(want)]
            assert shard["hash"] == content_hash(raw)
            assert shard["chunks"] == max(1, -(-len(raw) // CHUNK))
        total = sum(np.asarray(x).nbytes for x in jax.tree.leaves(st))
        copied = leaf.nbytes if case == "transposed" else 0
        stats = store.stats()
        assert stats["ckpt_copy_bytes"] == copied
        assert stats["ckpt_view_bytes"] == total - copied
    finally:
        store.close()


def _save_from_tobytes(store: BVCheckpointStore, step: int, state) -> dict:
    """A checkpoint as ``save`` wrote one when it serialized each leaf with
    ``tobytes()`` and put ``bytes`` slices of it, before leaves were saved
    in shards: one manifest entry per whole leaf with its ``chunks`` and
    ``hash``. Returns the hashes as a save chains them, one whole-leaf box
    per leaf."""
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
        hashes[path] = {tuple((0, d) for d in arr.shape): (h, step)}
    store._value_barrier()
    meta = {"step": step, "time": 0.0, "manifest": manifest, "extra": {}, "reused_tensors": 0}
    store.db.put(store._meta_key(step), msgpack.packb(meta, use_bin_type=True))
    store.db.flush()
    return hashes


def test_incremental_reuse_over_tobytes_checkpoint(tmp_path):
    """Hashes of a checkpoint written from ``tobytes`` buffers still match
    the copy-free save's, so its unchanged leaves are reused: a whole-leaf
    shard keeps the chunk keys of a leaf written before shards existed."""
    st = {"a": _leaf("float32"), "b": _leaf("bfloat16"), "t": _leaf("transposed"),
          "step": np.int32(1)}
    store = BVCheckpointStore(str(tmp_path / "ck"))
    try:
        old = _save_from_tobytes(store, 1, st)
        st2 = {**st, "step": np.int32(2)}
        store.save(2, st2, prev_hashes=old)
        meta2 = store.load_meta(2)
        reused = {e["path"] for e in meta2["manifest"] if reuse_steps(e) == {1}}
        assert reused == {"['a']", "['b']", "['t']"}
        written = {e["path"]: e for e in store.load_meta(1)["manifest"]}
        for ent in meta2["manifest"]:
            if ent["path"] in reused:  # the shard as the old save wrote the leaf
                (shard,) = ent["shards"]
                old = written[ent["path"]]
                assert ent["shape"] == old["shape"] and ent["dtype"] == old["dtype"]
                assert (shard["hash"], shard["chunks"]) == (old["hash"], old["chunks"])
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
        reused = [e for e in meta2["manifest"] if reuse_steps(e) == {1}]
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


def test_save_now_counts_a_wait_once(tmp_path):
    """``stall_seconds`` holds the wait on a running save once, in ``wait()``,
    plus each save's own snapshot; ``save_now`` does not count it again."""
    store = BVCheckpointStore(str(tmp_path / "ck"))
    save, hold = store.save, 0.5

    def slow_save(*a, **kw):
        time.sleep(hold)
        return save(*a, **kw)

    store.save = slow_save
    mgr = CheckpointManager(store, interval_steps=1, async_save=True, incremental=False)
    try:
        state = {"w": np.arange(1024, dtype=np.float32)}
        mgr.save_now(1, state)
        mgr.save_now(2, state)  # waits on step 1's save for at least ``hold``
        stall, spans = mgr.stall_seconds, store.stats()["spans"]
        mgr.wait()
        assert store.steps() == [1, 2]
        waited, snapshots = spans["ckpt.wait"]["seconds"], spans["ckpt.snapshot"]["seconds"]
        assert spans["ckpt.wait"]["count"] == 1 and waited >= hold * 0.8
        # counted twice, the stall would hold a second ``waited``
        assert stall == pytest.approx(waited + snapshots, abs=hold * 0.2), (stall, waited)
    finally:
        mgr.close()
        store.close()


# ---------------------------------------------------------------------------
# sharded saves and resharding restores, on four host devices
# ---------------------------------------------------------------------------

def _legacy_load(store: BVCheckpointStore, step: int) -> dict:
    """The plain reference of a restore: each leaf of a checkpoint written
    by ``_save_from_tobytes``, its chunks joined whole and read as one array,
    as ``load`` read every leaf before leaves were saved in shards."""
    out = {}
    for ent in store.load_meta(step)["manifest"]:
        parts = [store.db.get(store._chunk_key(step, ent["path"], ci)) for ci in range(ent["chunks"])]
        out[ent["path"]] = np.frombuffer(b"".join(parts), ent["dtype"]).reshape(ent["shape"])
    return out


def _same(got: dict, want: dict) -> list[str]:
    """Leaves of ``want`` whose bytes, dtype or shape ``got`` does not match."""
    return [p for p, w in want.items()
            if not (np.asarray(got[p]).dtype == w.dtype and np.asarray(got[p]).shape == w.shape
                    and np.asarray(got[p]).tobytes() == w.tobytes())]


def _elastic_cases(root: str) -> dict:
    """Run in a process with four host devices: every case's outcome, a
    list of what went wrong (empty when the case holds)."""
    from repro.configs import get_config
    from repro.dist import Axes, tree_shardings
    from repro.launch.mesh import make_host_mesh
    from repro.models import build_model
    from repro.training.optimizer import OptimizerConfig
    from repro.training.train_step import init_state, state_axes

    assert len(jax.devices()) == 4
    cfg = get_config("phi3-medium-14b").reduced(n_layers=2, vocab=256, vocab_pad_multiple=64)
    model = build_model(cfg)
    opt = OptimizerConfig()
    tmpl = jax.eval_shape(lambda k: init_state(model, k, opt), jax.random.key(0))
    axes = state_axes(model, opt, tmpl)
    meshes = {"2x2": make_host_mesh((2, 2)), "1x4": make_host_mesh((1, 4))}
    shardings = {k: tree_shardings(m, tmpl, axes) for k, m in meshes.items()}

    def make(seed: int, mesh: str):
        """Seeded random values in every leaf, built sharded on ``mesh``."""
        def build(key):
            flat, treedef = jax.tree_util.tree_flatten(tmpl)
            leaves = [jax.random.normal(jax.random.fold_in(key, i), s.shape, s.dtype)
                      if jnp.issubdtype(s.dtype, jnp.floating) else jnp.full(s.shape, 7 + i, s.dtype)
                      for i, s in enumerate(flat)]
            return jax.tree_util.tree_unflatten(treedef, leaves)
        return jax.jit(build, out_shardings=shardings[mesh])(jax.random.key(seed))

    def paths(tree) -> dict:
        return {p: np.asarray(x) for p, x in _leaf_paths(tree)}

    def reference(state) -> dict:
        ref = BVCheckpointStore(os.path.join(root, f"ref{len(os.listdir(root))}"))
        try:
            _save_from_tobytes(ref, 1, jax.device_get(state))
            return _legacy_load(ref, 1)
        finally:
            ref.close()

    def restored(directory: str, mesh: str | None):
        """A fresh store's restore and its read counter."""
        store = BVCheckpointStore(directory)
        try:
            if mesh is None:
                out, _ = store.load(template=tmpl)
            else:
                out, _ = store.load_distributed(meshes[mesh], tmpl, axes)
                for p, x in _leaf_paths(out):
                    want = dict(_leaf_paths(shardings[mesh]))[p]
                    assert x.sharding.is_equivalent_to(want, x.ndim), p
            return out, store.stats()["ckpt_read_bytes"]
        finally:
            store.close()

    results = {}

    def saved(name: str, state, mesh: str) -> str:
        directory = os.path.join(root, name)
        store = BVCheckpointStore(directory)
        CheckpointManager(store, async_save=False).save_now(1, state)
        store.close()
        return directory

    for src, dst in [("2x2", "1x4"), ("2x2", "2x2"), ("2x2", None), ("1x4", "2x2")]:
        state = make(1, src)
        want = reference(state)
        nbytes = sum(w.nbytes for w in want.values())
        out, read = restored(saved(f"{src}-{dst}", state, src), dst)
        bad = _same(paths(out), want)
        if read != nbytes:
            bad.append(f"read {read} bytes of {nbytes}")
        results[f"{src}->{dst or 'meshless'}"] = bad

    # shards of several chunks, whose chunk ends cut rows one and two
    # dimensions down, onto targets that cut them the other way
    big = {"a": jax.ShapeDtypeStruct((2000, 3000), jnp.float32),
           "b": jax.ShapeDtypeStruct((6, 1400, 1200), jnp.bfloat16)}
    big_axes = {"a": Axes("param_embed", "mlp"), "b": Axes("layers", "mlp", "param_embed")}
    a = jax.jit(lambda k: {n: jax.random.normal(jax.random.fold_in(k, i), s.shape, s.dtype)
                           for i, (n, s) in enumerate(big.items())},
                out_shardings=tree_shardings(meshes["2x2"], big, big_axes))(jax.random.key(9))
    want = reference(a)
    directory = saved("multi-chunk", a, "2x2")
    store = BVCheckpointStore(directory)
    try:
        manifest = store.load_meta(1)["manifest"]
        multi = sum(sh["chunks"] > 1 for e in manifest for sh in e["shards"])
        for dst in ("1x4", None):
            out = (store.load(template=big)[0] if dst is None
                   else store.load_distributed(meshes[dst], big, big_axes)[0])
            bad = _same(paths(out), want)
            if multi != 8:
                bad.append(f"{multi} of 8 shards of more than one chunk")
            results[f"multi-chunk->{dst or 'meshless'}"] = bad
    finally:
        store.close()

    # a checkpoint written before leaves were saved in shards
    state = make(2, "2x2")
    want = paths(state)
    old = os.path.join(root, "legacy")
    store = BVCheckpointStore(old)
    _save_from_tobytes(store, 1, jax.device_get(state))
    store.close()
    for dst in ("1x4", None):
        out, _ = restored(old, dst)
        results[f"legacy->{dst or 'meshless'}"] = _same(paths(out), want)

    # a replicated leaf is written once; the skipped replicas are counted
    state = make(3, "2x2")
    store = BVCheckpointStore(os.path.join(root, "replicated"))
    CheckpointManager(store, async_save=False).save_now(1, state)
    manifest = {e["path"]: e for e in store.load_meta(1)["manifest"]}
    skipped = store.stats()["ckpt_replica_bytes_skipped"]
    store.close()
    bad, expect = [], 0
    for p, x in _leaf_paths(state):
        boxes = {tuple((s.start or 0, s.stop or n) for s, n in zip(sh.index, x.shape))
                 for sh in x.addressable_shards}
        got = [tuple(map(tuple, sh["index"])) for sh in manifest[p]["shards"]]
        if sorted(got) != sorted(boxes):
            bad.append(f"{p}: shards {got}, devices hold {sorted(boxes)}")
        expect += x.nbytes // len(boxes) * (len(x.addressable_shards) - len(boxes))
    if skipped != expect or expect == 0:
        bad.append(f"replica bytes skipped {skipped}, expected {expect}")
    results["replicated-once"] = bad

    # incremental reuse per shard: one shard of one leaf changed
    state = make(4, "2x2")
    store = BVCheckpointStore(os.path.join(root, "incremental"))
    mgr = CheckpointManager(store, interval_steps=1, keep_last=2, async_save=False)
    mgr.save_now(1, state)
    leaf = "['params']['embed']"
    changed = jax.tree_util.tree_map_with_path(
        lambda kp, x: x.at[0, 0].add(1.0) if jax.tree_util.keystr(kp) == leaf else x, state)
    mgr.save_now(2, changed)
    bad = []
    for ent in store.load_meta(2)["manifest"]:
        for sh in ent["shards"]:
            fresh = ent["path"] == leaf and all(a == 0 for a, _ in sh["index"])
            if ("reuse_step" in sh) == fresh:
                bad.append(f"{ent['path']} {sh['index']}: reuse_step {sh.get('reuse_step')}")
    store.close()
    out, _ = restored(os.path.join(root, "incremental"), "1x4")
    results["incremental-per-shard"] = bad + _same(paths(out), paths(changed))

    # a crash between the shard puts and META: the previous checkpoint is the newest
    state1, state2 = make(5, "2x2"), make(6, "2x2")
    directory = os.path.join(root, "crash")
    store = BVCheckpointStore(directory)
    CheckpointManager(store, async_save=False).save_now(1, state1)
    put = store.db.put

    def put_no_meta(key, value):
        if key.startswith(b"meta/"):
            raise RuntimeError("crash before META")
        return put(key, value)

    store.db.put = put_no_meta
    try:
        CheckpointManager(store, async_save=False).save_now(2, state2)
    except RuntimeError:
        pass
    chunks2 = sum(1 for k, _ in store.db.range(b"ckpt/000000000002/", end=b"ckpt/000000000003"))
    store.db.close(crash=True)
    store = BVCheckpointStore(directory)
    newest = store.latest_step()
    store.close()
    out, _ = restored(directory, "1x4")
    bad = _same(paths(out), paths(state1))
    if newest != 1 or chunks2 == 0:
        bad.append(f"newest step {newest}; {chunks2} chunks of step 2 put")
    results["crash-before-meta"] = bad
    return results


ELASTIC_CASES = ["2x2->1x4", "2x2->2x2", "2x2->meshless", "1x4->2x2", "multi-chunk->1x4",
                 "multi-chunk->meshless", "legacy->1x4",
                 "legacy->meshless", "replicated-once", "incremental-per-shard",
                 "crash-before-meta"]


@pytest.fixture(scope="module")
def elastic(tmp_path_factory):
    """``_elastic_cases`` in a child process that has four host devices."""
    import json
    import subprocess
    import sys

    here = os.path.dirname(os.path.abspath(__file__))
    src = os.path.join(os.path.dirname(here), "src")
    root = str(tmp_path_factory.mktemp("elastic"))
    env = dict(os.environ, PYTHONPATH=os.pathsep.join([src, here]), JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = ("import json, test_checkpoint as t\n"
            f"print(json.dumps(t._elastic_cases({root!r})))\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True, env=env,
                         cwd=root, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("case", ELASTIC_CASES)
def test_sharded_save_and_resharding_restore(elastic, case):
    """Saved per shard on one layout, restored on another (or with no mesh):
    bit for bit the whole arrays the plain reference reads, on the target's
    shardings, each chunk read once; a pre-PR manifest loads; a replicated
    leaf is written once; reuse is per shard; a crash before META leaves the
    previous checkpoint the newest."""
    assert elastic[case] == [], elastic[case]
