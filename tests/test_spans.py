"""Spans of ``EngineStats``: self time, threads, the profiler's host plane,
and the spans a checkpoint save and restore record, with their counts."""
import glob
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from repro.core import DB, DBConfig, ShardedDB
from repro.core.stats import SPAN_NAMES, EngineStats


def _nest_one_thread(stats, other):
    with stats.span("ckpt.save"):
        time.sleep(0.002)
        with other.span("ckpt.hash"):
            time.sleep(0.01)


def _nest_two_threads(stats, other):
    """The child opens on another thread while the parent is open there:
    it is no child, and the parent keeps its whole time as self time."""
    entered, done = threading.Event(), threading.Event()

    def worker():
        entered.wait(5)
        with other.span("ckpt.hash"):
            time.sleep(0.01)
        done.set()

    t = threading.Thread(target=worker)
    t.start()
    with stats.span("ckpt.save"):
        entered.set()
        assert done.wait(5)
    t.join(5)
    assert not t.is_alive()


@pytest.mark.parametrize(
    "case, shared",
    [("one_thread", True), ("one_thread", False), ("two_threads", True)],
)
def test_span_self_time(case, shared):
    """Self time is the duration less the child spans on the same thread,
    whichever ``EngineStats`` those record into."""
    stats = EngineStats()
    other = stats if shared else EngineStats()
    (_nest_one_thread if case == "one_thread" else _nest_two_threads)(stats, other)
    parent = stats.spans()["ckpt.save"]
    child = other.spans()["ckpt.hash"]
    assert parent["count"] == child["count"] == 1
    assert child["self_seconds"] == child["seconds"] >= 0.01
    assert parent["max_seconds"] == parent["seconds"] > child["seconds"]
    if case == "one_thread":
        assert parent["self_seconds"] == pytest.approx(parent["seconds"] - child["seconds"], abs=1e-9)
        assert parent["self_seconds"] >= 0.002
    else:
        assert parent["self_seconds"] == parent["seconds"]


def test_span_table_quantiles_and_names():
    stats = EngineStats()
    for ms in range(1, 101):
        with stats.span("db.put"):
            pass
        stats._record_span("bvalue.pread", ms / 1e3, ms / 1e3)
    row = stats.snapshot()["spans"]["bvalue.pread"]
    assert row["count"] == 100
    assert row["p50_ms"] == pytest.approx(51.0)
    assert row["p99_ms"] == pytest.approx(100.0)
    assert row["max_seconds"] == pytest.approx(0.1)
    assert stats.snapshot()["spans"]["db.put"]["count"] == 100
    with pytest.raises(ValueError, match="SPAN_NAMES"):
        stats.span("no.such.span")
    assert len(set(SPAN_NAMES)) == len(SPAN_NAMES)


def test_spans_leave_jax_unimported():
    code = (
        "import sys, repro.core\n"
        "from repro.core.stats import EngineStats\n"
        "s = EngineStats()\n"
        "with s.span('ckpt.save', step=1):\n"
        "    with s.span('db.put'):\n"
        "        pass\n"
        "assert s.snapshot()['spans']['db.put']['count'] == 1\n"
        "assert 'jax' not in sys.modules, 'spans imported jax'\n"
    )
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(sys.path))
    subprocess.run([sys.executable, "-c", code], check=True, env=env, timeout=120)


def test_span_lands_on_host_plane(tmp_path):
    import jax
    from jax.profiler import ProfileData

    stats = EngineStats()

    def save_thread():
        with stats.span("db.put"):
            time.sleep(0.002)

    jax.profiler.start_trace(str(tmp_path))
    try:
        with stats.span("ckpt.save", step=41):
            with stats.span("ckpt.hash", step=41, leaf="['params']['w']"):
                time.sleep(0.002)
        t = threading.Thread(target=save_thread)
        t.start()
        t.join(5)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    events.setdefault(ev.name, []).append(ev)
    (save,) = events["ckpt.save"]
    (hash_,) = events["ckpt.hash"]
    assert dict(save.stats)["step"] == 41
    assert dict(hash_.stats) == {"step": 41, "leaf": "['params']['w']"}
    assert save.start_ns <= hash_.start_ns and hash_.end_ns <= save.end_ns
    assert len(events["db.put"]) == 1
    assert not jax.profiler.TraceAnnotation.is_enabled()


def test_engine_spans(tmp_path):
    """The store engine's spans on a sync-WAL write and read, a flush job,
    and a read after a reopen; the ``jobs`` table keeps its shape."""
    cfg = DBConfig.bvlsm(wal_mode="sync", value_threshold=4096)
    db = DB.open(str(tmp_path / "db"), cfg)
    for i in range(5):
        db.put(f"k{i}".encode(), bytes([i]) * 8192)
    db.put(b"small", b"v" * 100)  # under value_threshold: no span
    db.flush()
    db.close()
    snap = db.stats.snapshot()
    spans = snap["spans"]
    for name, count in [("db.put", 5), ("bvalue.write", 5), ("bvalue.pwrite", 5),
                        ("bvalue.fsync", 5)]:
        assert spans[name]["count"] == count, name
    assert spans["wal.fsync"]["count"] >= 5
    assert spans["engine.flush"]["count"] >= 1
    assert snap["jobs"]["flush"] == {"count": spans["engine.flush"]["count"],
                                     "seconds": spans["engine.flush"]["seconds"]}
    assert snap["job_flush_count"] == spans["engine.flush"]["count"]
    put, write = spans["db.put"], spans["bvalue.write"]
    assert put["self_seconds"] <= put["seconds"] - write["seconds"] + 1e-9
    assert not hasattr(db.stats, "timeline")
    with DB.open(str(tmp_path / "db"), cfg) as db:  # cold caches: the value is read
        assert db.get(b"k3") == bytes([3]) * 8192
        spans = db.stats.snapshot()["spans"]
    assert spans["bvalue.pread"]["count"] == 1


def _tiny_state():
    """Three leaves: one of 9 MiB (three 4 MiB chunks) and two small."""
    return {
        "params": {"w": np.arange(9 << 18, dtype=np.float32).reshape(9 << 10, 256),
                   "b": np.ones(7, np.float32)},
        "step": np.int32(3),
    }


def _counts(store):
    """Span counts of a checkpoint store, a sharded engine's db.* summed in."""
    st = store.stats()
    counts = {k: v["count"] for k, v in st["spans"].items()}
    for shard in st.get("per_shard", []):
        for k, v in shard["spans"].items():
            counts[k] = counts.get(k, 0) + v["count"]
    return counts


def _open_store(tmp_path, engine):
    from repro.checkpoint.bvstore import BVCheckpointStore

    if engine == "db":
        return BVCheckpointStore(str(tmp_path / "ck"))
    return BVCheckpointStore("ignored", db=ShardedDB.open(str(tmp_path / "ck"), shards=2))


@pytest.mark.parametrize("engine", ["db", "sharded"])
def test_checkpoint_save_and_restore_spans(tmp_path, engine):
    from repro.checkpoint.manager import CheckpointManager

    store = _open_store(tmp_path, engine)
    if engine == "db":
        assert store._stats is store.db.stats
    else:
        assert isinstance(store._stats, EngineStats)
    save = store.save

    def slow_save(*a, **kw):  # each save outlasts the next save_now call
        time.sleep(0.2)
        return save(*a, **kw)

    store.save = slow_save
    mgr = CheckpointManager(store, interval_steps=1, keep_last=1, async_save=True,
                            incremental=False)
    state = _tiny_state()
    mgr.save_now(10, state, {"pipeline": {}})
    mgr.save_now(11, state, {"pipeline": {}})  # waits on step 10's save, retires it
    mgr.wait()
    counts = _counts(store)
    # a db.put span per big value: the 9 MiB leaf's three 4 MiB chunks; the
    # small leaves and META are under the threshold
    leaves, big_chunks = 3, 3
    for name, count in [("ckpt.wait", 2), ("ckpt.snapshot", 2), ("ckpt.save", 2),
                        ("ckpt.serialize", 2 * leaves), ("ckpt.hash", 2 * leaves),
                        ("ckpt.put", 2 * leaves), ("ckpt.barrier", 2), ("ckpt.commit", 2),
                        ("db.put", 2 * big_chunks)]:
        assert counts[name] == count, name
    assert store.steps() == [11]
    spans = store.stats()["spans"]
    assert spans["ckpt.save"]["seconds"] <= sum(mgr.save_seconds) - 0.4
    children = sum(spans[k]["seconds"] for k in
                   ("ckpt.serialize", "ckpt.hash", "ckpt.put", "ckpt.barrier", "ckpt.commit"))
    assert spans["ckpt.save"]["self_seconds"] == pytest.approx(
        spans["ckpt.save"]["seconds"] - children, abs=1e-6)

    host, meta = store.load(11, template=state)
    np.testing.assert_array_equal(host["params"]["w"], state["params"]["w"])
    loaded = _counts(store)
    chunks = leaves - 1 + big_chunks  # a read and a scatter per chunk
    for name, count in [("ckpt.load_meta", 1), ("ckpt.read", chunks), ("ckpt.scatter", chunks)]:
        assert loaded[name] == count, name
    store.close()
    # reopened, with cold caches: each separated chunk (the 9 MiB leaf's
    # three) is one pread; the small leaves and META are inline
    store = _open_store(tmp_path, engine)
    store.load(11, template=state)
    assert _counts(store)["bvalue.pread"] == big_chunks
    store.close()


@pytest.mark.parametrize("engine", ["db", "sharded"])
def test_shard_snapshot_and_scatter_spans(tmp_path, engine):
    """A device state is snapshotted with one ``ckpt.shard_snapshot`` per
    shard inside ``ckpt.snapshot``; a restore onto a mesh reads and scatters
    once per chunk, places once per leaf, and counts the bytes of each."""
    import jax
    import jax.numpy as jnp

    from repro.checkpoint.manager import CheckpointManager
    from repro.dist import Axes
    from repro.launch.mesh import make_host_mesh

    store = _open_store(tmp_path, engine)
    state = jax.tree.map(jnp.asarray, _tiny_state())
    nbytes = sum(x.nbytes for x in jax.tree.leaves(state))
    CheckpointManager(store, async_save=False).save_now(1, state)
    leaves, big_chunks = 3, 3
    counts = _counts(store)
    assert counts["ckpt.snapshot"] == 1 and counts["ckpt.shard_snapshot"] == leaves
    spans = store.stats()["spans"]
    assert spans["ckpt.shard_snapshot"]["seconds"] <= spans["ckpt.snapshot"]["seconds"]
    assert store.stats()["ckpt_shards_saved"] == leaves
    axes = {"params": {"w": Axes("param_embed", "mlp"), "b": Axes(None)}, "step": Axes()}
    out, _ = store.load_distributed(make_host_mesh((1, 1)), state, axes)
    np.testing.assert_array_equal(np.asarray(out["params"]["w"]), np.asarray(state["params"]["w"]))
    chunks = leaves - 1 + big_chunks
    counts = _counts(store)
    for name, count in [("ckpt.read", chunks), ("ckpt.scatter", chunks), ("ckpt.place", leaves)]:
        assert counts[name] == count, name
    stats = store.stats()
    assert stats["ckpt_read_bytes"] == nbytes and stats["ckpt_place_bytes"] == nbytes
    store.close()


def test_restore_spans_through_trainer(tmp_path):
    import jax

    from repro.configs import get_config
    from repro.training.trainer import Trainer, TrainerConfig

    cfg = get_config("llama3-8b").reduced(d_model=64, n_layers=1, vocab=512, vocab_pad_multiple=64)
    tcfg = TrainerConfig(global_batch=2, seq_len=16, ckpt_dir=str(tmp_path / "ck"))
    tr = Trainer(cfg, tcfg)
    assert tr._init_or_restore() == 0
    tr.ckpt.save_now(5, tr.state, {"pipeline": tr.pipeline.state_dict()})
    tr.close()
    n_leaves = len(jax.tree.leaves(tr.state))

    tr = Trainer(cfg, tcfg)
    assert tr._init_or_restore() == 5
    spans = tr.store.stats()["spans"]
    tr.close()
    # every leaf of this model is one chunk: a read and a scatter each
    for name, count in [("ckpt.restore", 1), ("ckpt.load_meta", 1), ("ckpt.read", n_leaves),
                        ("ckpt.scatter", n_leaves), ("ckpt.place", 1)]:
        assert spans[name]["count"] == count, name
    restore = spans["ckpt.restore"]
    inside = sum(spans[k]["seconds"] for k in ("ckpt.load_meta", "ckpt.read", "ckpt.scatter", "ckpt.place"))
    assert inside <= restore["seconds"]
    assert restore["self_seconds"] == pytest.approx(restore["seconds"] - inside, abs=1e-6)
