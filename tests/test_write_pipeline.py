"""Write pipeline: WriteBatch semantics, leader/follower group commit,
pipelined leader handoff (v2: overlap, sequence-ordered publication,
adaptive group sizing, sharded memtable apply), BValue batched fan-out +
roll race, MemTable sorted-view cache, the BValue flush barrier, and puts
of byte buffers other than ``bytes``."""
import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from repro.core import DB, DBConfig, WriteBatch
from repro.core.bvalue import BValueManager
from repro.core.memtable import MemTable
from repro.core.record import decode_entries, kTypeValue
from repro.core.wal import replay_wal

SMALL = dict(
    memtable_size=64 << 10,
    level1_max_bytes=256 << 10,
    value_threshold=512,
    bvcache_bytes=64 << 10,
    l0_compaction_trigger=2,
)


def mk(tmp, mode="wal", wal="sync", **kw):
    cfg = {**SMALL, **kw}
    return DB(tmp, DBConfig(separation_mode=mode, wal_mode=wal, **cfg))


# ---------------------------------------------------------------------------
# WriteBatch API
# ---------------------------------------------------------------------------

def test_writebatch_basic_and_empty(tmp_db_dir):
    db = mk(tmp_db_dir)
    try:
        b = WriteBatch()
        assert len(b) == 0
        db.write(b)  # empty batch is a no-op
        b.put(b"a", b"1").put(b"b", b"2").delete(b"missing")
        assert len(b) == 3 and b.size_bytes == 4 + len(b"missing")
        db.write(b)
        assert db.get(b"a") == b"1"
        assert db.get(b"b") == b"2"
        assert db.get(b"missing") is None
        b.clear()
        assert len(b) == 0 and b.size_bytes == 0
    finally:
        db.close()


def test_writebatch_one_wal_record_one_fsync(tmp_db_dir):
    """A 100-entry batch must cost a single WAL record + a single fsync."""
    db = mk(tmp_db_dir, wal="sync")
    try:
        b = WriteBatch()
        for i in range(100):
            b.put(f"k{i:03d}".encode(), b"v" * 64)
        db.write(b)
        s = db.stats.snapshot()
        assert s["wal_records"] == 1
        assert s["wal_fsyncs"] == 1
        assert s["user_writes"] == 100
        assert s["group_commits"] == 1
    finally:
        db.close()


def test_writebatch_duplicate_keys_last_wins(tmp_db_dir):
    db = mk(tmp_db_dir)
    try:
        b = WriteBatch()
        b.put(b"k", b"first").delete(b"k").put(b"k", b"last")
        db.write(b)
        assert db.get(b"k") == b"last"
        db.flush()
        assert db.get(b"k") == b"last"
    finally:
        db.close()


# ---------------------------------------------------------------------------
# concurrent group commit
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("wal", ["sync", "async"])
def test_concurrent_writers_all_readable(tmp_db_dir, wal):
    db = mk(tmp_db_dir, wal=wal, memtable_size=4 << 20)
    nthreads, n = 8, 120
    errors = []

    def writer(t):
        try:
            for i in range(n):
                db.put(f"t{t}k{i:04d}".encode(), f"val-{t}-{i}".encode() * 20)
        except BaseException as e:  # pragma: no cover - failure diagnostics
            errors.append(e)

    try:
        ts = [threading.Thread(target=writer, args=(t,)) for t in range(nthreads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert not errors
        s = db.stats.snapshot()
        assert s["user_writes"] == nthreads * n
        for t in range(nthreads):
            for i in range(0, n, 13):
                assert db.get(f"t{t}k{i:04d}".encode()) == f"val-{t}-{i}".encode() * 20
    finally:
        db.close()


def test_concurrent_sync_writers_durable_after_crash(tmp_db_dir):
    """Every acknowledged concurrent write with sync WAL survives a crash:
    followers are only woken after the leader's group fsync covers them."""
    db = mk(tmp_db_dir, wal="sync", memtable_size=4 << 20)
    nthreads, n = 6, 60
    acked: dict[bytes, bytes] = {}
    lock = threading.Lock()

    def writer(t):
        for i in range(n):
            k, v = f"t{t}k{i:04d}".encode(), (b"%d.%d|" % (t, i)) * 30
            db.put(k, v)
            with lock:
                acked[k] = v

    ts = [threading.Thread(target=writer, args=(t,)) for t in range(nthreads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    db.close(crash=True)  # memtable NOT flushed
    db2 = mk(tmp_db_dir, wal="sync")
    try:
        for k, v in acked.items():
            assert db2.get(k) == v, k
    finally:
        db2.close()


def test_group_commit_amortizes_fsyncs(tmp_db_dir):
    """With 8 concurrent sync writers the leader must merge groups: strictly
    fewer fsyncs than writes (the pre-pipeline path pays 1.0 per write)."""
    db = mk(tmp_db_dir, wal="sync", memtable_size=16 << 20)
    nthreads, n = 8, 80

    def writer(t):
        for i in range(n):
            db.put(f"t{t}k{i:04d}".encode(), b"v" * 256)

    try:
        ts = [threading.Thread(target=writer, args=(t,)) for t in range(nthreads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        s = db.stats.snapshot()
        assert s["user_writes"] == nthreads * n
        # on a single CPU overlap varies, but SOME grouping must happen
        assert s["wal_fsyncs"] < s["user_writes"]
        assert s["fsyncs_per_write"] < 1.0
        assert sum(s["group_size_hist"].values()) == s["group_commits"]
    finally:
        db.close()


def test_group_commit_disabled_baseline(tmp_db_dir):
    """wal_group_commit=False restores one record + one fsync per write."""
    db = mk(tmp_db_dir, wal="sync", wal_group_commit=False)
    try:
        for i in range(20):
            db.put(f"k{i}".encode(), b"v" * 64)
        s = db.stats.snapshot()
        assert s["wal_fsyncs"] == 20
        assert s["fsyncs_per_write"] == 1.0
        assert s["avg_group_size"] == 1.0
    finally:
        db.close()


# ---------------------------------------------------------------------------
# pipelined commit (write pipeline v2)
# ---------------------------------------------------------------------------

def _slow_fsync(monkeypatch, delay_s: float):
    """Make WAL fsyncs observably slow (GIL released during the sleep, like
    a real fsync) so commit groups genuinely overlap. The WAL syncs through
    the Env layer, so the syscall site to slow down lives in core.env."""
    import repro.core.env as env_mod

    real = os.fsync

    def slow(fd):
        time.sleep(delay_s)
        return real(fd)

    monkeypatch.setattr(env_mod.os, "fsync", slow)


def test_pipelined_handoff_overlaps_fsync(tmp_db_dir, monkeypatch):
    """With a slow fsync, the next leader must form+write its group while
    the previous group's fsync is in flight: observed pipeline depth > 1."""
    _slow_fsync(monkeypatch, 0.01)
    db = mk(tmp_db_dir, wal="sync", memtable_size=16 << 20)
    nthreads, n = 8, 30

    def writer(t):
        for i in range(n):
            db.put(f"t{t}k{i:04d}".encode(), b"v" * 128)

    try:
        ts = [threading.Thread(target=writer, args=(t,)) for t in range(nthreads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        s = db.stats.snapshot()
        assert s["user_writes"] == nthreads * n
        assert s["pipeline_depth_max"] >= 2, s["pipeline_depth_hist"]
        for t in range(nthreads):
            for i in range(0, n, 7):
                assert db.get(f"t{t}k{i:04d}".encode()) == b"v" * 128
    finally:
        db.close()


def test_pipelined_disabled_is_single_outstanding(tmp_db_dir, monkeypatch):
    """wal_pipelined_commit=False restores PR 1's depth-1 pipeline."""
    _slow_fsync(monkeypatch, 0.005)
    db = mk(tmp_db_dir, wal="sync", wal_pipelined_commit=False, memtable_size=16 << 20)
    nthreads, n = 6, 20

    def writer(t):
        for i in range(n):
            db.put(f"t{t}k{i:04d}".encode(), b"v" * 64)

    try:
        ts = [threading.Thread(target=writer, args=(t,)) for t in range(nthreads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        s = db.stats.snapshot()
        assert s["user_writes"] == nthreads * n
        assert s["pipeline_depth_max"] <= 1
    finally:
        db.close()


def test_pipelined_crash_recovery_no_commit_order_hole(tmp_db_dir, monkeypatch):
    """Crash under pipelined sync commits: (a) the WAL byte stream is in
    strictly ascending sequence order — replay can never surface group N+1
    without group N — and (b) every ACKED write survives recovery."""
    _slow_fsync(monkeypatch, 0.002)
    db = mk(tmp_db_dir, wal="sync", memtable_size=16 << 20)
    nthreads, n = 6, 40
    acked: dict[bytes, bytes] = {}
    lock = threading.Lock()

    def writer(t):
        for i in range(n):
            k, v = f"t{t}k{i:04d}".encode(), (b"%d.%d|" % (t, i)) * 20
            db.put(k, v)
            with lock:
                acked[k] = v

    ts = [threading.Thread(target=writer, args=(t,)) for t in range(nthreads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    db.close(crash=True)  # memtable NOT flushed
    logs = sorted(f for f in os.listdir(tmp_db_dir) if f.startswith("wal_"))
    assert logs
    seqs = []
    for name in logs:
        for payload in replay_wal(os.path.join(tmp_db_dir, name)):
            seq, _ = decode_entries(payload)
            seqs.append(seq)
    assert seqs == sorted(seqs), "WAL file order diverged from sequence order"
    assert len(seqs) == len(set(seqs))
    db2 = mk(tmp_db_dir, wal="sync")
    try:
        for k, v in acked.items():
            assert db2.get(k) == v, k
    finally:
        db2.close()


def test_covered_fsync_skipped(tmp_db_dir, monkeypatch):
    """Pipelined groups whose ticket a later-started fsync already covered
    skip their own fsync (wal_fsync_skips > 0 under a slow-fsync pileup)."""
    _slow_fsync(monkeypatch, 0.01)
    db = mk(tmp_db_dir, wal="sync", memtable_size=16 << 20, wal_pipeline_depth=8,
            wal_pipeline_min_fill=1)  # eager handoff: force groups to stack
    nthreads, n = 8, 25

    def writer(t):
        for i in range(n):
            db.put(f"t{t}k{i:04d}".encode(), b"v" * 64)

    try:
        ts = [threading.Thread(target=writer, args=(t,)) for t in range(nthreads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        s = db.stats.snapshot()
        assert s["user_writes"] == nthreads * n
        assert s["wal_fsync_skips"] > 0, s
        # skips never weaken durability accounting: every group either
        # fsynced or was covered by one
        assert s["wal_fsyncs"] + s["wal_fsync_skips"] >= s["group_commits"]
    finally:
        db.close()


def test_adaptive_cap_tracks_latency_target(tmp_db_dir, monkeypatch):
    """The latency-target controller shrinks the effective byte cap to the
    floor under a slow fsync and grows it to the ceiling under a fast one."""
    import repro.core.env as env_mod

    # slow: persist EWMA far above the 4 ms default target -> floor
    monkeypatch.setattr(env_mod.os, "fsync", lambda fd: time.sleep(0.012))
    db = mk(tmp_db_dir + "_slow", wal="sync", memtable_size=16 << 20)
    try:
        for i in range(25):
            db.put(f"k{i:03d}".encode(), b"v" * 64)
        g = db.stats.snapshot()["gauges"]
        assert g["wal_group_effective_bytes"] == db.cfg.wal_group_min_bytes, g
        assert g["wal_persist_ewma_s"] > db.cfg.wal_group_target_latency_s
    finally:
        db.close()

    # fast: fsync is a no-op -> EWMA under target/2 -> ceiling
    monkeypatch.setattr(env_mod.os, "fsync", lambda fd: None)
    db = mk(tmp_db_dir + "_fast", wal="sync", memtable_size=16 << 20)
    try:
        for i in range(40):
            db.put(f"k{i:03d}".encode(), b"v" * 64)
        g = db.stats.snapshot()["gauges"]
        assert g["wal_group_effective_bytes"] == db.cfg.wal_group_max_bytes, g
    finally:
        db.close()


def test_adaptive_disabled_uses_fixed_cap(tmp_db_dir):
    db = mk(tmp_db_dir, wal="sync", wal_group_adaptive=False)
    try:
        for i in range(10):
            db.put(f"k{i}".encode(), b"v" * 64)
        assert "wal_group_effective_bytes" not in db.stats.snapshot()["gauges"]
    finally:
        db.close()


# ---------------------------------------------------------------------------
# sharded memtable apply
# ---------------------------------------------------------------------------

def test_memtable_add_group_sharded_matches_sequential():
    """Hash-sharded group apply is bit-identical to the sequential apply,
    including cross-batch overwrites (per-key seq order preserved)."""
    seq_mt, sh_mt = MemTable(), MemTable()
    applies = []
    for b in range(5):
        entries = [
            (kTypeValue, f"k{(b * 31 + i) % 97:03d}".encode(), bytes([b]) * (10 + i % 7))
            for i in range(50)
        ]
        applies.append((100 + b, entries))
    seq_prevs = []
    for seq, entries in applies:
        seq_prevs.extend(seq_mt.add_batch(seq, entries))
    with ThreadPoolExecutor(max_workers=4) as pool:
        sh_prevs = sh_mt.add_group_sharded(applies, pool, 4)
    assert list(seq_mt.sorted_items()) == list(sh_mt.sorted_items())
    assert seq_mt.approximate_size == sh_mt.approximate_size
    assert sorted(seq_prevs) == sorted(sh_prevs)
    assert (seq_mt.first_seq, seq_mt.last_seq) == (sh_mt.first_seq, sh_mt.last_seq)


def test_db_shards_huge_group_apply(tmp_db_dir):
    """A group over the entry threshold goes through the sharded apply and
    stays fully readable (and durable across reopen)."""
    db = mk(
        tmp_db_dir, wal="sync", memtable_size=32 << 20,
        memtable_shard_apply_entries=64, memtable_apply_shards=4,
        value_threshold=1 << 20,
    )
    b = WriteBatch()
    for i in range(500):
        b.put(f"k{i:04d}".encode(), bytes([i % 251]) * 40)
    try:
        db.write(b)
        s = db.stats.snapshot()
        assert s["memtable_shard_applies"] >= 1
        assert s["user_writes"] == 500
        for i in range(0, 500, 37):
            assert db.get(f"k{i:04d}".encode()) == bytes([i % 251]) * 40
    finally:
        db.close(crash=True)
    db2 = mk(tmp_db_dir, wal="sync")
    try:
        for i in range(0, 500, 11):
            assert db2.get(f"k{i:04d}".encode()) == bytes([i % 251]) * 40
    finally:
        db2.close()


def test_pipelined_rotation_preserves_durability(tmp_db_dir):
    """Tiny memtable: rotations interleave with pipelined commits; every
    acked write must survive a crash (rotation only happens with the
    pipeline drained, so no WAL record is stranded in a dropped file)."""
    db = mk(tmp_db_dir, wal="sync", memtable_size=8 << 10)
    nthreads, n = 4, 40
    acked: dict[bytes, bytes] = {}
    lock = threading.Lock()

    def writer(t):
        for i in range(n):
            k, v = f"t{t}k{i:04d}".encode(), (b"%d:%d|" % (t, i)) * 40
            db.put(k, v)
            with lock:
                acked[k] = v

    ts = [threading.Thread(target=writer, args=(t,)) for t in range(nthreads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    db.close(crash=True)
    db2 = mk(tmp_db_dir, wal="sync")
    try:
        for k, v in acked.items():
            assert db2.get(k) == v, k
    finally:
        db2.close()


# ---------------------------------------------------------------------------
# WriteBatch atomicity
# ---------------------------------------------------------------------------

def test_batch_atomic_across_memtable_rotation(tmp_db_dir):
    """A batch bigger than the memtable budget lands in ONE memtable/WAL
    generation (rotation happens between groups, never inside one)."""
    db = mk(tmp_db_dir, wal="sync", memtable_size=8 << 10)
    try:
        for r in range(6):
            b = WriteBatch()
            for i in range(40):
                b.put(f"r{r}k{i:03d}".encode(), bytes([r]) * 400)
            db.write(b)
        db.flush()
        db.compact_all()
        for r in range(6):
            for i in range(0, 40, 7):
                assert db.get(f"r{r}k{i:03d}".encode()) == bytes([r]) * 400
    finally:
        db.close()


def test_batch_replay_is_all_or_nothing(tmp_db_dir):
    """A torn WAL tail drops the whole trailing batch, never part of it."""
    db = mk(tmp_db_dir, wal="sync", memtable_size=4 << 20, value_threshold=1 << 20)
    for r in range(3):
        b = WriteBatch()
        for i in range(10):
            b.put(f"r{r}k{i:02d}".encode(), bytes([65 + r]) * 100)
        db.write(b)
    db.close(crash=True)
    # tear the tail of the WAL: the LAST batch's record becomes corrupt
    logs = sorted(f for f in os.listdir(tmp_db_dir) if f.startswith("wal_"))
    assert logs
    wal_path = os.path.join(tmp_db_dir, logs[-1])
    size = os.path.getsize(wal_path)
    with open(wal_path, "ab") as f:
        f.truncate(size - 3)
    db2 = mk(tmp_db_dir, wal="sync")
    try:
        for r in range(2):  # intact batches fully present
            for i in range(10):
                assert db2.get(f"r{r}k{i:02d}".encode()) == bytes([65 + r]) * 100
        # torn batch fully absent — not a single entry of it survived
        for i in range(10):
            assert db2.get(f"r2k{i:02d}".encode()) is None
    finally:
        db2.close()


def test_mixed_big_and_inline_batch(tmp_db_dir):
    """One batch mixing separated big values, inline values and deletes."""
    db = mk(tmp_db_dir, wal="sync", value_threshold=512)
    try:
        db.put(b"gone", b"x" * 64)
        b = WriteBatch()
        for i in range(20):
            b.put(f"big{i:02d}".encode(), bytes([i + 1]) * 2048)  # separated
            b.put(f"small{i:02d}".encode(), bytes([i + 1]) * 32)  # inline
        b.delete(b"gone")
        db.write(b)
        s = db.stats.snapshot()
        assert s["wal_records"] == 2  # the single put + the batch
        for i in range(20):
            assert db.get(f"big{i:02d}".encode()) == bytes([i + 1]) * 2048
            assert db.get(f"small{i:02d}".encode()) == bytes([i + 1]) * 32
        assert db.get(b"gone") is None
        db.flush()
        db.compact_all()
        assert db.get(b"big07") == bytes([8]) * 2048
    finally:
        db.close()
    db2 = mk(tmp_db_dir, wal="sync", value_threshold=512)
    try:
        for i in range(20):
            assert db2.get(f"big{i:02d}".encode()) == bytes([i + 1]) * 2048
            assert db2.get(f"small{i:02d}".encode()) == bytes([i + 1]) * 32
        assert db2.get(b"gone") is None
    finally:
        db2.close()


# ---------------------------------------------------------------------------
# BValue store: put_many fan-out, roll race, flush barrier
# ---------------------------------------------------------------------------

def test_put_many_fans_out_and_amortizes_fsyncs(tmp_path):
    mgr = BValueManager(str(tmp_path / "bv"), num_queues=4, async_writes=False)
    items = [(f"k{i:03d}".encode(), bytes([i % 251]) * 600) for i in range(32)]
    voffs = mgr.put_many(items, sync=True)
    assert len(voffs) == 32
    # round-robin: 32 values spread across all 4 queue files
    assert len({v.file_id for v in voffs}) == 4
    for (k, val), voff in zip(items, voffs):
        assert mgr.get(voff, verify=True) == val
    mgr.close()


def test_bvalue_roll_race_sync_writers(tmp_path):
    """Concurrent sync writers on one queue force file rolls between
    reserve() and the pwrite; every value must land in ITS reserved file
    (CRC-verified reads would explode if a write hit the wrong file)."""
    mgr = BValueManager(
        str(tmp_path / "bv"), num_queues=1, async_writes=False,
        max_file_bytes=4 << 10,  # tiny: rolls every ~2 values
    )
    results: dict[bytes, object] = {}
    lock = threading.Lock()
    errors = []

    def writer(t):
        try:
            for i in range(40):
                key = f"t{t}k{i:02d}".encode()
                val = (b"%d:%d|" % (t, i)) * 300  # ~1.8 KiB
                voff = mgr.put(key, val, sync=True)
                with lock:
                    results[key] = (voff, val)
        except BaseException as e:
            errors.append(e)

    ts = [threading.Thread(target=writer, args=(t,)) for t in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert not errors, errors
    assert len({v.file_id for v, _ in results.values()}) > 10  # many rolls happened
    for key, (voff, val) in results.items():
        assert mgr.get(voff, verify=True) == val, key
    mgr.close()


def test_async_big_value_batch_unpins_after_persist(tmp_db_dir):
    """Async WAL: pinned BVCache entries become evictable once the BValue
    writers persist them — the unpin must match despite the writer-side
    ValueOffset lacking the CRC, and must never race ahead of the insert."""
    db = mk(
        tmp_db_dir, wal="async",
        bvalue_batch_bytes=4 << 10, bvalue_gather_window_s=0.005,
        memtable_size=16 << 20, bvcache_bytes=16 << 20,
    )
    try:
        b = WriteBatch()
        for i in range(200):
            b.put(f"big{i:03d}".encode(), bytes([i % 251]) * 2048)
        db.write(b)
        db.bvalue.flush()
        assert db.bvcache.stats()["pinned"] == 0
        for i in range(0, 200, 23):
            assert db.get(f"big{i:03d}".encode()) == bytes([i % 251]) * 2048
    finally:
        db.close()


def _cached_values(db) -> list:
    with db.bvcache._lock:
        return [e.value for e in (*db.bvcache._map.values(), *db.bvcache._pinned.values())]


@pytest.mark.parametrize("wal", ["sync", "async"])
@pytest.mark.parametrize("size", [4096, 64])  # separated / inline (threshold 512)
def test_put_byte_buffer_reads_back_as_bytes(tmp_db_dir, wal, size):
    """A memoryview put is read back as ``bytes`` before and after a flush
    and after a reopen; overwriting the source array once ``put`` returned
    changes neither, and the BVCache never holds the caller's buffer."""
    src = np.arange(size, dtype=np.uint32).astype(np.uint8)
    want = src.tobytes()
    db = mk(tmp_db_dir, wal=wal)
    try:
        db.put(b"k", memoryview(src))
        db.put(b"m", memoryview(src.reshape(-1, 8)))  # 2-D: taken flat
        src[:] = 0xAB
        for key in (b"k", b"m"):
            got = db.get(key)
            assert type(got) is bytes and got == want
        assert all(type(v) is bytes for v in _cached_values(db))
        db.flush()
        for key in (b"k", b"m"):
            got = db.get(key)
            assert type(got) is bytes and got == want
    finally:
        db.close()
    db = mk(tmp_db_dir, wal=wal)
    try:
        assert db.get(b"k") == want and db.get(b"m") == want
    finally:
        db.close()


@pytest.mark.parametrize("kind", ["bytes", "memoryview"])
def test_sync_big_put_pwrites_the_callers_buffer(tmp_db_dir, monkeypatch, kind):
    """Under the sync WAL one big value reaches ``pwrite`` uncopied: a
    ``bytes`` value as the same object (and into the BVCache, as before),
    a memoryview as a view of the caller's memory (and not cached)."""
    db = mk(tmp_db_dir, wal="sync")
    src = np.arange(4096, dtype=np.uint32).astype(np.uint8)
    value = src.tobytes() if kind == "bytes" else memoryview(src)
    written = []
    pwrite = db.bvalue.env.pwrite

    def spy(fd, data, offset):
        written.append(data)
        return pwrite(fd, data, offset)

    monkeypatch.setattr(db.bvalue.env, "pwrite", spy)
    try:
        db.put(b"k", value)
        (data,) = written
        if kind == "bytes":
            assert data is value
            assert _cached_values(db) == [value] and _cached_values(db)[0] is value
        else:
            assert np.shares_memory(np.frombuffer(data, np.uint8), src)
            assert _cached_values(db) == []
        assert db.get(b"k") == src.tobytes()
    finally:
        monkeypatch.undo()
        db.close()


@pytest.mark.parametrize("kind", ["float32", "strided", "str"])
def test_put_refuses_a_value_that_is_no_byte_buffer(tmp_db_dir, kind):
    value = {
        "float32": lambda: memoryview(np.zeros(1024, np.float32)),
        "strided": lambda: memoryview(np.zeros(4096, np.uint8)[::2]),
        "str": lambda: "x" * 4096,
    }[kind]()
    db = mk(tmp_db_dir, wal="sync")
    try:
        with pytest.raises(TypeError):
            db.put(b"k", value)
        with pytest.raises(TypeError):
            db.write(WriteBatch().put(b"k", value))
        assert db.get(b"k") is None
    finally:
        db.close()


def test_bvalue_flush_barrier_drains_async_queues(tmp_path):
    persisted = []
    mgr = BValueManager(
        str(tmp_path / "bv"), num_queues=2, async_writes=True,
        gather_window_s=0.01, on_persisted=lambda k, v: persisted.append(k),
    )
    voffs = [mgr.put(f"k{i}".encode(), bytes([i]) * 512, sync=False) for i in range(50)]
    mgr.flush(timeout=30)  # CV barrier — returns only once queues are drained
    assert len(persisted) == 50
    for q in mgr.queues:
        assert q._pending_items == 0 and q.pending_bytes == 0
    for i, voff in enumerate(voffs):
        assert mgr.get(voff, verify=True) == bytes([i]) * 512
    mgr.close()


# ---------------------------------------------------------------------------
# MemTable: bulk apply + sorted-view cache
# ---------------------------------------------------------------------------

def test_memtable_add_batch_matches_add():
    a, b = MemTable(), MemTable()
    entries = [(kTypeValue, f"k{i % 7}".encode(), bytes([i]) * 10) for i in range(20)]
    for e in entries:
        a.add(5, *e)
    prevs = b.add_batch(5, entries)
    assert len(prevs) == 13  # 20 adds over 7 distinct keys
    assert list(a.sorted_items()) == list(b.sorted_items())
    assert a.approximate_size == b.approximate_size


def test_memtable_sorted_view_cached_and_invalidated():
    m = MemTable()
    for i in (3, 1, 2):
        m.add(i, kTypeValue, f"k{i}".encode(), b"v")
    assert [k for k, *_ in m.sorted_items()] == [b"k1", b"k2", b"k3"]
    cache = m._sorted_cache
    assert cache is not None and cache[0] == m._version
    # overwrite existing key: cached list survives (key set unchanged)
    m.add(4, kTypeValue, b"k2", b"v2")
    assert m._sorted() is cache[1]
    assert [k for k, *_ in m.range_items(b"k2", None)] == [b"k2", b"k3"]
    # new key: version bump invalidates, next read re-sorts
    m.add(5, kTypeValue, b"k0", b"v")
    assert m._sorted_cache[0] != m._version
    assert [k for k, *_ in m.sorted_items()] == [b"k0", b"k1", b"k2", b"k3"]
    assert m._sorted_cache[0] == m._version
    assert [k for k, *_ in m.range_items(b"k1", b"k3")] == [b"k1", b"k2"]
