"""MVCC surface: snapshots, cursors, range deletes — proven against the
dict-of-versions reference model (`repro.testing.model_db`).

Two layers of evidence:

* deterministic regression tests for every visibility rule the engine
  implements (and one for each bug the differential harness caught);
* the randomized differential driver itself — plain ``random`` here so it
  runs in the hypothesis-free container, plus a hypothesis stateful machine
  that layers minimizing shrinkage on top where the dependency exists.
"""
import threading

import pytest

from repro.core import DB, DBConfig
from repro.testing.model_db import LATEST, ModelDB, run_differential, run_example

try:
    from hypothesis import settings
    from hypothesis.stateful import (
        RuleBasedStateMachine,
        initialize,
        invariant,
        precondition,
        rule,
    )
    import hypothesis.strategies as st

    HAVE_HYPOTHESIS = True
except ModuleNotFoundError:  # container ships without hypothesis
    HAVE_HYPOTHESIS = False


def _db(tmp, **kw):
    cfg = dict(
        separation_mode="wal",
        memtable_size=4 << 10,  # tiny: tests exercise flux, not capacity
        value_threshold=64,
        l0_compaction_trigger=2,
    )
    cfg.update(kw)
    return DB(tmp, DBConfig(**cfg))


# ---------------------------------------------------------------------------
# snapshot isolation
# ---------------------------------------------------------------------------

def test_snapshot_pins_point_reads(tmp_db_dir):
    db = _db(tmp_db_dir)
    try:
        db.put(b"a", b"v1")
        with db.snapshot() as snap:
            db.put(b"a", b"v2")
            db.delete(b"a")
            assert db.get(b"a") is None
            assert db.get(b"a", snapshot=snap) == b"v1"
    finally:
        db.close()


def test_snapshot_survives_flush_and_compaction(tmp_db_dir):
    db = _db(tmp_db_dir)
    try:
        big = b"B" * 200  # separated: exercises BValue reachability too
        db.put(b"a", big)
        db.put(b"b", b"small")
        snap = db.snapshot()
        db.put(b"a", b"w" * 200)
        db.delete(b"b")
        db.flush()
        db.compact_all()
        assert db.get(b"a", snapshot=snap) == big
        assert db.get(b"b", snapshot=snap) == b"small"
        assert db.get(b"b") is None
        snap.release()
    finally:
        db.close()


def test_snapshot_release_is_idempotent_and_limited(tmp_db_dir):
    db = _db(tmp_db_dir, max_snapshots=2)
    try:
        s1, s2 = db.snapshot(), db.snapshot()
        with pytest.raises(RuntimeError):
            db.snapshot()
        s1.release()
        s1.release()  # second release is a no-op, not a double-decrement
        s3 = db.snapshot()
        s2.release()
        s3.release()
    finally:
        db.close()


def test_snapshot_sees_through_batch_boundary(tmp_db_dir):
    """A snapshot taken between two batches sees exactly the first."""
    db = _db(tmp_db_dir)
    try:
        from repro.core import WriteBatch

        wb = WriteBatch()
        wb.put(b"x", b"1")
        wb.put(b"y", b"1")
        db.write(wb)
        snap = db.snapshot()
        wb2 = WriteBatch()
        wb2.delete(b"x")
        wb2.put(b"y", b"2")
        db.write(wb2)
        assert db.get(b"x", snapshot=snap) == b"1"
        assert db.get(b"y", snapshot=snap) == b"1"
        assert db.get(b"x") is None
        assert db.get(b"y") == b"2"
        snap.release()
    finally:
        db.close()


# ---------------------------------------------------------------------------
# cursors
# ---------------------------------------------------------------------------

def test_cursor_ordering_across_flush_and_compaction(tmp_db_dir):
    db = _db(tmp_db_dir)
    try:
        keys = [f"k{i:03d}".encode() for i in range(40)]
        for k in keys:
            db.put(k, b"v_" + k)
        with db.iterator() as cur:
            seen = []
            ok = cur.seek(b"")
            for step in range(len(keys)):
                assert ok
                seen.append(cur.key)
                if step == 5:
                    # mutate + reorganize mid-iteration: the cursor's view
                    # is pinned, so none of this may perturb the walk
                    db.delete(keys[20])
                    db.put(keys[30], b"overwritten")
                    db.put(b"zzz", b"new")
                    db.flush()
                    db.compact_all()
                ok = cur.next()
            assert seen == keys
            assert not cur.next()
    finally:
        db.close()


def test_cursor_prev_and_seek(tmp_db_dir):
    db = _db(tmp_db_dir)
    try:
        keys = [f"k{i:02d}".encode() for i in range(10)]
        for k in keys:
            db.put(k, k)
        db.flush()
        db.delete(keys[4])
        with db.iterator() as cur:
            assert cur.seek(b"k05") and cur.key == b"k05"
            assert cur.prev() and cur.key == b"k03"  # k04 deleted
            assert cur.prev() and cur.key == b"k02"
            assert cur.next() and cur.key == b"k03"  # direction flip
            # prev from an exhausted cursor = seek-to-last
            while cur.next():
                pass
            assert not cur.valid
            assert cur.prev() and cur.key == keys[-1]
            # prev below the first key invalidates
            assert cur.seek(b"") and cur.key == keys[0]
            assert not cur.prev()
    finally:
        db.close()


def test_cursor_honors_snapshot(tmp_db_dir):
    db = _db(tmp_db_dir)
    try:
        db.put(b"a", b"1")
        db.put(b"c", b"1")
        snap = db.snapshot()
        db.put(b"b", b"late")
        db.delete(b"c")
        with db.iterator(snap) as cur:
            got = []
            ok = cur.seek(b"")
            while ok:
                got.append(cur.key)
                ok = cur.next()
            assert got == [b"a", b"c"]
        snap.release()
    finally:
        db.close()


def test_scan_streams_from_cursor(tmp_db_dir):
    """`scan` keeps its list signature but is a thin wrapper over Cursor."""
    db = _db(tmp_db_dir)
    try:
        for i in range(30):
            db.put(f"k{i:03d}".encode(), f"v{i}".encode())
        db.flush()
        got = list(db.range(b"k010", limit=5))
        assert [k for k, _ in got] == [f"k{i:03d}".encode() for i in range(10, 15)]
        assert got[0][1] == b"v10"
    finally:
        db.close()


# ---------------------------------------------------------------------------
# range deletes
# ---------------------------------------------------------------------------

def test_range_tombstone_visibility(tmp_db_dir):
    db = _db(tmp_db_dir)
    try:
        for k in (b"a", b"b", b"c", b"d"):
            db.put(k, b"v_" + k)
        snap = db.snapshot()
        db.delete_range(b"b", b"d")  # covers b, c; d is exclusive
        assert db.get(b"a") == b"v_a"
        assert db.get(b"b") is None
        assert db.get(b"c") is None
        assert db.get(b"d") == b"v_d"
        # the pre-delete snapshot still sees everything
        for k in (b"a", b"b", b"c", b"d"):
            assert db.get(k, snapshot=snap) == b"v_" + k
        # visibility is identical after the tombstone reaches SSTables
        db.flush()
        db.compact_all()
        assert db.get(b"b") is None
        assert db.get(b"b", snapshot=snap) == b"v_b"
        assert [k for k, _ in db.range(limit=10)] == [b"a", b"d"]
        snap.release()
    finally:
        db.close()


def test_range_tombstone_does_not_cover_same_batch_puts(tmp_db_dir):
    db = _db(tmp_db_dir)
    try:
        from repro.core import WriteBatch

        db.put(b"k1", b"old")
        wb = WriteBatch()
        wb.delete_range(b"k0", b"k9")
        wb.put(b"k1", b"new")  # same seq as the tombstone → not covered
        db.write(wb)
        assert db.get(b"k1") == b"new"
        db.flush()
        db.compact_all()
        assert db.get(b"k1") == b"new"
    finally:
        db.close()


def test_delete_range_validation(tmp_db_dir):
    db = _db(tmp_db_dir)
    try:
        with pytest.raises(ValueError):
            db.delete_range(b"b", b"a")
        with pytest.raises(ValueError):
            db.delete_range(b"a", b"a")
    finally:
        db.close()
    db2 = _db(tmp_db_dir + "_v2", sstable_format_version=2)
    try:
        with pytest.raises(ValueError):
            db2.delete_range(b"a", b"b")
    finally:
        db2.close()


def test_covering_tombstone_uses_oldest_not_newest(tmp_db_dir):
    """Regression (differential seed 7000038): an entry covered by an
    in-stripe tombstone AND a newer cross-stripe one must be dropped with
    the in-stripe tombstone — testing only the newest covering seq kept
    the value while the bottom pass dropped its tombstone, resurrecting
    the value under the pinned snapshot."""
    db = _db(tmp_db_dir)
    try:
        db.put(b"k", b"v1")          # seq 1
        db.delete_range(b"a", b"z")  # seq 2 — covers k
        snap = db.snapshot()         # pins seq 2 (sees the tombstone)
        db.flush()                   # L0 file A: k@1 + tombstone@2
        db.delete_range(b"a", b"z")  # seq 3 — newer, cross-stripe tombstone
        db.flush()                   # L0 file B: tombstone@3
        db.compact_all()             # real merge (two inputs, no trivial move)
        assert db.get(b"k") is None
        assert db.get(b"k", snapshot=snap) is None
        snap.release()
    finally:
        db.close()


def test_range_tombstone_survives_reopen(tmp_db_dir):
    db = _db(tmp_db_dir, wal_mode="sync")
    try:
        db.put(b"a", b"1")
        db.put(b"m", b"1")
        db.delete_range(b"a", b"m")  # WAL-only: no flush before reopen
    finally:
        db.close()
    db = _db(tmp_db_dir, wal_mode="sync")
    try:
        assert db.get(b"a") is None
        assert db.get(b"m") == b"1"
    finally:
        db.close()


# ---------------------------------------------------------------------------
# differential harness (plain random — runs everywhere)
# ---------------------------------------------------------------------------

def test_differential_smoke(tmp_path):
    out = run_differential(examples=20, seed=900, n_ops=50)
    assert out["failures"] == [], out["failures"]


def test_differential_known_bad_seed(tmp_path):
    # the seed that caught the covering-tombstone bug stays pinned forever
    assert run_example(7000038, str(tmp_path), 60) == []


def test_concurrent_readers_never_tear(tmp_db_dir):
    """Cursors + gets race flush/compaction from another thread; every
    observed state must be internally consistent (no torn reads)."""
    db = _db(tmp_db_dir)
    errors = []
    stop = threading.Event()

    def churn():
        i = 0
        while not stop.is_set():
            db.put(f"k{i % 50:03d}".encode(), f"v{i}".encode() * 8)
            if i % 40 == 0:
                db.flush()
            i += 1

    t = threading.Thread(target=churn)
    t.start()
    try:
        for _ in range(30):
            with db.iterator() as cur:
                prev = None
                ok = cur.seek(b"")
                while ok:
                    if prev is not None and not (prev < cur.key):
                        errors.append(f"order violated: {prev} !< {cur.key}")
                    prev = cur.key
                    ok = cur.next()
    finally:
        stop.set()
        t.join()
        db.close()
    assert errors == []


# ---------------------------------------------------------------------------
# hypothesis stateful machine (skipped where hypothesis is absent)
# ---------------------------------------------------------------------------

if HAVE_HYPOTHESIS:

    _KEYS = st.sampled_from([f"k{i:02d}".encode() for i in range(16)])

    @settings(max_examples=25, stateful_step_count=30, deadline=None)
    class MVCCMachine(RuleBasedStateMachine):
        """Differential stateful test: every rule mutates both the engine
        and the model; the invariant re-checks full visible state at the
        latest read point and at every live snapshot."""

        @initialize()
        def setup(self):
            import tempfile

            self._dir = tempfile.mkdtemp(prefix="mvccsm_")
            self.db = _db(self._dir + "/db")
            self.model = ModelDB()
            self.snaps = []

        def teardown(self):
            for s, _ in self.snaps:
                s.release()
            self.db.close()
            import shutil

            shutil.rmtree(self._dir, ignore_errors=True)

        @rule(k=_KEYS, v=st.binary(min_size=1, max_size=200))
        def put(self, k, v):
            self.db.put(k, v)
            self.model.put(k, v)

        @rule(k=_KEYS)
        def delete(self, k):
            self.db.delete(k)
            self.model.delete(k)

        @rule(a=_KEYS, b=_KEYS)
        def delete_range(self, a, b):
            a, b = sorted((a, b))
            if a == b:
                b = b + b"\x00"
            self.db.delete_range(a, b)
            self.model.delete_range(a, b)

        @precondition(lambda self: len(self.snaps) < 3)
        @rule()
        def take_snapshot(self):
            self.snaps.append((self.db.snapshot(), self.model.snapshot()))

        @precondition(lambda self: self.snaps)
        @rule()
        def release_snapshot(self):
            s, _ = self.snaps.pop(0)
            s.release()

        @rule()
        def flush(self):
            self.db.flush()

        @rule()
        def compact(self):
            self.db.compact_all()

        @invariant()
        def states_agree(self):
            for snap, mseq in [(None, None)] + self.snaps:
                want = self.model.items_at(LATEST if mseq is None else mseq)
                got = []
                with self.db.iterator(snap) as cur:
                    ok = cur.seek(b"")
                    while ok:
                        got.append((cur.key, cur.value))
                        ok = cur.next()
                assert got == want, f"@{mseq}: {got} != {want}"

    TestMVCCMachine = MVCCMachine.TestCase
