"""Async checkpoint manager: snapshots device state to the host shard by
shard (``BVCheckpointStore.snapshot``: each leaf's addressable shards, a
replicated one once, never gathered whole), hands the write to a
background thread (whose big values flow through the BValue multi-queue
writers), keeps the last N checkpoints, and exposes a preemption hook —
the trainer's SIGTERM handler calls ``save_now`` and the WAL-committed META
record makes the shutdown checkpoint crash-consistent.

The paper's I/O-jitter claim maps here: synchronous checkpointing stalls
the train loop for the full serialization+fsync time; BVLSM-async hides it
(benchmarks/stability.py measures both).
"""
from __future__ import annotations

import threading
import time

from .bvstore import BVCheckpointStore, reuse_steps


class CheckpointManager:
    def __init__(
        self,
        store: BVCheckpointStore,
        interval_steps: int = 100,
        keep_last: int = 3,
        async_save: bool = True,
        incremental: bool = True,
    ):
        self.store = store
        self.interval = interval_steps
        self.keep_last = keep_last
        self.async_save = async_save
        self.incremental = incremental
        self._prev_hashes: dict | None = None
        self._pending: threading.Thread | None = None
        self._pending_step: int | None = None
        self._lock = threading.Lock()
        self.save_count = 0
        self.stall_seconds = 0.0  # time the TRAIN LOOP was blocked
        self.save_seconds: list[float] = []  # store.save wall time, per save

    def maybe_save(self, step: int, state, extra_meta: dict | None = None) -> bool:
        if step % self.interval != 0:
            return False
        self.save_now(step, state, extra_meta)
        return True

    def save_now(self, step: int, state, extra_meta: dict | None = None) -> None:
        self.wait()  # one in-flight checkpoint at a time; wait() counts its stall
        t0 = time.monotonic()
        with self.store.span("ckpt.snapshot", step=step):
            host_state = self.store.snapshot(state, step)
        snapshot_s = time.monotonic() - t0

        def _write():
            prev = self._prev_hashes if self.incremental else None
            t_save = time.monotonic()
            hashes = self.store.save(step, host_state, extra_meta, prev_hashes=prev)
            with self._lock:
                self._prev_hashes = hashes
                self.save_count += 1
                self.save_seconds.append(time.monotonic() - t_save)
            self._retire(step)

        if self.async_save:
            self._pending = threading.Thread(target=_write, name=f"ckpt-{step}", daemon=True)
            self._pending_step = step
            self._pending.start()
            self.stall_seconds += snapshot_s  # loop only pays the snapshot
        else:
            _write()
            self.stall_seconds += time.monotonic() - t0

    def _retire(self, newest_step: int) -> None:
        steps = self.store.steps()
        # incremental checkpoints may reference older steps' chunks — only
        # retire steps no live checkpoint reuses
        keep = set(steps[-self.keep_last :])
        referenced = set()
        for s in keep:
            for ent in self.store.load_meta(s)["manifest"]:
                referenced |= reuse_steps(ent)
        for s in steps[: -self.keep_last]:
            if s not in referenced:
                try:
                    self.store.delete_step(s)
                except KeyError:
                    pass

    def backup(self, directory: str, base: str | None = None) -> str:
        """Durable offline copy of the store (e.g. before a risky restart):
        waits for the in-flight save so the image contains it, then
        hard-links the store into ``directory`` via ``DB.checkpoint``.
        ``base`` points at a previous backup to make this one incremental."""
        self.wait()
        return self.store.backup(directory, base=base)

    def wait(self) -> None:
        if self._pending is not None and self._pending.is_alive():
            t0 = time.monotonic()
            with self.store.span("ckpt.wait", step=self._pending_step):
                self._pending.join()
            self.stall_seconds += time.monotonic() - t0
        self._pending = None

    def close(self) -> None:
        self.wait()
