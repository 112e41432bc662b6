"""BVLSM-backed distributed checkpoint store.

The paper's WAL-time separation, applied to training state (DESIGN.md §2):

* **big values** = tensor shard chunks (4 MiB) → BValue multi-queue
  parallel writers (one queue per file ≙ one writer per host at cluster
  scale, the NVMe-SQ analogue);
* **lightweight metadata** = the manifest record (tree structure, shapes,
  dtypes, logical shard axes, step, data-iterator cursor, RNG) — the
  Key-ValueOffset side, WAL-committed synchronously.

Commit protocol: shard chunks (async, parallel) → BValue flush barrier →
META record (sync WAL) → WAL flush. A checkpoint exists iff its META
record is durable, so a crash mid-write leaves only orphaned (unreferenced,
GC-able) values, never a torn checkpoint. Restore reads the newest META and
re-shards onto whatever mesh the restarted job has (elastic restart).

Incremental mode skips tensors whose content hash matches the previous
step's — LSM levels naturally hold the deltas and compaction consolidates.

Spans (``EngineStats.span``, the ``ckpt.*`` names of ``SPAN_NAMES``) time a
save and a restore from inside, each carrying the checkpoint's ``step``
and, per tensor, its ``leaf`` path. They record into the engine's
``EngineStats`` beside its own ``db.*`` spans; a store over a ``ShardedDB``
keeps an ``EngineStats`` of its own for them.
"""
from __future__ import annotations

import hashlib
import io
import time

import jax
import msgpack
import numpy as np

from repro.core import DB, DBConfig, KVStore
from repro.core.stats import EngineStats

CHUNK = 4 << 20  # 4 MiB value chunks (page-aligned batches downstream)


def content_hash(buf) -> str:
    """The manifest's per-tensor hash: blake2b-128 of the tensor's bytes
    (``bytes`` or any byte buffer, such as a ``uint8`` view of the array)."""
    return hashlib.blake2b(buf, digest_size=16).hexdigest()


def _leaf_paths(tree) -> list[tuple[str, object]]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [(jax.tree_util.keystr(kp), leaf) for kp, leaf in flat]


class BVCheckpointStore:
    def __init__(
        self,
        path: str,
        num_queues: int = 4,
        sync_values: bool = False,
        env=None,
        db: KVStore | None = None,
    ):
        """``db`` injects any :class:`~repro.core.api.KVStore` (a ``DB``
        or a ``ShardedDB``) — the store takes ownership (``close()``
        closes it) and ``path``/``num_queues``/``sync_values``/``env``
        are ignored. Default: a fresh single-engine ``DB`` at ``path``."""
        if db is None:
            cfg = DBConfig.bvlsm(
                wal_mode="sync",  # metadata commits are synchronous
                value_threshold=4096,
                num_bvalue_queues=num_queues,
                memtable_size=4 << 20,
                bvcache_bytes=16 << 20,
            )
            cfg.sync_flush_io = sync_values
            cfg.env = env  # pluggable filesystem (fault-injection tests)
            db = DB.open(path, cfg)
        self.db = db
        engine = getattr(db, "stats", None)
        self._stats = engine if isinstance(engine, EngineStats) else EngineStats()

    def span(self, name: str, **args):
        """A ``ckpt.*`` span, recorded where ``stats()["spans"]`` reads it."""
        return self._stats.span(name, **args)

    def _value_barrier(self) -> None:
        """Every async BValue write durable before a META record commits.
        Engine-aware fast path (per-queue flush, no memtable rotation)
        for ``DB``/``ShardedDB``; a generic KVStore pays a full flush."""
        engines = getattr(self.db, "shards", None)
        if engines is None:
            engines = [self.db]
        if all(hasattr(e, "bvalue") for e in engines):
            for e in engines:
                e.bvalue.flush()
        else:
            self.db.flush()

    # ------------------------------------------------------------------
    # save
    # ------------------------------------------------------------------
    def save(self, step: int, state, extra_meta: dict | None = None,
             prev_hashes: dict | None = None) -> dict:
        """Returns {path: (content_hash, src_step)} for incremental chaining —
        src_step is where the chunks PHYSICALLY live (chains of reuse keep
        pointing at the original writer).

        Each leaf is hashed and put as a ``uint8`` view of its host array,
        4 MiB slices of one ``memoryview``: no copy in Python. A leaf that
        is not C-contiguous takes one contiguous copy first. The counters
        ``ckpt_view_bytes`` and ``ckpt_copy_bytes`` of ``stats()`` count
        the leaves' bytes taken each way."""
        with self.span("ckpt.save", step=step):
            leaves = _leaf_paths(state)
            manifest = []
            hashes: dict[str, tuple] = {}
            reused = 0
            viewed = copied = 0
            for path, leaf in leaves:
                with self.span("ckpt.serialize", step=step, leaf=path):
                    arr = np.asarray(leaf)
                    if arr.flags.c_contiguous:
                        viewed += arr.nbytes
                    else:
                        arr = np.ascontiguousarray(arr)
                        copied += arr.nbytes
                    buf = memoryview(arr.reshape(-1).view(np.uint8))
                with self.span("ckpt.hash", step=step, leaf=path):
                    h = content_hash(buf)
                entry = {
                    "path": path,
                    "shape": list(arr.shape),
                    "dtype": str(arr.dtype),
                    "chunks": max(1, -(-len(buf) // CHUNK)),
                    "hash": h,
                }
                prev = prev_hashes.get(path) if prev_hashes else None
                if prev is not None and prev[0] == h:
                    entry["reuse_step"] = prev[1]  # original writer's step
                    hashes[path] = (h, prev[1])
                    reused += 1
                else:
                    with self.span("ckpt.put", step=step, leaf=path):
                        for ci in range(entry["chunks"]):
                            key = self._chunk_key(step, path, ci)
                            self.db.put(key, buf[ci * CHUNK : (ci + 1) * CHUNK])
                    hashes[path] = (h, step)
                manifest.append(entry)
            self._stats.add("ckpt_view_bytes", viewed)
            self._stats.add("ckpt_copy_bytes", copied)
            # barrier: every async BValue write durable before META commits
            with self.span("ckpt.barrier", step=step):
                self._value_barrier()
            meta = {
                "step": step,
                "time": time.time(),
                "manifest": manifest,
                "extra": extra_meta or {},
                "reused_tensors": reused,
            }
            with self.span("ckpt.commit", step=step):
                self.db.put(self._meta_key(step), msgpack.packb(meta, use_bin_type=True))
                self.db.flush()
            return hashes

    def _chunk_key(self, step: int, path: str, ci: int) -> bytes:
        return f"ckpt/{step:012d}/t{path}/c{ci:05d}".encode()

    def _meta_key(self, step: int) -> bytes:
        return f"meta/{step:012d}".encode()

    # ------------------------------------------------------------------
    # load
    # ------------------------------------------------------------------
    def steps(self) -> list[int]:
        return sorted(
            int(k[5:]) for k, _ in self.db.range(b"meta/", end=b"meta0")
        )

    def latest_step(self) -> int | None:
        s = self.steps()
        return s[-1] if s else None

    def load_meta(self, step: int) -> dict:
        raw = self.db.get(self._meta_key(step))
        if raw is None:
            raise KeyError(f"no checkpoint at step {step}")
        return msgpack.unpackb(raw, raw=False)

    def load(self, step: int | None = None, template=None):
        """Returns (state_pytree_of_np, meta). With `template`, the result
        keeps its tree structure; otherwise a {path: array} dict."""
        if step is None:
            step = self.latest_step()
            if step is None:
                raise KeyError("no checkpoints")
        with self.span("ckpt.load_meta", step=step):
            meta = self.load_meta(step)
        arrays: dict[str, np.ndarray] = {}
        for ent in meta["manifest"]:
            path, src_step = ent["path"], ent.get("reuse_step", step)
            with self.span("ckpt.read", step=step, leaf=path):
                parts = []
                for ci in range(ent["chunks"]):
                    buf = self.db.get(self._chunk_key(src_step, path, ci))
                    if buf is None:
                        raise IOError(f"missing chunk {path}#{ci} @ step {src_step}")
                    parts.append(buf)
            with self.span("ckpt.join", step=step, leaf=path):
                raw = b"".join(parts)
                arrays[path] = np.frombuffer(raw, dtype=ent["dtype"]).reshape(ent["shape"])
        if template is None:
            return arrays, meta
        flat, treedef = jax.tree_util.tree_flatten_with_path(template)
        leaves = [arrays[jax.tree_util.keystr(kp)] for kp, _ in flat]
        return jax.tree_util.tree_unflatten(treedef, leaves), meta

    def load_distributed(self, mesh, template, axes_tree, step: int | None = None):
        """Elastic restore: load host arrays and re-shard onto `mesh`
        (which may differ from the mesh the checkpoint was written on)."""
        from repro.dist import tree_shardings

        state, meta = self.load(step, template=template)
        sds = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), state)
        shardings = tree_shardings(mesh, sds, axes_tree)
        with self.span("ckpt.place", step=meta["step"]):
            out = jax.tree.map(lambda a, s: jax.device_put(a, s), state, shardings)
        return out, meta

    # ------------------------------------------------------------------
    # retention
    # ------------------------------------------------------------------
    def delete_step(self, step: int) -> None:
        self.load_meta(step)  # raises KeyError if the step doesn't exist
        # one range tombstone covers every chunk the step physically owns
        # (reused chunks live under their writer's prefix, outside this
        # range) — constant WAL traffic instead of one delete per chunk
        prefix = f"ckpt/{step:012d}/".encode()
        self.db.delete_range(prefix, prefix + b"\xff")
        self.db.delete(self._meta_key(step))

    # ------------------------------------------------------------------
    # online backup
    # ------------------------------------------------------------------
    def backup(self, directory: str, base: str | None = None) -> str:
        """Hard-link an online, crash-consistent image of the whole store
        into ``directory`` (``DB.checkpoint``): every committed training
        checkpoint in it, openable as a ``BVCheckpointStore`` — without
        pausing in-flight saves. ``base`` (a previous backup directory)
        makes the image incremental: files already present in the base are
        hard-linked from it instead of from the live store. Returns
        ``directory``."""
        if base is None:
            self.db.checkpoint(directory)
        else:  # incremental images are a single-DB feature
            self.db.checkpoint(directory, base=base)
        return directory

    def stats(self) -> dict:
        """The engine's ``stats()``; over a ``ShardedDB`` (each shard's
        ``db.*`` spans under ``per_shard``) with this store's ``ckpt.*``
        spans under ``spans`` and its ``ckpt_*_bytes`` counters beside
        them."""
        out = self.db.stats()
        if self._stats is not getattr(self.db, "stats", None):
            out["spans"] = self._stats.spans()
            out.update((k, v) for k, v in self._stats.snapshot().items() if k.startswith("ckpt_"))
        return out

    def close(self) -> None:
        self.db.close()
