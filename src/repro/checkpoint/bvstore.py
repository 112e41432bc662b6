"""BVLSM-backed distributed checkpoint store.

The paper's WAL-time separation, applied to training state (DESIGN.md §2):

* **big values** = tensor shard chunks (4 MiB) → BValue multi-queue
  parallel writers (one queue per file ≙ one writer per host at cluster
  scale, the NVMe-SQ analogue);
* **lightweight metadata** = the manifest record (tree structure, shapes,
  dtypes, logical shard axes, step, data-iterator cursor, RNG) — the
  Key-ValueOffset side, WAL-committed synchronously.

A leaf is saved shard by shard, as the devices hold it: each addressable
shard with ``replica_id == 0`` (so a replicated leaf once), none of them
assembled whole on the host. Its manifest entry keeps the global ``shape``,
``dtype`` and ``path`` and a list of ``shards``, each with its ``index``
(``[start, stop]`` per dimension), ``chunks`` and content ``hash``; a
shard's chunks are keyed ``ckpt/<step>/t<path>/s<starts>/c<ci>``, and a
shard that covers the whole leaf (a single-device leaf, a replicated one)
keeps the key a whole leaf had before shards existed,
``ckpt/<step>/t<path>/c<ci>``. An entry written before shards existed (no
``shards``; ``chunks`` and ``hash`` of the whole leaf) reads as that one
shard.

Commit protocol: shard chunks (async, parallel) → BValue flush barrier →
META record (sync WAL) → WAL flush. A checkpoint exists iff its META
record is durable, so a crash mid-write leaves only orphaned (unreferenced,
GC-able) values, never a torn checkpoint.

Restore reads the newest META and reshards onto whatever mesh the restarted
job has (elastic restart): for each target device it finds the saved shards
that overlap the device's index, reads each chunk it needs once, and copies
the chunk's overlap straight into a host buffer per device, which is then
placed on its device before the next leaf is read; no leaf is built whole
on the host. A restore with no mesh (``load``) is the case of one target:
the whole leaf, kept on the host or, with ``place``, placed on the default
device the same way.

Incremental mode skips shards whose index and content hash match the
previous step's — LSM levels naturally hold the deltas and compaction
consolidates.

Spans (``EngineStats.span``, the ``ckpt.*`` names of ``SPAN_NAMES``) time a
save and a restore from inside, each carrying the checkpoint's ``step``
and, per tensor, its ``leaf`` path. They record into the engine's
``EngineStats`` beside its own ``db.*`` spans; a store over a ``ShardedDB``
keeps an ``EngineStats`` of its own for them.
"""
from __future__ import annotations

import hashlib
import math
import time
from dataclasses import dataclass

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


# A box of an array: (start, stop) per dimension; () for a scalar.
Box = tuple[tuple[int, int], ...]

# The store's counters in ``stats()``, each present from the store's open.
COUNTERS = ("ckpt_view_bytes", "ckpt_copy_bytes", "ckpt_shards_saved",
            "ckpt_replica_bytes_skipped", "ckpt_read_bytes", "ckpt_place_bytes")


def _whole(shape) -> Box:
    return tuple((0, int(n)) for n in shape)


def _box(index, shape) -> Box:
    """The box of a shard's ``index`` (a tuple of slices) in an array of ``shape``."""
    return tuple(sl.indices(int(n))[:2] for sl, n in zip(index, shape))


def _extent(box: Box) -> tuple[int, ...]:
    return tuple(b - a for a, b in box)


def _meet(a: Box, b: Box) -> Box | None:
    """The intersection of two boxes, or None where they do not overlap."""
    out = tuple((max(a0, b0), min(a1, b1)) for (a0, a1), (b0, b1) in zip(a, b))
    return out if all(lo < hi for lo, hi in out) else None


def _slices(box: Box, origin: Box) -> tuple[slice, ...]:
    """``box`` as slices into an array whose corner is ``origin``'s."""
    return tuple(slice(a - o, b - o) for (a, b), (o, _) in zip(box, origin))


def _pieces(shape: tuple[int, ...], e0: int, e1: int, off: int = 0) -> list[tuple[Box, int]]:
    """The flat elements [e0, e1) of a C-order array of ``shape`` as boxes,
    each with the offset of its first element from ``e0`` (plus ``off``):
    a partial first row, a block of whole rows, a partial last row, the
    partial rows split the same way one dimension down."""
    if e1 <= e0:
        return []
    if not shape:
        return [((), off)]
    inner = math.prod(shape[1:])
    rest = tuple((0, n) for n in shape[1:])
    out = []
    r, c = divmod(e0, inner)
    if c:
        stop = min(e1, (r + 1) * inner)
        out += [(((r, r + 1),) + b, o) for b, o in _pieces(shape[1:], c, stop - r * inner, off)]
        off, e0, r = off + stop - e0, stop, r + 1
    rows = (e1 - e0) // inner
    if rows:
        out.append((((r, r + rows),) + rest, off))
        off, e0, r = off + rows * inner, e0 + rows * inner, r + rows
    if e0 < e1:
        out += [(((r, r + 1),) + b, o) for b, o in _pieces(shape[1:], 0, e1 - e0, off)]
    return out


def _shard_tag(box: Box, shape) -> str:
    """A shard's part of its chunk keys: none for a shard of the whole leaf,
    else its start per dimension."""
    if box == _whole(shape):
        return ""
    return "s" + "_".join(str(a) for a, _ in box) + "/"


def _saved_shards(ent: dict) -> list[dict]:
    """A manifest entry's shards; an entry written before shards existed is
    one shard of the whole leaf."""
    if "shards" in ent:
        return ent["shards"]
    shard = {"index": [[0, n] for n in ent["shape"]], "chunks": ent["chunks"], "hash": ent["hash"]}
    if "reuse_step" in ent:
        shard["reuse_step"] = ent["reuse_step"]
    return [shard]


def reuse_steps(ent: dict) -> set[int]:
    """The older steps whose chunks a manifest entry's shards point at."""
    return {sh["reuse_step"] for sh in _saved_shards(ent) if "reuse_step" in sh}


@dataclass(eq=False)
class HostShards:
    """A leaf's host copy, shard by shard (a pytree leaf): its global shape
    and dtype, and each saved shard's box with its host array."""

    shape: tuple[int, ...]
    dtype: np.dtype
    shards: list[tuple[Box, np.ndarray]]


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
        for name in COUNTERS:
            self._stats.add(name, 0)

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
    def snapshot(self, state, step: int | None = None):
        """``state`` copied to the host shard by shard. A device array's
        addressable shards with ``replica_id == 0`` are copied, one
        ``ckpt.shard_snapshot`` span each (the other replicas' bytes count
        in ``ckpt_replica_bytes_skipped``): one shard of the whole array
        gives its host array, several a :class:`HostShards`. Any other
        leaf is kept as it is."""
        flat, treedef = jax.tree_util.tree_flatten_with_path(state)
        out = []
        for kp, leaf in flat:
            if not isinstance(leaf, jax.Array):
                out.append(leaf)
                continue
            path, shards, skipped = jax.tree_util.keystr(kp), [], 0
            for sh in leaf.addressable_shards:
                box = _box(sh.index, leaf.shape)
                if sh.replica_id != 0:
                    skipped += math.prod(_extent(box)) * leaf.dtype.itemsize
                    continue
                with self.span("ckpt.shard_snapshot", step=step, leaf=path):
                    shards.append((box, np.asarray(sh.data)))
            self._stats.add("ckpt_replica_bytes_skipped", skipped)
            if len(shards) == 1 and shards[0][0] == _whole(leaf.shape):
                out.append(shards[0][1])
            else:
                out.append(HostShards(tuple(leaf.shape), np.dtype(leaf.dtype), shards))
        return jax.tree_util.tree_unflatten(treedef, out)

    def save(self, step: int, state, extra_meta: dict | None = None,
             prev_hashes: dict | None = None) -> dict:
        """Save ``state`` (host arrays and :class:`HostShards`, as
        ``snapshot`` makes it; device arrays are snapshotted here) as
        checkpoint ``step``; a host array is one shard of the whole leaf.

        Returns {path: {box: (content_hash, src_step)}} for incremental
        chaining — src_step is where the shard's chunks PHYSICALLY live
        (chains of reuse keep pointing at the original writer); a shard is
        reused when its box and hash match ``prev_hashes``'.

        Each shard is hashed and put as a ``uint8`` view of its host array,
        4 MiB slices of one ``memoryview``: no copy in Python. A shard that
        is not C-contiguous takes one contiguous copy first. The counters
        ``ckpt_view_bytes`` and ``ckpt_copy_bytes`` of ``stats()`` count
        the shards' bytes taken each way, ``ckpt_shards_saved`` the shards
        entered in the manifest."""
        with self.span("ckpt.save", step=step):
            flat = _leaf_paths(state)
            if any(isinstance(leaf, jax.Array) for _, leaf in flat):
                flat = _leaf_paths(self.snapshot(state, step))
            manifest = []
            hashes: dict[str, dict] = {}
            reused = 0
            viewed = copied = 0
            for path, leaf in flat:
                if not isinstance(leaf, HostShards):
                    arr = np.asarray(leaf)
                    leaf = HostShards(arr.shape, arr.dtype, [(_whole(arr.shape), arr)])
                prev = prev_hashes.get(path, {}) if prev_hashes else {}
                hashes[path], shards = {}, []
                for box, arr in leaf.shards:
                    with self.span("ckpt.serialize", step=step, leaf=path):
                        if arr.flags.c_contiguous:
                            viewed += arr.nbytes
                        else:
                            arr = np.ascontiguousarray(arr)
                            copied += arr.nbytes
                        buf = memoryview(arr.reshape(-1).view(np.uint8))
                    with self.span("ckpt.hash", step=step, leaf=path):
                        h = content_hash(buf)
                    shard = {"index": [list(ab) for ab in box],
                             "chunks": max(1, -(-len(buf) // CHUNK)), "hash": h}
                    src = prev.get(box)
                    if src is not None and src[0] == h:
                        shard["reuse_step"] = src[1]  # original writer's step
                        hashes[path][box] = src
                        reused += 1
                    else:
                        tag = _shard_tag(box, leaf.shape)
                        with self.span("ckpt.put", step=step, leaf=path):
                            for ci in range(shard["chunks"]):
                                key = self._chunk_key(step, path, ci, tag)
                                self.db.put(key, buf[ci * CHUNK : (ci + 1) * CHUNK])
                        hashes[path][box] = (h, step)
                    shards.append(shard)
                manifest.append({"path": path, "shape": list(leaf.shape),
                                 "dtype": str(leaf.dtype), "shards": shards})
                self._stats.add("ckpt_shards_saved", len(shards))
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
                "reused_shards": reused,
            }
            with self.span("ckpt.commit", step=step):
                self.db.put(self._meta_key(step), msgpack.packb(meta, use_bin_type=True))
                self.db.flush()
            return hashes

    def _chunk_key(self, step: int, path: str, ci: int, tag: str = "") -> bytes:
        """Chunk ``ci`` of a shard (``tag`` from ``_shard_tag``; none for
        a whole leaf) of the leaf at ``path``, written by ``step``."""
        return f"ckpt/{step:012d}/t{path}/{tag}c{ci:05d}".encode()

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

    def _newest(self, step: int | None) -> int:
        if step is None:
            step = self.latest_step()
            if step is None:
                raise KeyError("no checkpoints")
        return step

    def _read_leaf(self, step: int, ent: dict, boxes: list[Box]) -> list[np.ndarray]:
        """Host buffers of ``boxes`` of the leaf of manifest entry ``ent``,
        filled from the saved shards' chunks: each chunk that overlaps a box
        is read once (``ckpt.read``) and its overlap copied into every box it
        meets (``ckpt.scatter``). Raises ``IOError`` for a chunk missing or of
        the wrong size, or boxes the saved shards do not cover exactly."""
        path, shape, dtype = ent["path"], tuple(ent["shape"]), np.dtype(ent["dtype"])
        bufs = [np.empty(_extent(b), dtype) for b in boxes]
        filled = [0] * len(boxes)
        per_chunk = CHUNK // dtype.itemsize
        for shard in _saved_shards(ent):
            sbox = tuple(tuple(ab) for ab in shard["index"])
            if all(_meet(sbox, b) is None for b in boxes):
                continue
            sshape, size = _extent(sbox), math.prod(_extent(sbox))
            src_step, tag = shard.get("reuse_step", step), _shard_tag(sbox, shape)
            for ci in range(shard["chunks"]):
                e0, e1 = ci * per_chunk, min(size, (ci + 1) * per_chunk)
                copies = []  # (box in the leaf, its offset in the chunk, target, overlap)
                for local, off in _pieces(sshape, e0, e1):
                    piece = tuple((a + s0, b + s0) for (a, b), (s0, _) in zip(local, sbox))
                    for j, target in enumerate(boxes):
                        part = _meet(piece, target)
                        if part is not None:
                            copies.append((piece, off, j, part))
                if not copies:
                    continue
                with self.span("ckpt.read", step=step, leaf=path):
                    raw = self.db.get(self._chunk_key(src_step, path, ci, tag))
                if raw is None or len(raw) != (e1 - e0) * dtype.itemsize:
                    raise IOError(f"missing or torn chunk {path}#{tag}{ci} @ step {src_step}")
                self._stats.add("ckpt_read_bytes", len(raw))
                with self.span("ckpt.scatter", step=step, leaf=path):
                    elems = np.frombuffer(raw, dtype)
                    for piece, off, j, part in copies:
                        block = elems[off : off + math.prod(_extent(piece))].reshape(_extent(piece))
                        bufs[j][_slices(part, boxes[j])] = block[_slices(part, piece)]
                        filled[j] += math.prod(_extent(part))
        for j, b in enumerate(boxes):
            if filled[j] != math.prod(_extent(b)):
                raise IOError(f"checkpoint @ step {step}: the saved shards of {path} "
                              f"cover {filled[j]} of the {math.prod(_extent(b))} elements of {b}")
        return bufs

    def _restore_leaf(self, step: int, ent: dict, targets: list, sharding=None):
        """The leaf of manifest entry ``ent`` read (``_read_leaf``) into one
        host buffer per ``(device, box)`` of ``targets``, each buffer placed
        on its device (``ckpt.place``, ``ckpt_place_bytes``) before the next
        leaf is read, so the transfer runs beside that read. With
        ``sharding`` the leaf is assembled from the parts with
        ``jax.make_array_from_single_device_arrays``; without, ``targets``
        is the whole leaf on device ``None``, the default device,
        uncommitted."""
        path = ent["path"]
        bufs = self._read_leaf(step, ent, [box for _, box in targets])
        with self.span("ckpt.place", step=step, leaf=path):
            parts = [jax.device_put(buf, d) for (d, _), buf in zip(targets, bufs)]
            out = parts[0] if sharding is None else jax.make_array_from_single_device_arrays(
                tuple(ent["shape"]), sharding, parts)
        self._stats.add("ckpt_place_bytes", sum(b.nbytes for b in bufs))
        return out

    def load(self, step: int | None = None, template=None, place: bool = False):
        """Returns (state_pytree, meta). With `template`, the result keeps
        its tree structure; otherwise a {path: array} dict. Each leaf is
        read into one host array of its whole shape, whatever the shards it
        was saved in. A leaf is an ``np.ndarray``; with ``place``, a
        ``jax.Array`` on the default device, placed as soon as it is read
        (``_restore_leaf``)."""
        step = self._newest(step)
        with self.span("ckpt.load_meta", step=step):
            meta = self.load_meta(step)
        arrays = {}
        for ent in meta["manifest"]:
            box = _whole(ent["shape"])
            arrays[ent["path"]] = (self._restore_leaf(step, ent, [(None, box)]) if place
                                   else self._read_leaf(step, ent, [box])[0])
        if template is None:
            return arrays, meta
        flat, treedef = jax.tree_util.tree_flatten_with_path(template)
        leaves = [arrays[jax.tree_util.keystr(kp)] for kp, _ in flat]
        return jax.tree_util.tree_unflatten(treedef, leaves), meta

    def load_distributed(self, mesh, template, axes_tree, step: int | None = None):
        """Elastic restore onto `mesh` (which may differ from the mesh the
        checkpoint was written on), under ``axes_tree``'s shardings: each
        leaf is read into one host buffer per addressable device (replicas
        included), each buffer placed on its device and the leaf assembled
        (``_restore_leaf``). No leaf is built whole on the host unless a
        device holds it whole."""
        from repro.dist import tree_shardings

        step = self._newest(step)
        with self.span("ckpt.load_meta", step=step):
            meta = self.load_meta(step)
        entries = {ent["path"]: ent for ent in meta["manifest"]}
        flat, treedef = jax.tree_util.tree_flatten_with_path(template)
        shardings = jax.tree.leaves(tree_shardings(mesh, template, axes_tree))
        out = []
        for (kp, sds), sharding in zip(flat, shardings):
            path = jax.tree_util.keystr(kp)
            ent = entries[path]
            shape = tuple(sds.shape)
            if tuple(ent["shape"]) != shape or np.dtype(ent["dtype"]) != np.dtype(sds.dtype):
                raise ValueError(f"checkpoint @ step {step}: {path} is {ent['dtype']}"
                                 f"{ent['shape']}, the template's {sds.dtype}{list(shape)}")
            devices = sharding.addressable_devices_indices_map(shape).items()
            targets = [(d, _box(idx, shape)) for d, idx in devices]
            out.append(self._restore_leaf(step, ent, targets, sharding))
        return jax.tree_util.tree_unflatten(treedef, out), meta

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
