"""Engine statistics: byte counters per I/O class (wal / flush / compaction /
bvalue), stall accounting, and a table of timed spans.

``write_amp`` = total device bytes / user payload bytes — the paper's core
metric.

Group-commit accounting (write pipeline): ``record_group`` tracks a
power-of-two histogram of writers-per-group, and ``fsyncs_per_write``
(= (wal_fsyncs + bvalue_fsyncs) / user_writes) measures how well the
leader/follower commit amortizes durability barriers — 1.0 means every
write paid its own fsync; well-batched sync workloads sit far below 0.5.

Pipelined-commit accounting (write pipeline v2): ``record_pipeline_depth``
histograms the number of commit groups in flight at group-formation time
(a max > 1 proves fsync/encode overlap actually happened), and the
``gauges`` dict carries the adaptive controller's live state —
``wal_group_effective_bytes`` (current latency-targeted byte cap) and
``wal_persist_ewma_s`` (smoothed group persist latency). ``wal_fsync_skips``
counts groups whose durability was covered by a later-started fsync.

Spans (``EngineStats.span``): named, timed regions of the store, its
background jobs and the checkpoint layer above it, all named in
``SPAN_NAMES``. Each name's row holds the count, total, self and longest
seconds and a reservoir of durations; ``snapshot()["spans"]`` exports it.
While a ``jax.profiler`` trace is being taken (and only if the process has
imported JAX already: this package never imports it) a span also enters a
``jax.profiler.TraceAnnotation``, so it lands on its thread's line of the
trace's host plane, on the device ops' clock.
"""
from __future__ import annotations

import contextlib
import random
import sys
import threading
import time
from collections import defaultdict

# Every span the program records, by layer; parents before their children.
SPAN_NAMES = (
    # checkpoint manager, on the train loop's thread: per shard device-to-host
    "ckpt.wait", "ckpt.snapshot", "ckpt.shard_snapshot",
    # checkpoint store, on the save thread: per shard serialize/hash/put
    "ckpt.save", "ckpt.serialize", "ckpt.hash", "ckpt.put",
    "ckpt.barrier", "ckpt.commit",
    # restore, on the loop's thread: per chunk read/scatter, per leaf placement
    "ckpt.restore", "ckpt.load_meta", "ckpt.read", "ckpt.scatter", "ckpt.place",
    # store engine: the big-value path only (a span costs a few µs, a
    # tenth of a small put or a cached get)
    "db.put", "wal.fsync",
    "bvalue.write", "bvalue.pwrite", "bvalue.fsync",
    "bvalue.pread",
    # background jobs, one per JobScheduler job kind
    "engine.flush", "engine.compaction", "engine.gc", "engine.repl_apply", "engine.scrub",
)
_SPAN_SET = frozenset(SPAN_NAMES)
_JOB_PREFIX = "engine."
_RESERVOIR = 10_000  # samples kept per stream (stall events, each span name)
_NULL = contextlib.nullcontext()
# the open spans of each thread, innermost last, whichever EngineStats they
# record into: a span's self time excludes every child on its thread
_open = threading.local()
_clock = time.perf_counter


def no_span(name: str, **args) -> contextlib.nullcontext:
    """``EngineStats.span``'s stand-in for a component built without stats."""
    return _NULL


def _keep_sample(samples: list[float], seen: int, x: float) -> None:
    """Reservoir sampling: after ``seen`` values (``x`` included), every
    one of them is in ``samples`` with the same probability."""
    if len(samples) < _RESERVOIR:
        samples.append(x)
    else:
        j = int(random.random() * seen)  # randrange(seen), at a fifth the cost
        if j < _RESERVOIR:
            samples[j] = x


def _quantile(samples: list[float], q: float) -> float:
    if not samples:
        return 0.0
    s = sorted(samples)
    return s[min(len(s) - 1, int(len(s) * q))]


class _SpanRow:
    __slots__ = ("count", "seconds", "self_seconds", "max_seconds", "samples")

    def __init__(self) -> None:
        self.count = 0
        self.seconds = 0.0
        self.self_seconds = 0.0
        self.max_seconds = 0.0
        self.samples: list[float] = []


class _Span:
    """One timed region; see ``EngineStats.span``."""

    __slots__ = ("stats", "name", "args", "t0", "child_s", "annotation")

    def __init__(self, stats: "EngineStats", name: str, args: dict) -> None:
        if name not in _SPAN_SET:
            raise ValueError(f"span {name!r} is not in SPAN_NAMES")
        self.stats, self.name, self.args = stats, name, args
        self.child_s = 0.0
        self.annotation = None

    def __enter__(self) -> "_Span":
        profiler = sys.modules.get("jax.profiler")
        if profiler is not None and profiler.TraceAnnotation.is_enabled():
            self.annotation = profiler.TraceAnnotation(self.name, **self.args)
            self.annotation.__enter__()
        try:
            _open.stack.append(self)
        except AttributeError:
            _open.stack = [self]
        self.t0 = _clock()
        return self

    def __exit__(self, *exc) -> None:
        dur = _clock() - self.t0
        stack = _open.stack
        stack.pop()
        if stack:
            stack[-1].child_s += dur
        if self.annotation is not None:
            self.annotation.__exit__(*exc)
        self.stats._record_span(self.name, dur, dur - self.child_s)


class EngineStats:
    """Thread-safe engine counters; read a consistent copy via ``snapshot()``.

    Counter names (``snapshot()`` keys; all monotonic):

    * ``user_writes`` / ``user_bytes`` — acknowledged entries / payload
    * ``wal_bytes`` / ``wal_records`` / ``wal_fsyncs`` — WAL I/O;
      ``wal_fsync_skips`` — groups covered by a later-started fsync
    * ``bvalue_bytes`` / ``bvalue_fsyncs`` — BValue store I/O
    * ``flush_bytes`` / ``flush_count`` — MemTable→L0 flushes
    * ``compaction_bytes`` / ``compaction_read_bytes`` / ``compaction_count``
    * ``trivial_moves`` / ``trivial_move_bytes`` — no-overlap files promoted
      by manifest edit alone (zero rewrite); ``compaction_bytes_written`` /
      ``user_bytes_written`` — aliases of ``compaction_bytes`` /
      ``user_bytes`` (the write-amp benchmark's canonical names)
    * ``gc_slices`` — auto-GC passes that yielded early on the slice budget
    * ``group_commits`` / ``group_writers`` / ``group_entries`` — group
      commit totals; ``memtable_shard_applies`` — groups applied sharded
    * ``job_{flush,compaction,gc,...}_count`` (+ the ``jobs`` table with
      wall seconds per kind; both read the ``engine.<kind>`` spans) —
      background scheduler jobs; ``subcompactions`` — key-range shards
      fanned out by partitioned compactions
    * ``rate_limiter_waits`` / ``rate_limiter_wait_seconds`` — background
      I/O token-bucket backpressure; ``rate_limiter_fg_bytes`` — foreground
      value-log bytes charged to the unified budget (accounted, never
      blocked)
    * ``wal_truncated_bytes`` — torn WAL tail bytes truncated at recovery
    * ``bg_retries`` — transient background-job errors retried with backoff;
      ``bg_errors_hard`` / ``bg_errors_transient_exhausted`` — errors that
      latched the DB read-only; ``resumes`` — successful ``DB.resume()``
      calls clearing the latch
    * ``corruptions_detected`` / ``files_quarantined`` — CRC-verified reads
      that failed and the files quarantined for it
    * ``ckpt_view_bytes`` / ``ckpt_copy_bytes`` — checkpoint leaves' bytes
      a ``BVCheckpointStore.save`` hashed and put from a view of the host
      array / from the one contiguous copy a non-contiguous leaf takes
    * ``stall_stop_seconds`` / ``stall_delay_seconds`` — hard stops vs
      delayed-write-controller delays; ``stall_hist`` (pow2 ms bucket →
      count) and ``stall_p99_ms`` — the stall tail
    * ``block_cache_hits`` / ``block_cache_misses`` /
      ``block_cache_evictions`` / ``block_cache_bytes`` /
      ``block_cache_entries`` / ``block_cache_hit_rate`` — shared block
      cache (pulled live from the registered BlockCache; all-zero when the
      cache is disabled). Every ratio in ``snapshot()`` reads 0.0 on a
      fresh DB rather than dividing by zero.

    Derived (properties, also in ``snapshot()``): ``device_bytes``,
    ``write_amp``, ``fsyncs_per_write``, ``avg_group_size``,
    ``pipeline_depth_max``. Structures: ``group_size_hist`` (pow2 bucket →
    count), ``pipeline_depth_hist`` (depth → count), ``gauges`` (last-value,
    e.g. ``wal_group_effective_bytes`` / ``wal_persist_ewma_s``),
    stall accounting (``stall_seconds`` / ``stall_events``), and ``spans``
    (name → ``count``, ``seconds``, ``self_seconds`` (without the child
    spans on its thread), ``max_seconds``, ``p50_ms``, ``p99_ms``).
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.counters: dict[str, int] = defaultdict(int)
        self.stall_seconds = 0.0
        self.stall_events = 0
        self.group_size_hist: dict[int, int] = defaultdict(int)  # pow2 bucket -> count
        self.pipeline_depth_hist: dict[int, int] = defaultdict(int)  # depth -> count
        self.stall_hist: dict[int, int] = defaultdict(int)  # pow2 ms bucket -> count
        self._stall_samples: list[float] = []  # capped reservoir for p99
        self._spans: dict[str, _SpanRow] = {}
        self.gauges: dict[str, float] = {}  # last-value gauges (adaptive caps, ...)
        self._block_cache = None  # BlockCache; its counters merge into snapshot()

    def register_block_cache(self, cache) -> None:
        """Attach the DB's shared BlockCache so ``snapshot()`` carries its
        hit/miss/eviction counters (the cache keeps them shard-local for
        lock-free-ish reads; we pull on demand instead of pushing per-get)."""
        self._block_cache = cache

    def add(self, name: str, n: int = 1) -> None:
        with self._lock:
            self.counters[name] += n

    def add_stall(self, seconds: float, kind: str = "stall") -> None:
        """One writer stall/delay event. ``kind`` splits hard stops from
        controller delays (``stall_stop_seconds`` / ``stall_delay_seconds``)
        and every event lands in the pow2-millisecond ``stall_hist`` plus a
        capped sample reservoir feeding ``stall_p99_ms``."""
        with self._lock:
            self.stall_seconds += seconds
            self.stall_events += 1
            self.counters[f"stall_{kind}_seconds"] += seconds
            ms = seconds * 1e3
            self.stall_hist[1 << max(0, int(ms).bit_length())] += 1
            # a true reservoir: stall_p99_ms reflects the whole run, not
            # just its first 10k events
            _keep_sample(self._stall_samples, self.stall_events, seconds)

    def span(self, name: str, **args) -> _Span:
        """Context manager timing one region named in ``SPAN_NAMES``; ``args``
        (e.g. ``step=``, ``leaf=``) go to the profiler's annotation only."""
        return _Span(self, name, args)

    def _record_span(self, name: str, seconds: float, self_seconds: float) -> None:
        with self._lock:
            row = self._spans.get(name)
            if row is None:
                row = self._spans[name] = _SpanRow()
            row.count += 1
            row.seconds += seconds
            row.self_seconds += self_seconds
            if seconds > row.max_seconds:
                row.max_seconds = seconds
            _keep_sample(row.samples, row.count, seconds)

    def spans(self) -> dict[str, dict]:
        """The span table, name → its totals and duration quantiles."""
        with self._lock:
            rows = [(n, r.count, r.seconds, r.self_seconds, r.max_seconds, list(r.samples))
                    for n, r in self._spans.items()]
        return {
            n: {"count": c, "seconds": s, "self_seconds": own, "max_seconds": mx,
                "p50_ms": _quantile(smp, 0.5) * 1e3, "p99_ms": _quantile(smp, 0.99) * 1e3}
            for n, c, s, own, mx, smp in sorted(rows)
        }

    def stall_p99_ms(self) -> float:
        with self._lock:
            samples = list(self._stall_samples)
        return _quantile(samples, 0.99) * 1e3

    def mark_user_write(self, nbytes: int) -> None:
        self.mark_user_writes(1, nbytes)

    def mark_user_writes(self, count: int, nbytes: int) -> None:
        """Bulk ack: one lock acquisition per group."""
        with self._lock:
            self.counters["user_writes"] += count
            self.counters["user_bytes"] += nbytes

    def record_group(self, n_writers: int, n_entries: int) -> None:
        """One group commit: n_writers batches merged into one WAL write."""
        with self._lock:
            self.counters["group_commits"] += 1
            self.counters["group_writers"] += n_writers
            self.counters["group_entries"] += n_entries
            self.group_size_hist[1 << max(0, n_writers - 1).bit_length()] += 1

    def record_pipeline_depth(self, depth: int) -> None:
        """Commit groups in flight (incl. this one) when a group formed."""
        with self._lock:
            self.pipeline_depth_hist[depth] += 1

    def set_gauge(self, name: str, value: float) -> None:
        """Publish a last-value gauge (e.g. the adaptive group-size cap)."""
        with self._lock:
            self.gauges[name] = value

    @property
    def pipeline_depth_max(self) -> int:
        return max(self.pipeline_depth_hist, default=0)

    @property
    def device_bytes(self) -> int:
        c = self.counters
        return (
            c["wal_bytes"]
            + c["flush_bytes"]
            + c["compaction_bytes"]
            + c["bvalue_bytes"]
        )

    @property
    def write_amp(self) -> float:
        user = self.counters["user_bytes"]
        return self.device_bytes / user if user else 0.0

    @property
    def fsyncs_per_write(self) -> float:
        # fresh DB (zero writes) must read 0.0, never ZeroDivisionError
        writes = self.counters["user_writes"]
        syncs = self.counters["wal_fsyncs"] + self.counters["bvalue_fsyncs"]
        return syncs / writes if writes else 0.0

    @property
    def avg_group_size(self) -> float:
        groups = self.counters["group_commits"]
        return self.counters["group_writers"] / groups if groups else 0.0

    @property
    def block_cache_hit_rate(self) -> float:
        if self._block_cache is None:
            return 0.0
        return self._block_cache.stats()["block_cache_hit_rate"]

    def snapshot(self) -> dict:
        with self._lock:
            d = dict(self.counters)
            hist = dict(sorted(self.group_size_hist.items()))
            depth_hist = dict(sorted(self.pipeline_depth_hist.items()))
            stall_hist = dict(sorted(self.stall_hist.items()))
            gauges = dict(self.gauges)
        spans = self.spans()
        jobs = {
            name[len(_JOB_PREFIX):]: {"count": row["count"], "seconds": row["seconds"]}
            for name, row in spans.items() if name.startswith(_JOB_PREFIX)
        }
        for kind, job in jobs.items():
            d[f"job_{kind}_count"] = job["count"]
        for k in (
            "wal_bytes",
            "flush_bytes",
            "compaction_bytes",
            "bvalue_bytes",
            "user_bytes",
            "user_writes",
            "wal_fsyncs",
            "bvalue_fsyncs",
            "group_commits",
        ):
            d.setdefault(k, 0)
        d["device_bytes"] = self.device_bytes
        d["write_amp"] = self.write_amp
        d["stall_seconds"] = self.stall_seconds
        d["stall_events"] = self.stall_events
        d.setdefault("wal_fsync_skips", 0)
        d["fsyncs_per_write"] = self.fsyncs_per_write
        d["avg_group_size"] = self.avg_group_size
        d["group_size_hist"] = hist
        d["pipeline_depth_hist"] = depth_hist
        d["pipeline_depth_max"] = max(depth_hist, default=0)
        d["stall_hist"] = stall_hist
        d["stall_p99_ms"] = self.stall_p99_ms()
        d["jobs"] = jobs
        d["spans"] = spans
        d.setdefault("rate_limiter_waits", 0)
        d.setdefault("rate_limiter_wait_seconds", 0.0)
        d.setdefault("rate_limiter_fg_bytes", 0)
        d.setdefault("subcompactions", 0)
        d.setdefault("trivial_moves", 0)
        d.setdefault("trivial_move_bytes", 0)
        d.setdefault("gc_slices", 0)
        d.setdefault("wal_truncated_bytes", 0)
        d.setdefault("bg_retries", 0)
        d.setdefault("bg_errors_hard", 0)
        d.setdefault("bg_errors_transient_exhausted", 0)
        d.setdefault("corruptions_detected", 0)
        d.setdefault("files_quarantined", 0)
        for k in (
            "repl_batches_shipped",
            "repl_bytes_shipped",
            "repl_batches_applied",
            "repl_frames_corrupt",
            "repl_frames_duplicate",
            "repl_catchups",
            "repl_crc_checks",
            "repl_divergence_detected",
            "repl_rebootstraps",
            "repl_ship_errors",
            "repl_lag_warnings",
            "repl_wals_retained",
            "repl_value_fetch_misses",
            "promotions",
        ):
            d.setdefault(k, 0)
        d.setdefault("resumes", 0)
        # canonical names for the write-amp trajectory (BENCH_writeamp.json):
        # device bytes compaction wrote vs. bytes the user actually stored
        d["compaction_bytes_written"] = d["compaction_bytes"]
        d["user_bytes_written"] = d["user_bytes"]
        d["gauges"] = gauges
        if self._block_cache is not None:
            d.update(self._block_cache.stats())
        else:
            d.update(
                block_cache_hits=0, block_cache_misses=0, block_cache_evictions=0,
                block_cache_bytes=0, block_cache_entries=0, block_cache_hit_rate=0.0,
                block_cache_promotions=0, block_cache_ghost_hits=0,
                block_cache_a1_bytes=0,
            )
        return d

    # ``db.stats`` is this object (attribute access keeps working for every
    # existing caller); making it callable lets ``db.stats()`` satisfy the
    # KVStore protocol's ``stats() -> dict`` the same way ShardedDB's real
    # method does.
    __call__ = snapshot
