"""Traffic ``resume``: a training job restored from BVLSM, back to back.

Only the restore path runs: no train step, no write in the window. Set-up
makes the job's state from the seed on the device, takes its digest, and
saves it as one checkpoint through a ``Trainer``'s ``CheckpointManager``,
the way a job checkpoints before it is preempted. The window then repeats,
until ``--seconds`` have passed, one restore as a restarted job makes it:

1. the store's files evicted from the operating system's page cache
   (``posix_fadvise(DONTNEED)``, after an ``fsync``), so that reads come
   from the disk as after a preemption, and the last restore's objects
   freed and collected, so that every restore starts from the heap the
   first one found;
2. a new ``Trainer`` on the directory, which opens a new
   ``BVCheckpointStore`` (cold caches, WAL recovery paid);
3. ``Trainer._init_or_restore``, which loads the newest checkpoint onto
   the chip;
4. ``block_until_ready``.

Each restore's time runs from 2 to 4. After each, the restored state's
digest is taken on the device; once the window has closed every one is
compared with the digest of the state that was saved.
"""
from __future__ import annotations

import gc
import os
import shutil
import time

import jax
import numpy as np

from bench import digest as dg
from bench import jobstate
from bench.harness import memory_peak_bytes


def evict(directory: str) -> None:
    for dirpath, _, names in os.walk(directory):
        for name in names:
            fd = os.open(os.path.join(dirpath, name), os.O_RDONLY)
            try:
                os.fsync(fd)
                os.posix_fadvise(fd, 0, 0, os.POSIX_FADV_DONTNEED)
            finally:
                os.close(fd)


def run(run) -> dict:
    from repro.training import train_step as ts
    from repro.training.trainer import Trainer, TrainerConfig

    c, w, ref = run.config, run.workload, run.reference
    mcfg, model = jobstate.program_model(c, ref)
    opt_cfg = jobstate.optimizer_config(c)
    tmpl = jobstate.template(model, opt_cfg)
    key = jobstate.seed_key(run.seed)
    store_dir = os.path.join(run.scratch, "ckpt")
    tcfg = TrainerConfig(
        global_batch=w["batch"], seq_len=w["seq_len"], ckpt_dir=store_dir,
        keep_last=w["keep_last"], seed=run.seed & 0x7FFFFFFF, train=ts.TrainConfig(opt=opt_cfg),
    )
    span = run.span
    restores, digests = [], []
    try:
        state = jobstate.make_state(tmpl, c, ref, w["job_step"])(key)
        want = np.asarray(dg.digest(state))
        state_bytes = sum(x.nbytes for x in jax.tree.leaves(state))
        tr = Trainer(mcfg, tcfg)
        tr.pipeline.state.step = w["job_step"]
        tr.ckpt.save_now(w["job_step"], state, {"pipeline": tr.pipeline.state_dict()})
        tr.close()
        del state, tr
        gc.collect()

        run.open_window()
        while run.in_window():
            with span("evict"):
                gc.collect()
                evict(store_dir)
            t0 = time.monotonic()
            with span("store_open"):
                tr = Trainer(mcfg, tcfg)
            t_open = time.monotonic() - t0
            load = tr.store.load
            loads = []

            def timed_load(*a, **kw):
                t = time.monotonic()
                out = load(*a, **kw)
                loads.append(time.monotonic() - t)
                return out

            tr.store.load = timed_load
            with span("restore"):
                step = tr._init_or_restore()
            with span("device_wait"):
                jax.block_until_ready(tr.state)
            restores.append({"s": time.monotonic() - t0, "open_s": t_open, "load_s": sum(loads),
                             "step": step})
            with span("digest"):
                digests.append(dg.digest(tr.state))
                jax.block_until_ready(digests[-1])
            del tr.store.load  # the wrapper refers to the store: leave no cycle
            tr.state = None
            tr.close()
            del tr, load, timed_load  # freed here, not inside the next restore's time
        run.close_window()
        peak = memory_peak_bytes()
        digests = [np.asarray(d) for d in digests]
    finally:
        shutil.rmtree(store_dir, ignore_errors=True)

    bad = sum(dg.mismatches(d, want) for d in digests)
    bad += sum(r["step"] != w["job_step"] for r in restores)
    total_s = sum(r["s"] for r in restores)
    gib = state_bytes / 2**30
    return {
        "end_to_end": {"restore_gib_s": len(restores) * gib / total_s if restores else None},
        "counters": {"restores": len(restores), "gib_restored": len(restores) * gib,
                     "restore_s": total_s, "load_s": sum(r["load_s"] for r in restores)},
        "compared": {"restore_leaves_differing": {"value": float(bad), "limit": 0.0}},
        "attempted": len(restores),
        "failed": 0,
        "memory_peak_bytes": peak,
        "info": {"state_gib": gib, "restores": len(restores),
                 "restore_s": [r["s"] for r in restores], "open_s": [r["open_s"] for r in restores],
                 "load_s": [r["load_s"] for r in restores]},
    }
