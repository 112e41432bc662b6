"""Traffic ``elastic_resume``: a sharded training job saved on one mesh and
restored onto another, back to back.

The job is one pipeline stage whose chips share each layer. Before its
preemption the stage runs on the configuration's ``meshes.saved`` mesh; it
resumes on ``meshes.restored``, both under the program's sharding rules
(``Trainer._state_shardings``). Set-up:

1. the job's state at ``job_step`` made from the seed, under jit with the
   saved mesh's shardings as its out shardings, so that no leaf is ever
   whole on one device, and its digest;
2. one checkpoint of it through a saved-mesh ``Trainer``'s
   ``CheckpointManager`` (``save_now``, then ``wait``);
3. one train step on the saved mesh, jitted as ``Trainer.run`` jits it on a
   mesh (in and out shardings, state donated), on one batch; its loss kept.

The window then repeats, until ``--seconds`` have passed, one restore as a
job restarted on the new mesh makes it, the steps of ``resume``: the
store's files evicted from the page cache, a new ``Trainer`` on the
restored mesh (a new store, WAL recovery paid), ``_init_or_restore``,
``block_until_ready``; then the digest. Each restore's time runs from the
new ``Trainer`` to ``block_until_ready``. After the window the last
restored state takes the same train step on the restored mesh.

Compared: every restore's digest with the saved state's
(``restore_leaves_differing``, exact) and the restored mesh's loss with the
saved mesh's (``resume_loss_gap``, relative; the two meshes sum the same
products in another order).
"""
from __future__ import annotations

import gc
import os
import shutil
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import digest as dg
from bench import files, jobstate
from bench.harness import memory_peak_bytes

GIB = 2**30
READ_SPANS = ("ckpt.read", "ckpt.scatter", "ckpt.place")  # the program's, where it has them
READ_COUNTERS = ("ckpt_read_bytes", "ckpt_place_bytes")


def _peaks() -> list[int] | None:
    """Each device's peak of bytes in use so far, where the backend reports it."""
    stats = [d.memory_stats() for d in jax.devices()]
    return None if None in stats else [int(s.get("peak_bytes_in_use", 0)) for s in stats]


def _stepper(model, train_cfg, shardings):
    from repro.training.train_step import make_train_step

    return jax.jit(make_train_step(model, train_cfg), in_shardings=(shardings, None),
                   out_shardings=(shardings, None), donate_argnums=0)


def _loss(jitted, mesh, state, batch) -> float:
    from repro.dist import mesh_context

    with mesh_context(mesh):
        _, metrics = jitted(state, batch)
    return float(metrics["loss"])


def run(run) -> dict:
    from repro.dist import tree_shardings
    from repro.launch.mesh import make_host_mesh
    from repro.training import train_step as ts
    from repro.training.trainer import Trainer, TrainerConfig

    evict = files.load_module("traffic", "resume").evict
    c, w, ref = run.config, run.workload, run.reference
    mcfg, model = jobstate.program_model(c, ref)
    opt_cfg = jobstate.optimizer_config(c)
    tmpl = jobstate.template(model, opt_cfg)
    key = jobstate.seed_key(run.seed)
    axes = tuple(c["meshes"]["axes"])
    mesh_a = make_host_mesh(tuple(c["meshes"]["saved"]), axes)
    mesh_b = make_host_mesh(tuple(c["meshes"]["restored"]), axes)
    store_dir = os.path.join(run.scratch, "ckpt")
    tcfg = TrainerConfig(
        global_batch=w["batch"], seq_len=w["seq_len"], ckpt_dir=store_dir,
        keep_last=w["keep_last"], seed=run.seed & 0x7FFFFFFF, train=ts.TrainConfig(opt=opt_cfg),
    )
    tokens, labels = jobstate.token_batches(run.seed, 1, w["batch"], w["seq_len"], mcfg.vocab,
                                            w["tokens"])
    batch = {"tokens": jnp.asarray(tokens[0]), "labels": jnp.asarray(labels[0])}
    span = run.span
    restores, digests = [], []
    span_s = dict.fromkeys(READ_SPANS, 0.0)
    counted = dict.fromkeys(READ_COUNTERS, 0)
    try:
        tr = Trainer(mcfg, tcfg, mesh=mesh_a)
        sh_a = tr._state_shardings(tmpl)
        make = jobstate.make_state(tmpl, c, ref, w["job_step"])
        state = jax.jit(make, out_shardings=sh_a)(key)
        want = np.asarray(dg.digest(state))
        state_bytes = sum(x.nbytes for x in jax.tree.leaves(state))
        tr.pipeline.state.step = w["job_step"]
        t0 = time.monotonic()
        tr.ckpt.save_now(w["job_step"], state, {"pipeline": tr.pipeline.state_dict()})
        snapshot_s = time.monotonic() - t0
        tr.ckpt.wait()
        save_s = list(tr.ckpt.save_seconds)
        tr.close()
        loss_a = _loss(_stepper(model, tcfg.train, sh_a), mesh_a, state, batch)
        del state, tr
        # the digest of a restored state compiles here, not in the window
        sh_b = tree_shardings(mesh_b, tmpl, ts.state_axes(model, opt_cfg, tmpl))
        zeros = jax.jit(lambda: jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), tmpl),
                        out_shardings=sh_b)()
        jax.block_until_ready(dg.digest(zeros))
        del zeros
        peaks_saved = _peaks()
        gc.collect()

        last = None
        run.open_window()
        while run.in_window():
            with span("evict"):
                last = None  # the last restore's state, freed before this one
                gc.collect()
                evict(store_dir)
            t0 = time.monotonic()
            with span("store_open"):
                tr = Trainer(mcfg, tcfg, mesh=mesh_b)
            with span("restore"):
                step = tr._init_or_restore()
            t_wait = time.monotonic()
            with span("device_wait"):
                jax.block_until_ready(tr.state)
            t1 = time.monotonic()
            restores.append({"s": t1 - t0, "wait_s": t1 - t_wait, "step": step})
            with span("digest"):
                digests.append(dg.digest(tr.state))
                jax.block_until_ready(digests[-1])
            stats = tr.store.stats()
            for name, row in stats.get("spans", {}).items():
                if name in span_s:
                    span_s[name] += row["seconds"]
            for name in READ_COUNTERS:
                counted[name] = None if counted[name] is None or name not in stats \
                    else counted[name] + stats[name]
            last, tr.state = tr.state, None
            tr.close()
            del tr
        run.close_window()
        loss_b = None
        if last is not None:
            loss_b = _loss(_stepper(model, tcfg.train, sh_b), mesh_b, last, batch)
        last = None
        peak = memory_peak_bytes()
        peaks_all = _peaks()
        digests = [np.asarray(d) for d in digests]
    finally:
        shutil.rmtree(store_dir, ignore_errors=True)

    bad = sum(dg.mismatches(d, want) for d in digests)
    bad += sum(r["step"] != w["job_step"] for r in restores)
    gap = abs(loss_b - loss_a) / abs(loss_a) if loss_b is not None else float("nan")
    total_s = sum(r["s"] for r in restores)
    gib = state_bytes / GIB
    n = len(restores)
    return {
        "end_to_end": {"restore_gib_s": n * gib / total_s if restores else None},
        "counters": {
            "restores": n, "state_bytes": state_bytes, "gib_restored": n * gib,
            "restore_s": total_s, "device_wait_s": sum(r["wait_s"] for r in restores),
            "span_s": {k: v for k, v in span_s.items() if v}, **counted,
            "save_s": save_s, "gib_saved": gib * len(save_s),
        },
        "compared": {
            "restore_leaves_differing": {"value": float(bad), "limit": 0.0},
            "resume_loss_gap": {"value": float(gap), "limit": w["limits"]["resume_loss_gap"]},
        },
        "attempted": n,
        "failed": 0,
        "memory_peak_bytes": peak,
        "info": {
            "state_gib": gib, "restores": n, "restore_s": [r["s"] for r in restores],
            "device_wait_s": [r["wait_s"] for r in restores], "snapshot_s": snapshot_s,
            "save_s": save_s, "loss_saved_mesh": loss_a, "loss_restored_mesh": loss_b,
            "peak_bytes_per_chip_after_setup": peaks_saved,
            "peak_bytes_per_chip_at_end": peaks_all,
        },
    }
