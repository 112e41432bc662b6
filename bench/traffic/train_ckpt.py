"""Traffic ``train_ckpt``: a training job that checkpoints into BVLSM.

The job is the program's own: its jitted train step (``make_train_step``,
as ``Trainer.run`` jits it, state donated) and a ``Trainer``'s
``CheckpointManager`` and ``BVCheckpointStore``. The loop is
``Trainer.run``'s: a batch to the device, the step, its loss on the host,
``maybe_save`` with the data cursor. Its state is made from the seed as a
job at step ``save_at_step - window_steps_before_save - checked_steps``,
so that one asynchronous save falls early in the window and the save
interval puts the next one beyond it.

Set-up drives that one step object through the ``checked_steps`` first
steps, which warm it up; the window then runs the same object for
``--seconds``. After the window: the save is waited for, the store closed,
reopened and every acknowledged checkpoint read back and compared with the
digest of the state that was snapshotted; the plain reference runs the
first steps again from the same state and data, and the losses, the first
gradient as the optimizer got it and the change of the parameters are
compared with it.
"""
from __future__ import annotations

import gc
import math
import os
import shutil
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import digest as dg
from bench import jobstate, reftrain
from bench.harness import memory_peak_bytes

GIB = 2**30


def _gaps(program: np.ndarray, reference: np.ndarray, keep: np.ndarray | None = None) -> float:
    """Worst leaf: |program − reference| over the larger of the reference
    leaf and the median reference leaf."""
    floor = np.maximum(reference, np.median(reference))
    gap = np.abs(program - reference) / floor
    if keep is not None:
        gap = gap[keep]
    return float(gap.max())


def compare_training(prog: dict, ref: dict, rule: float) -> dict:
    """The three numbers of the training check (see PERF.md)."""
    loss = max(abs(p - r) / abs(r) for p, r in zip(prog["losses"], ref["losses"]))
    keep = ref["grad"] >= rule * np.median(ref["grad"])
    return {
        "loss_gap": float(loss),
        "grad_gap": _gaps(prog["grad"], ref["grad"]),
        "update_gap": _gaps(prog["update"], ref["update"], keep),
    }


class Job:
    """The program's training job, its data and the check's readings."""

    def __init__(self, run, fault=None):
        from repro.training import train_step as ts
        from repro.training.trainer import Trainer, TrainerConfig

        c, w, ref = run.config, run.workload, run.reference
        self.run, self.c, self.w, self.ref = run, c, w, ref
        self.mcfg, model = jobstate.program_model(c, ref)
        self.opt_cfg = jobstate.optimizer_config(c)
        self.tmpl = jobstate.template(model, self.opt_cfg)
        self.key = jobstate.seed_key(run.seed)
        self.B, self.T = w["batch"], w["seq_len"]
        self.interval = w["save_interval"]
        self.start = w["save_at_step"] - w["window_steps_before_save"] - w["checked_steps"]
        self.tokens, self.labels = jobstate.token_batches(
            run.seed, w["batch_pool"], self.B, self.T, self.mcfg.vocab, w["tokens"])
        self.store_dir = os.path.join(run.scratch, "ckpt")
        self.tcfg = TrainerConfig(
            steps=w["save_at_step"] + self.interval, global_batch=self.B, seq_len=self.T,
            ckpt_dir=self.store_dir, ckpt_interval=self.interval, ckpt_async=True,
            keep_last=w["keep_last"], seed=run.seed & 0x7FFFFFFF, log_every=1 << 62,
            train=ts.TrainConfig(opt=self.opt_cfg),
        )
        self.trainer = Trainer(self.mcfg, self.tcfg)
        self.trainer.state = jobstate.make_state(self.tmpl, c, ref, self.start)(self.key)
        make = getattr(fault, "make_train_step", None) or ts.make_train_step
        self.step_fn = jax.jit(make(self.trainer.model, self.tcfg.train), donate_argnums=0)
        b1 = c["optimizer"]["b1"]
        self.grad_of_m = jobstate.subtree_delta_norms(self.tmpl, ("opt", "m"), c, ref, self.start,
                                                      made_scale=b1)
        self.change = jobstate.subtree_delta_norms(self.tmpl, ("params",), c, ref, self.start)
        self.step = self.start
        self.n = 0
        self.digests: dict[int, jax.Array] = {}
        self.saves: list[float] = []  # seconds of each saving maybe_save call

    def state_bytes(self) -> int:
        return sum(x.nbytes for x in jax.tree.leaves(self.trainer.state))

    def one_step(self) -> float:
        """One iteration of the loop; returns its loss."""
        tr, span = self.trainer, self.run.span
        i = self.n % self.tokens.shape[0]
        with span("train_step"):
            batch = {"tokens": jnp.asarray(self.tokens[i]), "labels": jnp.asarray(self.labels[i])}
            tr.state, metrics = self.step_fn(tr.state, batch)
            loss = float(metrics["loss"])
        self.step += 1
        self.n += 1
        if self.step % self.interval == 0:
            with span("digest"):
                self.digests[self.step] = dg.digest(tr.state)
        t0 = time.monotonic()
        with span("maybe_save"):
            saved = tr.ckpt.maybe_save(self.step, tr.state, {"pipeline": tr.pipeline.state_dict()})
        if saved:
            self.saves.append(time.monotonic() - t0)
        return loss

    def program_readings(self) -> dict:
        """The checked steps, through the window's own call."""
        b1 = self.c["optimizer"]["b1"]
        losses = [self.one_step()]
        grad = np.asarray(self.grad_of_m(self.trainer.state["opt"]["m"], self.key)) / (1 - b1)
        losses += [self.one_step() for _ in range(self.w["checked_steps"] - 1)]
        update = np.asarray(self.change(self.trainer.state["params"], self.key))
        dg.digest(self.trainer.state).block_until_ready()  # warm up the save step's digest
        return {"losses": losses, "grad": grad, "update": update}

    def reference_readings(self, mode: str) -> dict:
        """The plain reference from the same state and batches."""
        state = jobstate.make_state(self.tmpl, self.c, self.ref, self.start)(self.key)
        params = state["params"]
        m, v = jax.device_get((state["opt"]["m"], state["opt"]["v"]))
        del state
        n = self.w["checked_steps"]
        batches = [(jnp.asarray(self.tokens[i]), jnp.asarray(self.labels[i])) for i in range(n)]
        losses, grad, params = reftrain.train(self.ref, self.c, self.c["optimizer"], params, m, v,
                                              self.start, batches, mode)
        update = np.asarray(self.change(params, self.key))
        return {"losses": losses, "grad": np.asarray(grad), "update": update}

    def close_trainer(self) -> None:
        """Free the program's state and close its store."""
        if self.trainer is not None:
            self.trainer.state = None
            self.trainer.close()
            self.trainer = None
            gc.collect()

    def read_back(self) -> int:
        """Reopen the store; every acknowledged checkpoint against its digest."""
        from repro.checkpoint.bvstore import BVCheckpointStore

        store = BVCheckpointStore(self.store_dir)
        try:
            on_disk = set(store.steps())
            bad = 0
            for step, want in self.digests.items():
                got = None
                if step in on_disk:
                    host, _ = store.load(step, template=self.tmpl)
                    got = np.asarray(dg.digest(jax.tree.map(jnp.asarray, host)))
                    del host
                bad += dg.mismatches(got, want)
            return bad
        finally:
            store.close()

    def close(self) -> None:
        self.close_trainer()
        shutil.rmtree(self.store_dir, ignore_errors=True)


def run(run) -> dict:
    w = run.workload
    job = Job(run, run.fault)
    try:
        prog = job.program_readings()
        state_bytes = job.state_bytes()
        ckpt, engine = job.trainer.ckpt, job.trainer.store.db.stats
        stall0, eng0 = ckpt.stall_seconds, engine.snapshot()
        times, losses = [], []
        run.open_window()
        while run.in_window():
            t0 = time.monotonic()
            losses.append(job.one_step())
            times.append(time.monotonic() - t0)
        window_s = run.close_window()
        stall_s = ckpt.stall_seconds - stall0
        ckpt.wait()
        eng1 = engine.snapshot()
        save_s = list(ckpt.save_seconds)
        peak = memory_peak_bytes()
        job.digests = {k: np.asarray(v) for k, v in job.digests.items()}
        job.close_trainer()
        mismatched = job.read_back()
        refr = job.reference_readings("f32")
    finally:
        job.close()

    limits = w["limits"]
    gaps = compare_training(prog, refr, limits["grad_rule"])
    saves = job.saves
    durations = [snap + t for snap, t in zip(saves, save_s)]
    gib = state_bytes / GIB
    tokens = len(times) * job.B * job.T
    compared = {k: {"value": gaps[k], "limit": limits[k]} for k in ("loss_gap", "grad_gap", "update_gap")}
    compared["ckpt_leaves_differing"] = {"value": float(mismatched), "limit": 0.0}
    d = lambda k: eng1.get(k, 0) - eng0.get(k, 0)
    p95 = float(np.percentile(times, 95)) * 1e3 if times else None
    return {
        "end_to_end": {
            "train_tokens_s": tokens / window_s,
            "ckpt_save_gib_s": len(durations) * gib / sum(durations) if durations else None,
            "step_p95_ms": p95,
        },
        "counters": {
            "steps": len(times), "tokens": tokens, "step_p95_ms": p95,
            "flops_per_token": run.reference.train_flops_per_token(run.config, job.T),
            "saves": len(saves), "stall_s": stall_s, "save_s": save_s[: len(saves)],
            "gib_saved": gib * len(durations), "device_bytes": d("device_bytes"),
            "user_bytes": d("user_bytes"), "bvalue_fsyncs": d("bvalue_fsyncs"),
        },
        "compared": compared,
        "attempted": len(times) + len(saves),
        "failed": sum(not math.isfinite(x) for x in losses) + len(saves) - len(durations),
        "memory_peak_bytes": peak,
        "info": {
            "steps": len(times), "state_gib": gib, "saves": len(saves),
            "snapshot_s": saves, "store_save_s": save_s,
            "stall_s": stall_s, "losses_program": prog["losses"],
            "losses_reference": refr["losses"], "process_wchar": _wchar(),
            "step_ms_p50_p90_p95_p99_max": [float(np.percentile(times, q)) * 1e3
                                            for q in (50, 90, 95, 99, 100)] if times else [],
        },
    }


def _wchar() -> int | None:
    """Bytes this process has passed to write calls (``/proc/self/io``)."""
    try:
        with open("/proc/self/io") as f:
            for line in f:
                if line.startswith("wchar:"):
                    return int(line.split()[1])
    except OSError:
        return None
    return None
