"""Fault-tolerant training loop.

* BVLSM checkpoint/restart: resume restores params, optimizer, step AND the
  data-pipeline cursor (exact-batch resume — tested in
  tests/test_trainer.py).
* Preemption: SIGTERM triggers an immediate WAL-committed checkpoint and a
  clean 143 exit — at cluster scale this is the TPU maintenance-event hook.
* Straggler mitigation: per-step wall times feed a rolling median; steps
  slower than ``straggler_factor``× median increment a counter and invoke a
  pluggable callback (at scale: re-shard input files away from the slow
  host; here: observable hook + logged event).
* Async checkpointing keeps the loop's exposure to I/O at snapshot cost
  only (the paper's jitter story — measured in benchmarks/stability.py).
"""
from __future__ import annotations

import signal
import statistics
import time
from dataclasses import dataclass, field

import jax
import numpy as np

from repro.checkpoint.bvstore import BVCheckpointStore
from repro.checkpoint.manager import CheckpointManager
from repro.data.pipeline import TokenPipeline
from repro.dist import mesh_context, tree_shardings
from repro.models import build_model
from repro.training.optimizer import OptimizerConfig
from repro.training.train_step import TrainConfig, init_state, make_train_step, state_axes


@dataclass
class TrainerConfig:
    steps: int = 100
    global_batch: int = 8
    seq_len: int = 128
    ckpt_dir: str = "/tmp/repro_ckpt"
    ckpt_interval: int = 50
    ckpt_async: bool = True
    keep_last: int = 2
    seed: int = 0
    log_every: int = 10
    straggler_factor: float = 3.0
    train: TrainConfig = field(default_factory=lambda: TrainConfig(opt=OptimizerConfig(warmup_steps=10, total_steps=1000)))


class Trainer:
    def __init__(self, model_cfg, tcfg: TrainerConfig, mesh=None, straggler_cb=None):
        self.model_cfg = model_cfg
        self.tcfg = tcfg
        self.mesh = mesh
        self.model = build_model(model_cfg)
        self.store = BVCheckpointStore(tcfg.ckpt_dir)
        self.ckpt = CheckpointManager(
            self.store, tcfg.ckpt_interval, tcfg.keep_last, tcfg.ckpt_async
        )
        extra = {}
        if model_cfg.family == "vlm":
            extra["vision_embeds"] = ((model_cfg.n_vision_patches, model_cfg.d_model), np.float32)
        if model_cfg.family == "audio":
            extra["enc_embeds"] = ((model_cfg.enc_len, model_cfg.d_model), np.float32)
        self.pipeline = TokenPipeline(
            model_cfg.vocab, tcfg.global_batch, tcfg.seq_len, seed=tcfg.seed, extra_fields=extra
        )
        self.state = None
        self.step_times: list[float] = []
        self.straggler_events = 0
        self.straggler_cb = straggler_cb
        self._preempted = False
        self.metrics_log: list[dict] = []

    # ------------------------------------------------------------------
    def _state_shardings(self, template):
        """NamedShardings of the train state on the trainer's mesh."""
        axes = state_axes(self.model, self.tcfg.train.opt, template)
        return tree_shardings(self.mesh, template, axes)

    def _init_or_restore(self):
        latest = self.store.latest_step()
        init = lambda key: init_state(self.model, key, self.tcfg.train.opt)
        key = jax.random.key(self.tcfg.seed)
        template = jax.eval_shape(init, key)
        if latest is not None:
            with self.store.span("ckpt.restore", step=latest):
                if self.mesh is not None:
                    axes = state_axes(self.model, self.tcfg.train.opt, template)
                    self.state, meta = self.store.load_distributed(self.mesh, template, axes, latest)
                else:
                    self.state, meta = self.store.load(latest, template=template, place=True)
                self.pipeline.load_state_dict(meta["extra"]["pipeline"])
            return int(meta["step"])
        # under jit, so the state is built in place: sharded over the mesh
        # when there is one, never first whole on one device
        kw = {} if self.mesh is None else {"out_shardings": self._state_shardings(template)}
        self.state = jax.jit(init, **kw)(key)
        return 0

    def _handle_sigterm(self, signum, frame):
        self._preempted = True

    # ------------------------------------------------------------------
    def run(self) -> dict:
        tcfg = self.tcfg
        prev_handler = signal.signal(signal.SIGTERM, self._handle_sigterm)
        step_fn = make_train_step(self.model, tcfg.train)
        try:
            with mesh_context(self.mesh):
                start = self._init_or_restore()
                if self.mesh is not None:
                    st_sh = self._state_shardings(jax.eval_shape(lambda: self.state))
                    jitted = jax.jit(
                        step_fn, in_shardings=(st_sh, None), out_shardings=(st_sh, None),
                        donate_argnums=0,
                    )
                else:
                    jitted = jax.jit(step_fn, donate_argnums=0)

                saved = False
                for step in range(start, tcfg.steps):
                    t0 = time.monotonic()
                    batch = self.pipeline.next_batch()
                    batch = {k: jax.numpy.asarray(v) for k, v in batch.items()}
                    self.state, metrics = jitted(self.state, batch)
                    metrics = {k: float(v) for k, v in metrics.items()}
                    dt = time.monotonic() - t0
                    self.step_times.append(dt)
                    self._check_straggler(step, dt)
                    metrics["step_s"] = dt
                    metrics["step"] = step + 1
                    self.metrics_log.append(metrics)
                    if (step + 1) % tcfg.log_every == 0:
                        print(
                            f"step {step+1}: loss={metrics.get('loss', float('nan')):.4f} "
                            f"({dt*1e3:.0f} ms)",
                            flush=True,
                        )
                    saved = self.ckpt.maybe_save(
                        step + 1, self.state, {"pipeline": self.pipeline.state_dict()}
                    )
                    if self._preempted:
                        self.ckpt.save_now(
                            step + 1, self.state, {"pipeline": self.pipeline.state_dict()}
                        )
                        self.ckpt.wait()
                        print(f"preempted at step {step+1}; checkpoint committed", flush=True)
                        return {"status": "preempted", "step": step + 1, "metrics": self.metrics_log}
                if not saved:  # the interval has not just saved the last step
                    self.ckpt.save_now(
                        tcfg.steps, self.state, {"pipeline": self.pipeline.state_dict()}
                    )
                self.ckpt.wait()
            return {"status": "done", "step": tcfg.steps, "metrics": self.metrics_log}
        finally:
            signal.signal(signal.SIGTERM, prev_handler)

    def _check_straggler(self, step: int, dt: float) -> None:
        if len(self.step_times) < 8:
            return
        med = statistics.median(self.step_times[-32:])
        if dt > self.tcfg.straggler_factor * med:
            self.straggler_events += 1
            if self.straggler_cb is not None:
                self.straggler_cb(step, dt, med)

    def close(self) -> None:
        self.ckpt.close()
        self.store.close()
