"""Fault tolerance: exact resume (params + optimizer + data cursor),
preemption checkpointing, straggler detection, pipeline determinism."""
import shutil

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.data.pipeline import TokenPipeline
from repro.training.optimizer import OptimizerConfig
from repro.training.train_step import TrainConfig
from repro.training.trainer import Trainer, TrainerConfig

CFG = get_config("llama3-8b").reduced(d_model=64, n_layers=2, vocab=512, vocab_pad_multiple=64)


def _tcfg(tmp, steps, interval=5, async_=True):
    return TrainerConfig(
        steps=steps,
        global_batch=2,
        seq_len=32,
        ckpt_dir=tmp,
        ckpt_interval=interval,
        ckpt_async=async_,
        log_every=10_000,
        train=TrainConfig(opt=OptimizerConfig(warmup_steps=2, total_steps=100)),
    )


def _params_equal(a, b):
    flat_a = jax.tree.leaves(a)
    flat_b = jax.tree.leaves(b)
    return all(np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(flat_a, flat_b))


def test_pipeline_deterministic_resume():
    p1 = TokenPipeline(512, 4, 16, seed=3)
    batches = [p1.next_batch() for _ in range(5)]
    p2 = TokenPipeline(512, 4, 16, seed=3)
    p2.load_state_dict({"seed": 3, "step": 3, "host": 0, "num_hosts": 1})
    b3 = p2.next_batch()
    np.testing.assert_array_equal(b3["tokens"], batches[3]["tokens"])


def test_exact_resume_matches_uninterrupted(tmp_path):
    """train 10 straight  ==  train 5, 'crash', resume to 10 — bitwise."""
    d1, d2 = str(tmp_path / "a"), str(tmp_path / "b")
    t_full = Trainer(CFG, _tcfg(d1, steps=10, interval=100))
    t_full.run()
    full_params = jax.device_get(t_full.state["params"])
    t_full.close()

    t_half = Trainer(CFG, _tcfg(d2, steps=5, interval=5, async_=False))
    t_half.run()
    t_half.close()  # process "dies" here
    assert t_half.ckpt.save_count == 1  # the interval's save of step 5 is the last one
    t_resume = Trainer(CFG, _tcfg(d2, steps=10, interval=100))
    res = t_resume.run()
    assert res["step"] == 10
    resumed_params = jax.device_get(t_resume.state["params"])
    t_resume.close()
    assert _params_equal(full_params, resumed_params)


def test_preemption_checkpoints_and_resumes(tmp_path):
    d = str(tmp_path / "p")
    tr = Trainer(CFG, _tcfg(d, steps=20, interval=100))
    orig = tr.pipeline.next_batch
    n = {"v": 0}

    def wrapped():
        n["v"] += 1
        if n["v"] == 7:
            tr._preempted = True  # SIGTERM equivalent
        return orig()

    tr.pipeline.next_batch = wrapped
    res = tr.run()
    tr.close()
    assert res["status"] == "preempted" and res["step"] == 7

    tr2 = Trainer(CFG, _tcfg(d, steps=20, interval=100))
    res2 = tr2.run()
    tr2.close()
    assert res2["status"] == "done" and res2["step"] == 20


def test_straggler_detection(tmp_path):
    import time

    d = str(tmp_path / "s")
    events = []
    tr = Trainer(CFG, _tcfg(d, steps=15, interval=100), straggler_cb=lambda *a: events.append(a))
    orig = tr.pipeline.next_batch
    n = {"v": 0}

    def slow():
        n["v"] += 1
        if n["v"] == 12:
            time.sleep(1.0)  # inject a straggler step
        return orig()

    tr.pipeline.next_batch = slow
    tr.run()
    tr.close()
    assert tr.straggler_events >= 1
    assert events


def test_mesh_resume_matches_uninterrupted(tmp_path):
    """The mesh path: state built under jit with the mesh's shardings,
    steps with pinned in/out shardings, restore through
    ``load_distributed`` — resumed params equal an uninterrupted run."""
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh((1, 1))
    d1, d2 = str(tmp_path / "a"), str(tmp_path / "b")
    t_full = Trainer(CFG, _tcfg(d1, steps=6, interval=100), mesh=mesh)
    t_full.run()
    full_params = jax.device_get(t_full.state["params"])
    t_full.close()

    t_half = Trainer(CFG, _tcfg(d2, steps=3, interval=3), mesh=mesh)
    t_half.run()
    t_half.close()
    t_resume = Trainer(CFG, _tcfg(d2, steps=6, interval=100), mesh=mesh)
    res = t_resume.run()
    assert res["step"] == 6
    leaf = jax.tree.leaves(t_resume.state["params"])[0]
    assert leaf.sharding.mesh.shape == mesh.shape
    resumed_params = jax.device_get(t_resume.state["params"])
    t_resume.close()
    assert _params_equal(full_params, resumed_params)
