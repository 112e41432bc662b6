"""End-to-end system behaviour: train → checkpoint → crash → restore →
serve, exercising every layer of the stack together, plus cell-spec
contracts used by the dry-run."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import SHAPES, get_config
from repro.configs.base import ShapeCell
from repro.launch.analytic import analytic_memory_bytes, model_flops
from repro.launch.specs import auto_accum_steps, batch_specs, input_specs
from repro.models import build_model
from repro.serving.engine import Request, ServingEngine
from repro.training.optimizer import OptimizerConfig
from repro.training.train_step import TrainConfig
from repro.training.trainer import Trainer, TrainerConfig

CFG = get_config("llama3-8b").reduced(d_model=64, n_layers=2, vocab=512, vocab_pad_multiple=64)


def test_train_crash_restore_serve(tmp_path):
    """The full lifecycle on one model."""
    d = str(tmp_path / "ck")
    tcfg = TrainerConfig(
        steps=8, global_batch=2, seq_len=32, ckpt_dir=d, ckpt_interval=4,
        log_every=10_000,
        train=TrainConfig(opt=OptimizerConfig(warmup_steps=2, total_steps=50)),
    )
    tr = Trainer(CFG, tcfg)
    res = tr.run()
    assert res["status"] == "done"
    losses = [m["loss"] for m in res["metrics"]]
    assert losses[-1] < losses[0]  # it learns
    params = jax.device_get(tr.state["params"])
    tr.store.db.close(crash=True)  # hard crash of the storage engine

    # restore into a fresh trainer (recovery path) and serve with the params
    tr2 = Trainer(CFG, tcfg)
    start = tr2._init_or_restore()
    assert start == 8
    p2 = jax.device_get(tr2.state["params"])
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    engine = ServingEngine(CFG, tr2.state["params"], max_batch=2, max_len=64, page_size=16)
    engine.submit(Request(0, np.arange(1, 9, dtype=np.int32), max_new_tokens=4))
    done = engine.run_until_drained()
    assert len(done[0].tokens) == 4
    tr2.close()


def test_input_specs_contract():
    """input_specs returns weak-type-correct, shardable stand-ins for every
    (arch × shape) cell — the dry-run contract."""
    for arch in ("llama3-8b", "whisper-small", "internvl2-76b", "mamba2-1.3b"):
        cfg = get_config(arch)
        for cell in SHAPES.values():
            ok, _ = cfg.shape_supported(cell)
            if not ok:
                continue
            specs = input_specs(cfg, cell)
            assert "tokens" in specs
            t = specs["tokens"]
            assert t.dtype == jnp.int32
            if cell.kind == "decode":
                assert t.shape == (cell.global_batch, 1)
            else:
                assert t.shape == (cell.global_batch, cell.seq_len)
            if cell.kind != "decode":
                if cfg.family == "audio":
                    assert "enc_embeds" in batch_specs(cfg, cell)[0]
                if cfg.family == "vlm":
                    assert "vision_embeds" in batch_specs(cfg, cell)[0]


def test_auto_accum_bounds_microbatch_tokens():
    class FakeMesh:
        shape = {"data": 16, "model": 16}

    # 256 seqs × 4096 → local 16 seqs; accum 8 → 2×4096 = 8192 tokens ✓
    assert auto_accum_steps(FakeMesh(), 256, 4096) == 8
    assert auto_accum_steps(FakeMesh(), 256, 8192) == 16
    assert auto_accum_steps(FakeMesh(), 16, 512) == 1


def test_analytic_model_sane():
    cfg = get_config("llama3-8b")
    mesh = {"data": 16, "model": 16}
    tr = SHAPES["train_4k"]
    f = model_flops(cfg, tr)
    assert 0.9 * 6 * 8e9 * 1048576 < f < 1.5 * 6 * 8e9 * 1048576
    m = analytic_memory_bytes(cfg, tr, mesh, accum=8)
    assert 1e9 < m < 1e12  # per-chip, plausible range
    de = SHAPES["decode_32k"]
    f_de = model_flops(cfg, de)
    assert f_de < f / 1000  # decode step ≪ train step


def test_all_arch_cells_have_verdict():
    """Every (arch × shape) is either supported or explicitly skipped."""
    from repro.configs import ARCH_IDS, all_configs

    n_run = n_skip = 0
    for arch, cfg in all_configs().items():
        for cell in SHAPES.values():
            ok, why = cfg.shape_supported(cell)
            if ok:
                n_run += 1
            else:
                assert "skip" in why
                n_skip += 1
    assert n_run + n_skip == 40
    assert n_skip == 8  # 8 full-attention archs × long_500k


def test_pipeline_host_sharding():
    from repro.data.pipeline import TokenPipeline

    p0 = TokenPipeline(512, 8, 16, seed=1, host=0, num_hosts=2)
    p1 = TokenPipeline(512, 8, 16, seed=1, host=1, num_hosts=2)
    b0, b1 = p0.next_batch(), p1.next_batch()
    assert b0["tokens"].shape == (4, 16)
    assert not np.array_equal(b0["tokens"], b1["tokens"])  # different shards


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_placement(from_env, tmp_path, monkeypatch):
    """$JAX_COMPILATION_CACHE_DIR wins and the helper sets nothing;
    without it the cache sits at a fixed path in the checkout."""
    from pathlib import Path

    from repro.launch import compile_cache

    before = jax.config.jax_compilation_cache_dir
    if from_env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        got = compile_cache.use_compile_cache()
        if from_env:
            assert got == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == before
        else:
            root = Path(__file__).resolve().parents[1]
            assert got == str(root / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
