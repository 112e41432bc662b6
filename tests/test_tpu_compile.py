"""Compiles for a described TPU v5e, with no chip attached.

The TPU compiler refuses what interpret mode accepts: block shapes that
break the (8, 128) tiling rule, row slices it cannot prove aligned,
programs larger than the chip's 16 GiB. These tests compile the four
Pallas kernels at the widths of the models they serve, the jitted bf16
initialisation of full qwen3-4b, and the 4-layer qwen3-4b train step that
``chip_smoke.py`` runs, so a change that would fail on the chip fails
here first. Nothing runs: only compiles and their memory analysis.

The topology is described inside a module fixture (never at import), so
every test worker collects the same tests and only the worker running
this file loads the TPU library.
"""
import os
from dataclasses import replace

import jax
import jax.numpy as jnp
import pytest

GiB = 2**30


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topo
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding), tree)


def _kernel_case(name):
    """(fn, [(shape, dtype)...]) at model widths: qwen3-4b attention (32/8
    heads of 128), mamba2-1.3b SSD (64 heads of 64, state 128, chunk 256),
    recurrentgemma-9b RG-LRU (width 4096)."""
    from repro.kernels.decode_attention import paged_decode_attention
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.rglru_scan import rglru_pallas
    from repro.kernels.ssd_scan import ssd_chunked_pallas

    bf, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
    if name == "flash":
        return (lambda q, k, v: flash_attention(q, k, v, causal=True),
                [((1, 4096, 32, 128), bf), ((1, 4096, 8, 128), bf), ((1, 4096, 8, 128), bf)])
    if name.startswith("paged"):
        page = int(name[len("paged"):])
        return (paged_decode_attention,
                [((4, 32, 128), bf), ((64, page, 8, 128), bf), ((64, page, 8, 128), bf),
                 ((4, 8), i32), ((4,), i32)])
    if name == "ssd":
        return (lambda x, dA, B, C: ssd_chunked_pallas(x, dA, B, C, 256),
                [((1, 512, 64, 64), f32), ((1, 512, 64), f32),
                 ((1, 512, 1, 128), f32), ((1, 512, 1, 128), f32)])
    batch = int(name[len("rglru_b"):])
    return rglru_pallas, [((batch, 512, 4096), bf)] * 3 + [((4096,), f32)]


@pytest.mark.parametrize(
    "name", ["flash", "paged64", "paged128", "ssd", "rglru_b4", "rglru_b1"]
)
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, specs = _kernel_case(name)
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in specs]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_bf16_init_of_full_qwen3_4b_fits(one_chip):
    """init and cast in one program: only the bf16 parameters come out."""
    from repro.configs import get_config
    from repro.models import build_model
    from repro.serving.engine import bf16_init

    key = _on(one_chip, jax.eval_shape(lambda: jax.random.key(0)))
    mem = bf16_init(build_model(get_config("qwen3-4b"))).lower(key).compile().memory_analysis()
    assert 7 * GiB < mem.output_size_in_bytes < 8 * GiB
    assert mem.temp_size_in_bytes < GiB


def test_four_layer_qwen3_4b_train_step_fits(one_chip):
    """The chip smoke's train step: published widths, 4 of 36 layers,
    fp32 master weights and Adam, batch 4 x 512."""
    from repro.configs import get_config
    from repro.models import build_model
    from repro.training.optimizer import OptimizerConfig
    from repro.training.train_step import TrainConfig, init_state, make_train_step

    model = build_model(replace(get_config("qwen3-4b"), n_layers=4))
    opt = OptimizerConfig()
    state = _on(one_chip, jax.eval_shape(lambda: init_state(model, jax.random.key(0), opt)))
    batch = {k: jax.ShapeDtypeStruct((4, 512), jnp.int32, sharding=one_chip)
             for k in ("tokens", "labels")}
    step = jax.jit(make_train_step(model, TrainConfig(opt=opt)), donate_argnums=0)
    mem = step.lower(state, batch).compile().memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15 * GiB


def test_eight_layer_qwen3_4b_train_step_fits_the_2x2_mesh(topo):
    """``chip_smoke.py --four-chips``: 8 layers of qwen3-4b, a state larger
    than one chip's HBM, sharded over a (data=2, model=2) mesh."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from repro.configs import get_config
    from repro.dist import mesh_context, tree_shardings
    from repro.models import build_model
    from repro.training.optimizer import OptimizerConfig
    from repro.training.train_step import TrainConfig, init_state, make_train_step, state_axes

    model = build_model(replace(get_config("qwen3-4b"), n_layers=8))
    opt = OptimizerConfig()
    state = jax.eval_shape(lambda: init_state(model, jax.random.key(0), opt))
    assert sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(state)) > 13 * GiB
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"),
                axis_types=(jax.sharding.AxisType.Auto,) * 2)
    sh = tree_shardings(mesh, state, state_axes(model, opt, state))
    state = jax.tree.map(lambda s, h: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=h), state, sh)
    batch = {k: jax.ShapeDtypeStruct((4, 512), jnp.int32, sharding=NamedSharding(mesh, PartitionSpec()))
             for k in ("tokens", "labels")}
    with mesh_context(mesh):
        step = jax.jit(make_train_step(model, TrainConfig(opt=opt)), in_shardings=(sh, None),
                       out_shardings=(sh, None), donate_argnums=0)
        mem = step.lower(state, batch).compile().memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 8 * GiB  # per chip
