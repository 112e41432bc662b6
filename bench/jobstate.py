"""A training job's state and data, made by the benchmark from the seed.

The state has the program's layout (``params``, AdamW's ``m``/``v``/``count``
and ``step``) but its values are the benchmark's data: the reference
module's published initialisation for the weights, and random first and
second moments of the size of a gradient element, as in a job that has
been running, so that every byte a checkpoint moves is a live float. The whole state is made on the device in one jitted
call; any leaf can be made again alone from the same seed, which is how the
check recovers the state a step started from without keeping a copy.
"""
from __future__ import annotations

import zlib
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np

# scale of the moments: m ~ s·N(0,1), v = s²·(1 + N(0,1)²), about a
# gradient element of these models under a global norm of 1; m/sqrt(v)
# stays O(1), so every update stays of the size of the learning rate
MOMENT_SCALE = 1e-5


def seed_key(seed: int) -> jax.Array:
    """A key from any non-negative seed, 64 bits and beyond."""
    key = jax.random.key(0)
    for word in range(4):
        key = jax.random.fold_in(key, (seed >> (32 * word)) & 0xFFFFFFFF)
    return key


def program_model(config: dict, ref):
    from repro.configs import get_config
    from repro.models import build_model

    mcfg = replace(get_config(config["program_arch"]), **ref.program_overrides(config))
    return mcfg, build_model(mcfg)


def optimizer_config(config: dict):
    from repro.training.optimizer import OptimizerConfig

    opt = config["optimizer"]
    return OptimizerConfig(
        name="adamw", lr=opt["lr"], warmup_steps=opt["warmup_steps"],
        total_steps=opt["total_steps"], min_lr_ratio=opt["min_lr_ratio"], b1=opt["b1"],
        b2=opt["b2"], eps=opt["eps"], weight_decay=opt["weight_decay"],
        grad_clip=opt["grad_clip"],
    )


def template(model, opt_cfg):
    from repro.training.train_step import init_state

    return jax.eval_shape(lambda k: init_state(model, k, opt_cfg), jax.random.key(0))


def _names(path) -> tuple:
    return tuple(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)


def _leaf(names: tuple, sds, key, config, ref, step: int):
    k = jax.random.fold_in(key, zlib.crc32("/".join(names).encode()))
    if names[0] == "params":
        return ref.init_param(names[1:], sds.shape, k, config).astype(sds.dtype)
    if names[:2] == ("opt", "m"):
        return (MOMENT_SCALE * jax.random.normal(k, sds.shape)).astype(sds.dtype)
    if names[:2] == ("opt", "v"):
        z = jax.random.normal(k, sds.shape)
        return (MOMENT_SCALE**2 * (1.0 + z * z)).astype(sds.dtype)
    if names in (("opt", "count"), ("step",)):
        return jnp.full(sds.shape, step, sds.dtype)
    raise KeyError(f"the benchmark makes no data for state leaf {'/'.join(names)}")


def make_state(tmpl, config: dict, ref, step: int):
    """jitted ``key -> state`` for the job at ``step``."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(tmpl)

    def build(key):
        leaves = [_leaf(_names(p), s, key, config, ref, step) for p, s in flat]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(build)


def subtree_delta_norms(tmpl, prefix: tuple, config: dict, ref, step: int, made_scale: float = 1.0):
    """jitted ``(subtree, key) -> per-leaf norms`` of ``subtree - made_scale·made``,
    where ``made`` is the subtree as the job's state was made."""
    flat = [(p, s) for p, s in jax.tree_util.tree_flatten_with_path(tmpl)[0]
            if _names(p)[: len(prefix)] == prefix]

    def norms(subtree, key):
        out = []
        for (path, sds), now in zip(flat, jax.tree.leaves(subtree)):
            made = _leaf(_names(path), sds, key, config, ref, step).astype(jnp.float32)
            d = now.astype(jnp.float32) - made_scale * made
            out.append(jnp.sqrt(jnp.sum(d * d)))
        return jnp.stack(out)

    return jax.jit(norms)


def token_batches(seed: int, n: int, batch: int, seq_len: int, vocab: int, tokens: dict):
    """``n`` batches of (tokens, labels), int32 (n, batch, seq_len) each.

    Token ids follow a Zipf law of exponent ``tokens["zipf_a"]`` folded
    into the vocabulary, as the frequencies of natural text do; labels are
    the next tokens."""
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 0xB1])
    raw = rng.zipf(tokens["zipf_a"], size=(n, batch, seq_len + 1))
    ids = (raw % (vocab - 2) + 1).astype(np.int32)
    return ids[..., :-1], ids[..., 1:]
