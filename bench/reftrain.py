"""The plain reference's training step, from the configuration's optimizer.

AdamW as the configuration states it: the gradient of the mean token loss
over the batch, clipped to a global norm; first and second moments with
bias correction; decoupled weight decay on every leaf whose stored rank is
at least ``decay_min_ndim``; a learning rate warmed up linearly over
``warmup_steps`` and then decayed along a cosine to ``min_lr_ratio`` of
its peak at ``total_steps``. The gradient is summed one batch row at a
time into one buffer, and the moments wait in host memory while it is, so
that the reference of a state that fills the chip still fits on it.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def learning_rate(opt: dict, count: int) -> float:
    if count < opt["warmup_steps"]:
        return opt["lr"] * count / max(opt["warmup_steps"], 1)
    span = max(opt["total_steps"] - opt["warmup_steps"], 1)
    prog = min(max((count - opt["warmup_steps"]) / span, 0.0), 1.0)
    cos = opt["min_lr_ratio"] + (1 - opt["min_lr_ratio"]) * 0.5 * (1 + math.cos(math.pi * prog))
    return opt["lr"] * cos


def _add_row_grad(ref, c, mode, params, acc, tokens, labels):
    loss, g = jax.value_and_grad(lambda p: ref.loss(p, tokens, labels, c, mode))(params)
    return loss, jax.tree.map(jnp.add, acc, g)


def leaf_norms(tree) -> jax.Array:
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


@partial(jax.jit, donate_argnums=(0, 1, 2), static_argnames=("opt_items",))
def _adamw(params, m, v, grad_sum, rows, lr, count, opt_items):
    opt = dict(opt_items)
    grads = jax.tree.map(lambda g: g / rows, grad_sum)
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, opt["grad_clip"] / gnorm)
    grads = jax.tree.map(lambda g: g * scale, grads)
    bc1 = 1.0 - opt["b1"] ** count
    bc2 = 1.0 - opt["b2"] ** count

    def leaf(p, m, v, g):
        m = opt["b1"] * m + (1 - opt["b1"]) * g
        v = opt["b2"] * v + (1 - opt["b2"]) * g * g
        u = (m / bc1) / (jnp.sqrt(v / bc2) + opt["eps"])
        if p.ndim >= opt["decay_min_ndim"]:
            u = u + opt["weight_decay"] * p
        return p - lr * u, m, v

    out = jax.tree.map(leaf, params, m, v, grads)
    pick = lambda i: jax.tree.map(lambda t: t[i], out, is_leaf=lambda x: isinstance(x, tuple))
    return pick(0), pick(1), pick(2), leaf_norms(grads)


def train(ref, c: dict, opt: dict, params, m, v, count0: int, batches, mode: str):
    """Run one step per batch from ``params`` (on the device, donated) and
    the moments ``m``, ``v`` (host arrays).

    Returns (losses, per-leaf norms of the first step's clipped gradient,
    params after the last step)."""
    add_row = jax.jit(partial(_add_row_grad, ref, c, mode), donate_argnums=(1,))
    zeros = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p))
    opt_items = tuple(sorted(opt.items()))
    to_host = lambda t: jax.tree.map(np.asarray, t)
    losses, first = [], None
    for i, (tokens, labels) in enumerate(batches):
        rows = tokens.shape[0]
        acc, total = zeros(params), 0.0
        for r in range(rows):
            loss, acc = add_row(params, acc, tokens[r: r + 1], labels[r: r + 1])
            total += float(loss)
        count = count0 + i + 1
        params, m, v, norms = _adamw(params, jax.device_put(m), jax.device_put(v), acc,
                                     float(rows), learning_rate(opt, count), float(count), opt_items)
        m, v = to_host(m), to_host(v)
        if first is None:
            first = norms
        losses.append(total / rows)
    return losses, first, params
