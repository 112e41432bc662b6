"""Arithmetic shared by the plain references.

A reference computes in float32 with every matrix product at
``Precision.HIGHEST`` (``"f32"``). Its control (``"fp8"``) is the same
computation one precision step below the bfloat16 that the configurations
state: every operand of every matrix product, and every gradient flowing
back into one, rounded to float8 e4m3 with one scale per tensor, the
products summed in float32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _round_fp8(x: jax.Array) -> jax.Array:
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / scale).astype(F8).astype(jnp.float32) * scale


@jax.custom_vjp
def fp8(x: jax.Array) -> jax.Array:
    return _round_fp8(x)


def _fp8_fwd(x):
    return _round_fp8(x), None


def _fp8_bwd(_, g):
    return (_round_fp8(g),)


fp8.defvjp(_fp8_fwd, _fp8_bwd)


def mm(eq: str, a: jax.Array, b: jax.Array, mode: str) -> jax.Array:
    """``einsum(eq, a, b)`` in float32, or with fp8 operands for the control."""
    if mode == "fp8":
        a, b = fp8(a), fp8(b)
    elif mode != "f32":
        raise ValueError(f"unknown precision mode {mode!r}")
    return jnp.einsum(eq, a, b, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def rms_norm(x: jax.Array, offset: jax.Array, eps: float) -> jax.Array:
    """RMSNorm whose weight is stored as its offset from 1 (the program's
    layout: a zero leaf is the published initial weight of ones)."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + offset)


def token_nll(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Mean negative log-likelihood of ``labels`` under float32 ``logits``."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - picked)
