"""RG-LRU linear-recurrence Pallas TPU kernel.

The recurrence h_t = a_t ⊙ h_{t-1} + √(1−a_t²) ⊙ (i_t ⊙ x_t) is diagonal —
no MXU work — so the kernel's job is bandwidth shaping: stream (T, W)
activation tiles through VMEM once, carrying the (1, W) state in a VMEM
scratch that persists across the sequential T-block grid dimension.
Grid = (batch, W_blocks, T_blocks); the T dimension is innermost so the
state scratch carries across its steps.

This layer is inherently memory-bound (the roofline table shows it); the
win over the jnp associative scan is avoiding its O(log T) full-tensor
round trips — one HBM pass instead of ~log₂(T).

The time loop steps over (8, wb) fp32 or (16, wb) 16-bit row tiles at
offsets Mosaic can prove aligned and runs the recurrence over the tile's
rows statically; the (B, W) state crosses the call as (B, 1, W), so every
block's last two dims are full or (8, 128)-aligned. Validated in interpret
mode against ``ref.rglru_reference``; compiled for v5e in
tests/test_tpu_compile.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_C = 8.0


def _rglru_kernel(x_ref, r_ref, i_ref, lam_ref, h0_ref, y_ref, hout_ref, h_scr, *,
                  block_t: int, sub: int):
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _init():
        h_scr[...] = h0_ref[0].astype(jnp.float32)  # (1, wb)

    lam = lam_ref[...].astype(jnp.float32)  # (1, wb)
    log_a_base = -_C * jax.nn.softplus(lam)

    def step(s, h):
        # one (sub, wb) tile per step, read and written at a row offset
        # Mosaic can prove sublane-aligned
        t0 = pl.multiple_of(s * sub, sub)
        xs, rs, ins = (ref[0, pl.ds(t0, sub), :].astype(jnp.float32)
                       for ref in (x_ref, r_ref, i_ref))
        log_a = rs * log_a_base
        a = jnp.exp(log_a)
        beta = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12))
        u = beta * (ins * xs)
        row = jax.lax.broadcasted_iota(jnp.int32, xs.shape, 0)
        ys = jnp.zeros_like(xs)
        for k in range(sub):  # the recurrence itself: static rows of the tile
            h = a[k : k + 1] * h + u[k : k + 1]
            ys = jnp.where(row == k, h, ys)
        y_ref[0, pl.ds(t0, sub), :] = ys.astype(y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, block_t // sub, step, h_scr[...])
    h_scr[...] = h

    @pl.when(ti == pl.num_programs(2) - 1)
    def _finish():
        hout_ref[0] = h.astype(hout_ref.dtype)


def rglru_pallas(x, r, i, lam, h0=None, *, block_t: int = 256, block_w: int = 256, interpret: bool = False):
    """x, r, i: (B, T, W); lam (W,); h0 (B, W) fp32. Returns (y, h_last)."""
    B, T, W = x.shape
    if h0 is None:
        h0 = jnp.zeros((B, W), jnp.float32)
    block_t = min(block_t, T)
    block_w = min(block_w, W)
    # rows per aligned sub-tile: one (8, 128) fp32 tile, or a packed
    # (16, 128) tile for 16-bit inputs
    sub = 8 * max(1, 4 // x.dtype.itemsize)
    assert T % block_t == 0 and W % block_w == 0 and block_t % sub == 0, (T, W, block_t, block_w)
    lam2 = lam[None, :]  # (1, W)
    # (B, 1, W): each batch row's state is a full (1, wb) block
    h03 = h0.astype(jnp.float32)[:, None, :]

    grid = (B, W // block_w, T // block_t)
    y, h_last = pl.pallas_call(
        functools.partial(_rglru_kernel, block_t=block_t, sub=sub),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_t, block_w), lambda b, w, t: (b, t, w)),
            pl.BlockSpec((1, block_t, block_w), lambda b, w, t: (b, t, w)),
            pl.BlockSpec((1, block_t, block_w), lambda b, w, t: (b, t, w)),
            pl.BlockSpec((1, block_w), lambda b, w, t: (0, w)),
            pl.BlockSpec((1, 1, block_w), lambda b, w, t: (b, 0, w)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_t, block_w), lambda b, w, t: (b, t, w)),
            pl.BlockSpec((1, 1, block_w), lambda b, w, t: (b, 0, w)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, T, W), x.dtype),
            jax.ShapeDtypeStruct((B, 1, W), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, block_w), jnp.float32)],
        interpret=interpret,
    )(x, r, i, lam2, h03)
    return y, h_last[:, 0, :]
