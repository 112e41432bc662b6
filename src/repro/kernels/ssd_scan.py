"""Mamba-2 SSD chunk kernels (Pallas TPU).

The SSD computation splits into (i) chunk-local quadratic work — MXU
matmuls — and (ii) a tiny inter-chunk state recurrence. The kernels here
implement (i) in two phases around the host-side scan for (ii):

  phase A (``ssd_chunk_states``): per (batch, chunk, head) computes the
      intra-chunk output  y_diag = (CBᵀ ⊙ L) x  and the chunk state
      S = (B ⊙ decay)ᵀ x — three (cs × cs/n) MXU matmuls per program.
  host: inter-chunk scan over  H_c = exp(ΣA_c)·H_{c-1} + S_c  (nc steps of
      an (h, p, n) elementwise update — negligible FLOPs, stays in jnp).
  phase B (``ssd_chunk_output``): y = y_diag + (C ⊙ exp(cumA)) H_inᵀ.

VMEM per program ≈ cs·(p + 2n + cs) fp32 ≈ 0.7 MiB at cs=256, p=64, n=128.
``dA`` is laid out (b, nc, h, 1, cs) so its (1, cs) block spans full dims
and meets the TPU's (8, 128) block tiling at any head count. Validated in
interpret mode against ``ref.ssd_chunk_reference``; compiled for v5e in
tests/test_tpu_compile.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# fp32 operands are contracted at fp32, not in one bf16 pass
_F32 = jax.lax.Precision.HIGHEST


def _cumsum_col(dA):
    """dA (1, cs) row → its inclusive prefix sums as a (cs, 1) column.

    Masked lane reductions over iota masks: no cumsum or transpose, which
    Mosaic does not lower for these shapes, and exact fp32 adds."""
    cs = dA.shape[1]
    ii = jax.lax.broadcasted_iota(jnp.int32, (cs, cs), 0)
    kk = jax.lax.broadcasted_iota(jnp.int32, (cs, cs), 1)
    row = jnp.broadcast_to(dA, (cs, cs))
    return jnp.sum(jnp.where(kk <= ii, row, 0.0), axis=1, keepdims=True)


def _states_kernel(x_ref, dA_ref, b_ref, c_ref, y_ref, s_ref, *, chunk: int):
    # x (1,1,1,cs,p); dA (1,1,1,1,cs); b/c (1,1,cs,n); y (1,1,1,cs,p); s (1,1,1,p,n)
    x = x_ref[0, 0, 0].astype(jnp.float32)  # (cs, p)
    dA = dA_ref[0, 0, 0].astype(jnp.float32)  # (1, cs)
    B = b_ref[0, 0].astype(jnp.float32)  # (cs, n)
    C = c_ref[0, 0].astype(jnp.float32)  # (cs, n)

    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    cum_col = _cumsum_col(dA)  # (cs, 1): cum[i] down the rows
    # cum[j] along the lanes: the same prefix sums, read off the diagonal
    diag = jnp.where(ii == jj, jnp.broadcast_to(cum_col, (chunk, chunk)), 0.0)
    cum_row = jnp.sum(diag, axis=0, keepdims=True)  # (1, cs)
    seg = cum_col - cum_row  # (i, j)
    L = jnp.exp(jnp.where(ii >= jj, seg, NEG_INF))

    CB = jax.lax.dot_general(
        C, B, (((1,), (1,)), ((), ())), precision=_F32, preferred_element_type=jnp.float32
    )  # (i, j)
    scores = CB * L
    y_ref[0, 0, 0, ...] = jax.lax.dot_general(
        scores, x, (((1,), (0,)), ((), ())), precision=_F32, preferred_element_type=jnp.float32
    ).astype(y_ref.dtype)

    total = jnp.sum(dA, axis=1, keepdims=True)  # (1, 1) = cum[-1]
    decay = jnp.exp(total - cum_col)  # (cs, 1)
    Bd = B * decay  # (cs, n)
    s_ref[0, 0, 0, ...] = jax.lax.dot_general(
        x, Bd, (((0,), (0,)), ((), ())), precision=_F32, preferred_element_type=jnp.float32
    ).astype(s_ref.dtype)  # (p, n)


def _output_kernel(ydiag_ref, dA_ref, c_ref, hin_ref, y_ref):
    ydiag = ydiag_ref[0, 0, 0].astype(jnp.float32)  # (cs, p)
    dA = dA_ref[0, 0, 0].astype(jnp.float32)  # (1, cs)
    C = c_ref[0, 0].astype(jnp.float32)  # (cs, n)
    Hin = hin_ref[0, 0, 0].astype(jnp.float32)  # (p, n)
    Cd = C * jnp.exp(_cumsum_col(dA))  # (cs, n)
    y_off = jax.lax.dot_general(
        Cd, Hin, (((1,), (1,)), ((), ())), precision=_F32, preferred_element_type=jnp.float32
    )  # (cs, p)
    y_ref[0, 0, 0, ...] = (ydiag + y_off).astype(y_ref.dtype)


def ssd_chunked_pallas(x, dA, B_, C_, chunk: int, *, interpret: bool = False):
    """x (b,t,h,p); dA (b,t,h); B_/C_ (b,t,g,n) with g=1.
    Returns (y (b,t,h,p), final_state (b,h,p,n))."""
    b, t, h, p = x.shape
    g, n = B_.shape[2], B_.shape[3]
    assert g == 1, "kernel specialization: mamba2 configs use a single group"
    assert t % chunk == 0
    nc = t // chunk

    xc = x.reshape(b, nc, chunk, h, p).transpose(0, 1, 3, 2, 4)  # (b,nc,h,cs,p)
    dAc = dA.reshape(b, nc, chunk, h).transpose(0, 1, 3, 2)[:, :, :, None, :]  # (b,nc,h,1,cs)
    Bc = B_.reshape(b, nc, chunk, n)  # (b,nc,cs,n)
    Cc = C_.reshape(b, nc, chunk, n)

    grid = (b, nc, h)
    y_diag, states = pl.pallas_call(
        functools.partial(_states_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 1, chunk, p), lambda i, c, j: (i, c, j, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1, chunk), lambda i, c, j: (i, c, j, 0, 0)),
            pl.BlockSpec((1, 1, chunk, n), lambda i, c, j: (i, c, 0, 0)),
            pl.BlockSpec((1, 1, chunk, n), lambda i, c, j: (i, c, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, chunk, p), lambda i, c, j: (i, c, j, 0, 0)),
            pl.BlockSpec((1, 1, 1, p, n), lambda i, c, j: (i, c, j, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, nc, h, chunk, p), jnp.float32),
            jax.ShapeDtypeStruct((b, nc, h, p, n), jnp.float32),
        ],
        interpret=interpret,
    )(xc, dAc, Bc, Cc)

    # inter-chunk recurrence (tiny): H_{c} entering chunk c
    chunk_decay = jnp.exp(dAc.astype(jnp.float32).sum(axis=(3, 4)))  # (b,nc,h)

    def step(H, inp):
        S_c, dec_c = inp
        return dec_c[..., None, None] * H + S_c, H

    S_sw = jnp.moveaxis(states, 1, 0)
    d_sw = jnp.moveaxis(chunk_decay, 1, 0)
    H_last, H_in = jax.lax.scan(step, jnp.zeros((b, h, p, n), jnp.float32), (S_sw, d_sw))
    H_in = jnp.moveaxis(H_in, 0, 1)  # (b,nc,h,p,n)

    y = pl.pallas_call(
        _output_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 1, chunk, p), lambda i, c, j: (i, c, j, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1, chunk), lambda i, c, j: (i, c, j, 0, 0)),
            pl.BlockSpec((1, 1, chunk, n), lambda i, c, j: (i, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, p, n), lambda i, c, j: (i, c, j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, chunk, p), lambda i, c, j: (i, c, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, nc, h, chunk, p), x.dtype),
        interpret=interpret,
    )(y_diag, dAc, Cc, H_in)

    y = y.transpose(0, 1, 3, 2, 4).reshape(b, t, h, p)
    return y, H_last
