"""Per-kernel interpret-mode sweeps against the pure-jnp oracles in
kernels/ref.py — shapes × dtypes per the deliverable contract."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attention import paged_decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ref import (
    mha_reference,
    paged_decode_reference,
    rglru_reference,
    ssd_chunk_reference,
)
from repro.kernels.rglru_scan import rglru_pallas
from repro.kernels.ssd_scan import ssd_chunked_pallas

RNG = np.random.default_rng(0)


def _tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 2e-5


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,T,H,K,hd,causal,window",
    [
        (2, 256, 8, 4, 64, True, None),   # GQA
        (1, 384, 4, 1, 128, True, None),  # MQA
        (2, 256, 8, 8, 64, False, None),  # MHA bidirectional (whisper enc)
        (1, 512, 4, 2, 64, True, 128),    # sliding window (recurrentgemma)
        (1, 200, 4, 2, 64, True, None),   # unaligned T (padding path)
        (1, 256, 2, 2, 32, True, None),   # small head_dim
    ],
)
def test_flash_attention_sweep(B, T, H, K, hd, causal, window, dtype):
    q = jnp.asarray(RNG.normal(size=(B, T, H, hd)), dtype)
    k = jnp.asarray(RNG.normal(size=(B, T, K, hd)), dtype)
    v = jnp.asarray(RNG.normal(size=(B, T, K, hd)), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=128, block_k=128, interpret=True)
    ref = mha_reference(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=_tol(dtype), rtol=1e-2
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,H,K,hd,P,page,maxp",
    [(2, 8, 4, 64, 16, 128, 4), (4, 4, 1, 128, 32, 128, 6), (2, 16, 8, 64, 16, 256, 3)],
)
def test_paged_decode_sweep(B, H, K, hd, P, page, maxp, dtype):
    q = jnp.asarray(RNG.normal(size=(B, H, hd)), dtype)
    pk = jnp.asarray(RNG.normal(size=(P, page, K, hd)), dtype)
    pv = jnp.asarray(RNG.normal(size=(P, page, K, hd)), dtype)
    pt = jnp.asarray(RNG.integers(0, P, size=(B, maxp)), jnp.int32)
    lengths = jnp.asarray(RNG.integers(1, maxp * page, size=(B,)), jnp.int32)
    out = paged_decode_attention(q, pk, pv, pt, lengths, interpret=True)
    ref = paged_decode_reference(q, pk, pv, pt, lengths)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=_tol(dtype), rtol=1e-2
    )


@pytest.mark.parametrize(
    "b,t,h,p,n,chunk",
    [(1, 128, 4, 32, 64, 32), (2, 256, 2, 64, 128, 64), (1, 64, 8, 16, 32, 64)],
)
def test_ssd_chunk_sweep(b, t, h, p, n, chunk):
    x = jnp.asarray(RNG.normal(size=(b, t, h, p)), jnp.float32)
    dA = -jnp.abs(jnp.asarray(RNG.normal(size=(b, t, h)), jnp.float32)) * 0.3
    B_ = jnp.asarray(RNG.normal(size=(b, t, 1, n)), jnp.float32)
    C_ = jnp.asarray(RNG.normal(size=(b, t, 1, n)), jnp.float32)
    y, st = ssd_chunked_pallas(x, dA, B_, C_, chunk, interpret=True)
    yr, sr = ssd_chunk_reference(x, dA, B_, C_)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=5e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(st), np.asarray(sr), atol=5e-4, rtol=1e-3)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,T,W,bt,bw", [(2, 128, 256, 64, 128), (1, 256, 512, 128, 256)])
def test_rglru_sweep(B, T, W, bt, bw, dtype):
    x = jnp.asarray(RNG.normal(size=(B, T, W)), dtype)
    r = jnp.asarray(RNG.uniform(size=(B, T, W)), dtype)
    i = jnp.asarray(RNG.uniform(size=(B, T, W)), dtype)
    lam = jnp.asarray(RNG.uniform(0.5, 4.0, size=(W,)), jnp.float32)
    y, h = rglru_pallas(x, r, i, lam, block_t=bt, block_w=bw, interpret=True)
    yr, hr = rglru_reference(x, r, i, lam)
    np.testing.assert_allclose(
        np.asarray(y, np.float32), np.asarray(yr, np.float32), atol=_tol(dtype), rtol=1e-2
    )
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr), atol=_tol(dtype), rtol=1e-2)


def test_rglru_carried_state():
    """State h0 threads correctly across two kernel invocations."""
    B, T, W = 1, 64, 128
    x = jnp.asarray(RNG.normal(size=(B, 2 * T, W)), jnp.float32)
    r = jnp.asarray(RNG.uniform(size=(B, 2 * T, W)), jnp.float32)
    i = jnp.asarray(RNG.uniform(size=(B, 2 * T, W)), jnp.float32)
    lam = jnp.asarray(RNG.uniform(0.5, 4.0, size=(W,)), jnp.float32)
    y1, h1 = rglru_pallas(x[:, :T], r[:, :T], i[:, :T], lam, block_t=64, block_w=128, interpret=True)
    y2, h2 = rglru_pallas(x[:, T:], r[:, T:], i[:, T:], lam, h0=h1, block_t=64, block_w=128, interpret=True)
    yr, hr = rglru_reference(x, r, i, lam)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)), np.asarray(yr), atol=1e-5)
    np.testing.assert_allclose(np.asarray(h2), np.asarray(hr), atol=1e-5)


def test_ops_wrappers_run_the_kernels():
    """The public wrappers pick interpret mode on the CPU backend (the
    compiled kernel elsewhere) and agree with the oracles."""
    from repro.kernels import ops

    B, T, H, K, hd = 1, 128, 4, 2, 64
    q = jnp.asarray(RNG.normal(size=(B, T, H, hd)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(B, T, K, hd)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(B, T, K, hd)), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(ops.attention(q, k, v)), np.asarray(mha_reference(q, k, v)), atol=2e-5, rtol=1e-2
    )
    P, page, maxp = 8, 128, 2
    qd = jnp.asarray(RNG.normal(size=(2, H, hd)), jnp.float32)
    pk = jnp.asarray(RNG.normal(size=(P, page, K, hd)), jnp.float32)
    pv = jnp.asarray(RNG.normal(size=(P, page, K, hd)), jnp.float32)
    pt = jnp.asarray(RNG.integers(0, P, size=(2, maxp)), jnp.int32)
    lengths = jnp.asarray([5, 200], jnp.int32)
    np.testing.assert_allclose(
        np.asarray(ops.paged_decode(qd, pk, pv, pt, lengths)),
        np.asarray(paged_decode_reference(qd, pk, pv, pt, lengths)), atol=2e-5, rtol=1e-2,
    )
    x = jnp.asarray(RNG.normal(size=(1, 64, 2, 16)), jnp.float32)
    dA = -jnp.abs(jnp.asarray(RNG.normal(size=(1, 64, 2)), jnp.float32)) * 0.3
    Bs = jnp.asarray(RNG.normal(size=(1, 64, 1, 32)), jnp.float32)
    Cs = jnp.asarray(RNG.normal(size=(1, 64, 1, 32)), jnp.float32)
    for got, want in zip(ops.ssd_scan(x, dA, Bs, Cs, 32), ssd_chunk_reference(x, dA, Bs, Cs)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=5e-4, rtol=1e-3)
    xr = jnp.asarray(RNG.normal(size=(1, 64, 128)), jnp.float32)
    r = jnp.asarray(RNG.uniform(size=(1, 64, 128)), jnp.float32)
    i = jnp.asarray(RNG.uniform(size=(1, 64, 128)), jnp.float32)
    lam = jnp.asarray(RNG.uniform(0.5, 4.0, size=(128,)), jnp.float32)
    for got, want in zip(ops.rglru(xr, r, i, lam), rglru_reference(xr, r, i, lam)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
