"""Jit'd public wrappers for the Pallas kernels.

Each wrapper runs its kernel compiled for the device it is traced for;
only on the CPU backend, which has no Mosaic compiler, does the kernel
body run in interpret mode. There is no jnp fallback: the pure-jnp
oracles in :mod:`repro.kernels.ref` are test references only.
"""
from __future__ import annotations

from functools import partial

import jax

from .decode_attention import paged_decode_attention
from .flash_attention import flash_attention
from .rglru_scan import rglru_pallas
from .ssd_scan import ssd_chunked_pallas


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


@partial(jax.jit, static_argnames=("causal", "window"))
def attention(q, k, v, *, causal=True, window=None):
    return flash_attention(q, k, v, causal=causal, window=window, interpret=_interpret())


@jax.jit
def paged_decode(q, pages_k, pages_v, page_table, lengths):
    return paged_decode_attention(
        q, pages_k, pages_v, page_table, lengths, interpret=_interpret()
    )


@partial(jax.jit, static_argnames=("chunk",))
def ssd_scan(x, dA, B_, C_, chunk):
    return ssd_chunked_pallas(x, dA, B_, C_, chunk, interpret=_interpret())


@jax.jit
def rglru(x, r, i, lam, h0=None):
    return rglru_pallas(x, r, i, lam, h0, interpret=_interpret())
