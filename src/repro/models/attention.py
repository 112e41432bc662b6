"""Grouped-query attention: train/prefill (optionally chunked + windowed) and
single-token decode against a KV cache.

Shapes: q (B, T, H, D); k/v (B, S, K, D) with H = K·G (GQA groups).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _gqa_scores(q, k):
    """q (B,T,K,G,D), k (B,S,K,D) → (B,K,G,T,S) fp32."""
    return jnp.einsum("btkgd,bskd->bkgts", q, k, preferred_element_type=jnp.float32)


def _gqa_out(p, v):
    """p (B,K,G,T,S) (same dtype as v), v (B,S,K,D) → (B,T,K,G,D)."""
    return jnp.einsum("bkgts,bskd->btkgd", p, v)


def _mask(q_pos, k_pos, causal: bool, window: int | None):
    m = jnp.ones((q_pos.shape[0], k_pos.shape[0]), dtype=bool)
    if causal:
        m &= q_pos[:, None] >= k_pos[None, :]
    if window is not None:
        m &= q_pos[:, None] - k_pos[None, :] < window
    return m


def full_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    q_chunk: int = 2048,
) -> jax.Array:
    """Exact attention, chunked over query blocks so peak memory is
    O(T·q_chunk) instead of O(T²). q (B,T,H,D) → (B,T,H,D)."""
    B, T, H, D = q.shape
    S, K = k.shape[1], k.shape[2]
    G = H // K
    scale = 1.0 / math.sqrt(D)
    qg = q.reshape(B, T, K, G, D) * scale

    k_pos = jnp.arange(S)

    def block(args):
        qc, q0 = args  # qc: (B, C, K, G, D); q0: scalar chunk start
        C = qc.shape[1]
        s = _gqa_scores(qc, k)
        q_pos = q0 + jnp.arange(C)
        m = _mask(q_pos, k_pos, causal, window)
        s = jnp.where(m[None, None, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        return _gqa_out(p, v)

    from repro.dist.perf import perf

    if T <= q_chunk:
        out = block((qg, jnp.array(0)))
    elif causal and perf().causal_chunk_growth:
        # §Perf V4: query chunk i only attends keys [lo, (i+1)·c) — static
        # growing slices halve attention FLOPs vs full-width chunks.
        assert T % q_chunk == 0, (T, q_chunk)
        n = T // q_chunk
        outs = []
        for i in range(n):
            qc = qg[:, i * q_chunk : (i + 1) * q_chunk]
            hi = (i + 1) * q_chunk
            lo = max(0, i * q_chunk - window + 1) if window is not None else 0
            lo = (lo // 128) * 128  # keep slices lane-aligned
            kc, vc = k[:, lo:hi], v[:, lo:hi]
            s = _gqa_scores(qc, kc)
            q_pos = i * q_chunk + jnp.arange(q_chunk)
            m = _mask(q_pos, lo + jnp.arange(hi - lo), causal, window)
            s = jnp.where(m[None, None, None], s, NEG_INF)
            p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
            outs.append(_gqa_out(p, vc))
        out = jnp.concatenate(outs, axis=1)
    else:
        assert T % q_chunk == 0, (T, q_chunk)
        n = T // q_chunk
        qs = qg.reshape(B, n, q_chunk, K, G, D).transpose(1, 0, 2, 3, 4, 5)
        starts = jnp.arange(n) * q_chunk
        outs = jax.lax.map(block, (qs, starts))  # (n, B, C, K, G, D)
        out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, T, K, G, D)
    return out.reshape(B, T, H, D)


def decode_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    cache_len: jax.Array,
    *,
    window: int | None = None,
) -> jax.Array:
    """One-step decode. q (B,1,H,D); caches (B,S,K,D); cache_len () or (B,)
    = number of valid cache entries (the new token's K/V already written).
    With ``window`` the cache is a ring buffer of size S=window and all
    slots are valid once wrapped."""
    B, _, H, D = q.shape
    S, K = k_cache.shape[1], k_cache.shape[2]
    G = H // K
    scale = 1.0 / math.sqrt(D)
    qg = q.reshape(B, 1, K, G, D) * scale
    s = _gqa_scores(qg, k_cache)  # (B,K,G,1,S)
    pos = jnp.arange(S)
    if jnp.ndim(cache_len) == 0:
        valid = pos < cache_len
        s = jnp.where(valid[None, None, None, None, :], s, NEG_INF)
    else:
        valid = pos[None, :] < cache_len[:, None]
        s = jnp.where(valid[:, None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)  # fp32 — decode is memory-bound; fp32
    # accumulation is free and matches the sharded flash-decode numerics
    out = jnp.einsum(
        "bkgts,bskd->btkgd", p, v_cache.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    ).astype(q.dtype)
    return out.reshape(B, 1, H, D)


def update_cache(cache: jax.Array, new: jax.Array, index: jax.Array, ring: bool = False):
    """cache (B,S,K,D) ← new (B,1,K,D) at position index (ring: index % S)."""
    S = cache.shape[1]
    idx = jnp.mod(index, S) if ring else index
    return jax.lax.dynamic_update_slice_in_dim(cache, new.astype(cache.dtype), idx, axis=1)


# ---------------------------------------------------------------------------
# §Perf V3: flash-decode over the model-axis-sharded KV sequence
# ---------------------------------------------------------------------------

def sharded_decode_update_attend(q, k_cache, v_cache, k_new, v_new, pos):
    """Cache update + decode attention with the cache's SEQ dim sharded over
    `model`, via shard_map: each shard writes its slot (if it owns position
    ``pos``) and computes partial online-softmax stats over its local keys;
    the combine is a psum of (B,H,hd)+(B,H) — ~KB instead of the per-layer
    cache all-gather GSPMD would otherwise emit.

    q (B,1,H,D); caches (B,S,K,D); k_new/v_new (B,1,K,D); pos scalar
    (cache_len = pos + 1). Returns (out (B,1,H,D), k_cache, v_cache).
    """
    from repro.dist import active_mesh, logical_to_spec, shard_map

    mesh = active_mesh()
    B, S, K, D = k_cache.shape
    H = q.shape[2]
    n_shards = mesh.shape.get("model", 1) if mesh is not None else 1
    if mesh is None or n_shards == 1 or S % n_shards:
        kc = update_cache(k_cache, k_new, pos)
        vc = update_cache(v_cache, v_new, pos)
        return decode_attention(q, kc, vc, pos + 1), kc, vc

    from jax.sharding import PartitionSpec as P

    cache_spec = logical_to_spec(("cache_batch", "kv_seq", None, None), k_cache.shape, mesh)
    bspec = cache_spec[0]  # however batch resolved (data / (pod,data) / None)
    q_spec = P(bspec, None, None, None)
    # return attention output with HEADS sharded over model so the
    # downstream row-parallel wo einsum keeps its TP pattern — a replicated
    # output makes GSPMD replicate the whole layer's compute.
    H_l = H // n_shards if H % n_shards == 0 else None
    o_spec = P(bspec, None, "model", None) if H_l else q_spec
    S_l = S // n_shards
    G = H // K
    scale = 1.0 / math.sqrt(D)

    def f(q, kc, vc, kn, vn, pos):
        sid = jax.lax.axis_index("model")
        # --- shard-local cache write ---
        local = pos - sid * S_l
        owner = (local >= 0) & (local < S_l)
        idx = jnp.clip(local, 0, S_l - 1)
        cur_k = jax.lax.dynamic_slice_in_dim(kc, idx, 1, 1)
        cur_v = jax.lax.dynamic_slice_in_dim(vc, idx, 1, 1)
        kc = jax.lax.dynamic_update_slice_in_dim(
            kc, jnp.where(owner, kn.astype(kc.dtype), cur_k), idx, 1
        )
        vc = jax.lax.dynamic_update_slice_in_dim(
            vc, jnp.where(owner, vn.astype(vc.dtype), cur_v), idx, 1
        )
        # --- partial flash stats over local keys ---
        qg = q.reshape(-1, 1, K, G, D) * scale
        s = _gqa_scores(qg, kc)  # (B,K,G,1,S_l) fp32
        kpos = sid * S_l + jnp.arange(S_l)
        valid = kpos < pos + 1
        s = jnp.where(valid[None, None, None, None, :], s, NEG_INF)
        m = jnp.max(s, axis=-1)  # (B,K,G,1)
        p = jnp.where(valid[None, None, None, None, :], jnp.exp(s - m[..., None]), 0.0)
        l = jnp.sum(p, axis=-1)  # (B,K,G,1)
        # fp32 accumulation (standard flash-decode): partial sums must not
        # round to bf16 before the cross-shard combine
        acc = jnp.einsum(
            "bkgts,bskd->btkgd", p, vc.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )  # (B,1,K,G,D)
        # --- combine across shards (tiny) ---
        m_g = jax.lax.pmax(m, "model")
        corr = jnp.exp(m - m_g)
        l_g = jax.lax.psum(l * corr, "model")
        acc_g = jax.lax.psum(acc * corr.transpose(0, 3, 1, 2)[..., None], "model")
        l_g = jnp.where(l_g == 0.0, 1.0, l_g)
        out = acc_g / l_g.transpose(0, 3, 1, 2)[..., None]
        out = out.reshape(-1, 1, H, D).astype(q.dtype)
        if H_l:
            out = jax.lax.dynamic_slice_in_dim(out, sid * H_l, H_l, axis=2)
        return out, kc, vc

    manual = {"model"} | (
        {a for a in ("data", "pod") if a in mesh.shape and bspec
         and a in (bspec if isinstance(bspec, tuple) else (bspec,))}
    )
    out, kc, vc = shard_map(
        f,
        mesh=mesh,
        in_specs=(q_spec, cache_spec, cache_spec, q_spec, q_spec, P()),
        out_specs=(o_spec, cache_spec, cache_spec),
        axis_names=frozenset(manual),
        check_vma=False,
    )(q, k_cache, v_cache, k_new, v_new, pos)
    return out, kc, vc
