"""Plain reference of a Phi-3-style decoder (arXiv:2404.14219).

Per layer: RMSNorm, grouped-query attention with rotary positions (the
``rotate_half`` form, frequencies ``theta**(-2i/head_dim)``), a causal
softmax, a residual, RMSNorm, SwiGLU, a residual; then a final RMSNorm and
an untied head over the vocabulary. Loss: mean next-token cross-entropy.

It reads weights in the state layout of the program under test (stacked
layers; norm weights stored as their offset from 1), but imports nothing
of it. Sizes come from the configuration file's published keys.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.refmath import mm, rms_norm, token_nll


def _dims(c: dict) -> dict:
    d, H = c["hidden_size"], c["num_attention_heads"]
    return dict(L=c["num_hidden_layers"], d=d, H=H, K=c["num_key_value_heads"],
                hd=d // H, ff=c["intermediate_size"], V=c["vocab_size"])


def program_overrides(c: dict) -> dict:
    """The program's ``ModelConfig`` fields for this configuration."""
    n = _dims(c)
    return dict(
        n_layers=n["L"], d_model=n["d"], n_heads=n["H"], n_kv_heads=n["K"], head_dim=n["hd"],
        d_ff=n["ff"], vocab=n["V"], vocab_pad_multiple=c["program_vocab_pad_multiple"],
        rope_theta=float(c["rope_theta"]), rms_eps=float(c["rms_norm_eps"]),
        tie_embeddings=bool(c["tie_word_embeddings"]), activation="swiglu",
        norm_type="rmsnorm", use_rope=True, qk_norm=False, attention_bias=False,
    )


def init_param(names: tuple, shape: tuple, key: jax.Array, c: dict) -> jax.Array:
    """Published initialisation: normal(0, initializer_range) for every
    matrix and embedding, RMSNorm weights at one (offset 0)."""
    if names[-1] in ("ln1", "ln2", "ln_f"):
        return jnp.zeros(shape, jnp.float32)
    return c["initializer_range"] * jax.random.normal(key, shape, jnp.float32)


def train_flops_per_token(c: dict, seq_len: int) -> float:
    """Operations a forward and backward pass need per token: 6 per
    weight of every matrix product (the head included, the embedding
    lookup not), and the causal attention products, 4·H·hd per earlier
    position in the forward pass and twice that in the backward."""
    n = _dims(c)
    d, H, K, hd = n["d"], n["H"], n["K"], n["hd"]
    per_layer = d * H * hd + 2 * d * K * hd + H * hd * d + 3 * d * n["ff"]
    weights = n["L"] * per_layer + n["V"] * d
    attention = n["L"] * 4 * H * hd * (seq_len + 1) / 2
    return 6.0 * weights + 3.0 * attention


def loss(p: dict, tokens: jax.Array, labels: jax.Array, c: dict, mode: str) -> jax.Array:
    n = _dims(c)
    H, K, hd, V = n["H"], n["K"], n["hd"], n["V"]
    eps = float(c["rms_norm_eps"])
    B, T = tokens.shape
    x = p["embed"][tokens].astype(jnp.float32)

    inv = float(c["rope_theta"]) ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]

    def rope(t):
        t1, t2 = t[..., : hd // 2], t[..., hd // 2:]
        return jnp.concatenate([t1 * cos - t2 * sin, t2 * cos + t1 * sin], axis=-1)

    causal = jnp.tril(jnp.ones((T, T), bool))
    a, f = p["attn"], p["mlp"]
    for l in range(n["L"]):
        h = rms_norm(x, p["ln1"][l], eps)
        q = rope(mm("btd,de->bte", h, a["wq"][l], mode).reshape(B, T, H, hd))
        k = rope(mm("btd,de->bte", h, a["wk"][l], mode).reshape(B, T, K, hd))
        v = mm("btd,de->bte", h, a["wv"][l], mode).reshape(B, T, K, hd)
        k, v = jnp.repeat(k, H // K, axis=2), jnp.repeat(v, H // K, axis=2)
        s = mm("bqhd,bkhd->bhqk", q, k, mode) / math.sqrt(hd)
        s = jnp.where(causal, s, -jnp.inf)
        o = mm("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v, mode)
        x = x + mm("bte,ed->btd", o.reshape(B, T, H * hd), a["wo"][l], mode)
        h = rms_norm(x, p["ln2"][l], eps)
        gate = mm("btd,df->btf", h, f["w_gate"][l], mode)
        up = mm("btd,df->btf", h, f["w_up"][l], mode)
        x = x + mm("btf,fd->btd", jax.nn.silu(gate) * up, f["w_down"][l], mode)
    x = rms_norm(x, p["ln_f"], eps)
    head = p["embed"] if c["tie_word_embeddings"] else p["out_embed"]
    logits = mm("btd,vd->btv", x, head[:V], mode)
    return token_nll(logits, labels)
