"""Plain reference of a Mamba-2 language model (arXiv:2405.21060).

Per layer: RMSNorm, the input projection split into ``z``, ``xBC`` and
``dt``; a causal depthwise convolution and SiLU over ``xBC``;
``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; the SSD scan of
``x·dt`` with log-decay ``dt·A`` in the chunked form of the paper's
Listing 1 (``ssd_minimal_discrete``); the ``D`` skip; the gated RMSNorm
``norm(y·silu(z))``; the output projection and a residual. Then a final
RMSNorm and the head tied to the embedding. Loss: mean next-token
cross-entropy.

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
    s = c["layer_defaults"]
    d_in = s["expand"] * c["d_model"]
    return dict(L=c["n_layer"], d=c["d_model"], d_in=d_in, h=d_in // s["headdim"],
                p=s["headdim"], g=s["ngroups"], n=s["d_state"], k=s["d_conv"],
                cs=s["chunk_size"], V=c["vocab_size"])


def program_overrides(c: dict) -> dict:
    n = _dims(c)
    return dict(
        n_layers=n["L"], d_model=n["d"], vocab=n["V"],
        vocab_pad_multiple=c["pad_vocab_size_multiple"], tie_embeddings=bool(c["tie_embeddings"]),
        ssm_state=n["n"], ssm_head_dim=n["p"], ssm_expand=c["layer_defaults"]["expand"],
        ssm_chunk=n["cs"], conv_kernel=n["k"], ssm_groups=n["g"], rms_eps=float(c["layer_defaults"]["norm_epsilon"]),
    )


def init_param(names: tuple, shape: tuple, key: jax.Array, c: dict) -> jax.Array:
    """The published module's initialisation (``mamba_ssm``): embedding
    normal(0, 0.02); linear and convolution layers PyTorch's default
    uniform(±1/sqrt(fan_in)), the output projection further divided by
    sqrt(n_layer) of the published depth; ``A`` uniform in [1, 16];
    ``dt`` log-uniform in [0.001, 0.1] stored through the inverse
    softplus; ``D`` one; every RMSNorm weight one (offset 0)."""
    n = _dims(c)
    leaf = names[-1]
    u = lambda bound: jax.random.uniform(key, shape, jnp.float32, -bound, bound)
    if leaf == "embed":
        return 0.02 * jax.random.normal(key, shape, jnp.float32)
    if leaf in ("ln", "ln_f", "norm"):
        return jnp.zeros(shape, jnp.float32)
    if leaf == "in_proj":
        return u(1.0 / math.sqrt(n["d"]))
    if leaf == "out_proj":
        return u(1.0 / math.sqrt(n["d_in"])) / math.sqrt(c["published"]["n_layer"])
    if leaf in ("conv_w", "conv_b"):
        return u(1.0 / math.sqrt(n["k"]))
    if leaf == "A_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if leaf == "dt_bias":
        lo, hi = math.log(1e-3), math.log(1e-1)
        dt = jnp.maximum(jnp.exp(jax.random.uniform(key, shape, jnp.float32, lo, hi)), 1e-4)
        return dt + jnp.log(-jnp.expm1(-dt))
    if leaf == "D":
        return jnp.ones(shape, jnp.float32)
    raise KeyError(f"no initialisation for parameter {'/'.join(names)}")


def train_flops_per_token(c: dict, seq_len: int) -> float:
    """6 per weight of the two projections and of the tied head, plus the
    SSD scan in its chunked form, 3 times its forward matrix products:
    C·Bᵀ and its causal product with x inside each chunk ((cs+1)/2 earlier
    positions on average), the chunk states, and the carried states read
    back (2·h·p·n each). The depthwise convolution and the elementwise
    work are not counted."""
    n = _dims(c)
    proj = n["d"] * (2 * n["d_in"] + 2 * n["g"] * n["n"] + n["h"]) + n["d_in"] * n["d"]
    weights = n["L"] * proj + n["V"] * n["d"]
    avg = (n["cs"] + 1) / 2
    hp = n["h"] * n["p"]
    ssd = 2 * n["g"] * n["n"] * avg + 2 * hp * avg + 2 * hp * n["n"] * 2
    return 6.0 * weights + 3.0 * n["L"] * ssd


def _segsum(x: jax.Array) -> jax.Array:
    """x (..., T) → (..., T, T): sum of x[j+1..i] below the diagonal, -inf above."""
    T = x.shape[-1]
    xx = jnp.broadcast_to(x[..., None], x.shape + (T,))
    xx = jnp.where(jnp.tril(jnp.ones((T, T), bool), -1), xx, 0.0)
    s = jnp.cumsum(xx, axis=-2)
    return jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)


def ssd(X, A, B, C, chunk: int, mode: str):
    """Listing 1: X (b,l,h,p), A (b,l,h), B/C (b,l,h,n) → Y (b,l,h,p)."""
    b, l, h, p = X.shape
    c = l // chunk
    X = X.reshape(b, c, chunk, h, p)
    B = B.reshape(b, c, chunk, h, -1)
    C = C.reshape(b, c, chunk, h, -1)
    A = A.reshape(b, c, chunk, h).transpose(0, 3, 1, 2)  # b h c l
    A_cum = jnp.cumsum(A, axis=-1)
    Lmat = jnp.exp(_segsum(A))
    scores = mm("bclhn,bcshn->bhcls", C, B, mode) * Lmat
    Y_diag = mm("bhcls,bcshp->bclhp", scores, X, mode)
    decay_states = jnp.exp(A_cum[..., -1:] - A_cum)
    states = mm("bclhn,bclhp->bchpn", B * decay_states.transpose(0, 2, 3, 1)[..., None], X, mode)
    states = jnp.concatenate([jnp.zeros_like(states[:, :1]), states], axis=1)
    decay_chunk = jnp.exp(_segsum(jnp.pad(A_cum[..., -1], ((0, 0), (0, 0), (1, 0)))))
    states = mm("bhzc,bchpn->bzhpn", decay_chunk, states, mode)[:, :-1]
    state_decay_out = jnp.exp(A_cum)
    Y_off = mm("bclhn,bchpn->bclhp", C * state_decay_out.transpose(0, 2, 3, 1)[..., None], states, mode)
    return (Y_diag + Y_off).reshape(b, l, h, p)


def _layer(x, lp: dict, c: dict, mode: str):
    n = _dims(c)
    d_in, h, hp, g, N, k = n["d_in"], n["h"], n["p"], n["g"], n["n"], n["k"]
    eps = float(c["layer_defaults"]["norm_epsilon"])
    Bsz, T, _ = x.shape
    u = rms_norm(x, lp["ln"], eps)
    zxbcdt = mm("btd,dk->btk", u, lp["in_proj"], mode)
    z, xBC, dt = jnp.split(zxbcdt, [d_in, 2 * d_in + 2 * g * N], axis=-1)
    w = lp["conv_w"]  # (conv_dim, k): w[:, k-1] weighs the current position
    padded = jnp.pad(xBC, ((0, 0), (k - 1, 0), (0, 0)))
    xBC = jax.nn.silu(sum(padded[:, i: i + T] * w[:, i] for i in range(k)) + lp["conv_b"])
    xs, Bm, Cm = jnp.split(xBC, [d_in, d_in + g * N], axis=-1)
    xs = xs.reshape(Bsz, T, h, hp)
    Bm = jnp.repeat(Bm.reshape(Bsz, T, g, N), h // g, axis=2)
    Cm = jnp.repeat(Cm.reshape(Bsz, T, g, N), h // g, axis=2)
    dt = jax.nn.softplus(dt + lp["dt_bias"])
    y = ssd(xs * dt[..., None], dt * -jnp.exp(lp["A_log"]), Bm, Cm, n["cs"], mode)
    y = y + lp["D"][:, None] * xs
    y = rms_norm(y.reshape(Bsz, T, d_in) * jax.nn.silu(z), lp["norm"], eps)
    return x + mm("btk,kd->btd", y, lp["out_proj"], mode)


LAYER_LEAVES = ("ln", "in_proj", "conv_w", "conv_b", "dt_bias", "A_log", "D", "norm", "out_proj")


def loss(p: dict, tokens: jax.Array, labels: jax.Array, c: dict, mode: str) -> jax.Array:
    n = _dims(c)
    x = p["embed"][tokens].astype(jnp.float32)
    # recomputed in the backward pass one layer at a time, so that the
    # reference of 24 layers fits beside its state
    layer = jax.checkpoint(lambda x, lp: _layer(x, lp, c, mode))
    for l in range(n["L"]):
        x = layer(x, {k: p[k][l] for k in LAYER_LEAVES})
    x = rms_norm(x, p["ln_f"], float(c["layer_defaults"]["norm_epsilon"]))
    head = p["embed"] if c["tie_embeddings"] else p["out_embed"]
    logits = mm("btd,vd->btv", x, head[: n["V"]], mode)
    return token_nll(logits, labels)
