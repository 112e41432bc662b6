"""Continuous-batching serving engine over the paged KV cache.

Requests queue up; each engine step (1) admits pending requests while pages
remain (prefill builds their cache), (2) decodes one token for every active
sequence in a single batched ``decode_step``, (3) retires finished
sequences and frees their pages. The page-table indirection (the paper's
Key-ValueOffset) is what makes admission/eviction O(1) metadata ops rather
than cache copies.

This engine drives the *contiguous-cache* decode path of the models
(models/*.decode_step) batched over active sequences; the Pallas
``paged_decode`` kernel is the TPU hot path consuming the same page tables
(exercised in examples/serve_paged.py and tests).
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import build_model
from .kv_cache import OutOfPages, PagedKVCache


@dataclass
class Request:
    req_id: int
    prompt: np.ndarray  # (prompt_len,) int32
    max_new_tokens: int = 16
    submitted_at: float = field(default_factory=time.monotonic)
    tokens: list[int] = field(default_factory=list)
    first_token_at: float | None = None
    done_at: float | None = None


def bf16_init(model):
    """``key -> params``: the model's initialisation with every fp32
    parameter cast to bf16, jitted as one program so the fp32 parameters
    never sit whole on the device."""

    def init(key):
        params = model.init(key)
        return jax.tree.map(
            lambda p: p.astype(jnp.bfloat16) if p.dtype == jnp.float32 else p, params
        )

    return jax.jit(init)


class ServingEngine:
    def __init__(self, model_cfg, params, max_batch: int = 8, max_len: int = 512,
                 page_size: int = 64):
        self.cfg = model_cfg
        self.model = build_model(model_cfg)
        self.params = params
        self.max_batch = max_batch
        self.max_len = max_len
        self.page_size = page_size
        self.kv = PagedKVCache(
            num_pages=max_batch * (max_len // page_size + 1) * 2,
            page_size=page_size,
            n_layers=model_cfg.n_layers,
            n_kv_heads=max(model_cfg.n_kv_heads, 1),
            head_dim=model_cfg.resolved_head_dim,
            max_pages_per_seq=max_len // page_size + 1,
        )
        self.pending: list[Request] = []
        self.active: dict[int, Request] = {}
        self.caches: dict[int, dict] = {}  # per-seq model cache (contiguous path)
        self.finished: list[Request] = []
        self._decode = jax.jit(lambda p, c, t: self.model.decode_step(p, c, t))
        self._prefill = jax.jit(lambda p, t: self.model.prefill(p, t, pad_to=self.max_len))

    # ------------------------------------------------------------------
    def submit(self, req: Request) -> None:
        self.pending.append(req)

    def _admit(self) -> None:
        while self.pending and len(self.active) < self.max_batch:
            req = self.pending[0]
            try:
                self.kv.admit(req.req_id, len(req.prompt))
            except OutOfPages:
                break
            self.pending.pop(0)
            logits, cache = self._prefill(self.params, jnp.asarray(req.prompt)[None])
            tok = int(jnp.argmax(logits[0]))
            req.tokens.append(tok)
            req.first_token_at = time.monotonic()
            self.kv.reserve(req.req_id, 1)
            self.active[req.req_id] = req
            self.caches[req.req_id] = cache

    def _retire(self, req: Request) -> None:
        req.done_at = time.monotonic()
        self.kv.release(req.req_id)
        self.caches.pop(req.req_id)
        self.active.pop(req.req_id)
        self.finished.append(req)

    def step(self) -> int:
        """One engine iteration; returns number of tokens produced."""
        self._admit()
        if not self.active:
            return 0
        produced = 0
        for sid in list(self.active):
            req = self.active[sid]
            cache = self.caches[sid]
            last = jnp.asarray([[req.tokens[-1]]], jnp.int32)
            logits, cache = self._decode(self.params, cache, last)
            self.caches[sid] = cache
            tok = int(jnp.argmax(logits[0]))
            req.tokens.append(tok)
            produced += 1
            try:
                self.kv.reserve(sid, 1)
            except OutOfPages:
                self._retire(req)
                continue
            if len(req.tokens) >= req.max_new_tokens or int(cache["length"]) >= self.max_len - 1:
                self._retire(req)
        return produced

    def run_until_drained(self, max_steps: int = 10_000) -> list[Request]:
        for _ in range(max_steps):
            if not self.pending and not self.active:
                break
            self.step()
        return self.finished

    def metrics(self) -> dict:
        lat = [r.done_at - r.submitted_at for r in self.finished if r.done_at]
        ttft = [r.first_token_at - r.submitted_at for r in self.finished if r.first_token_at]
        toks = sum(len(r.tokens) for r in self.finished)
        return {
            "requests": len(self.finished),
            "tokens": toks,
            "mean_latency_s": float(np.mean(lat)) if lat else 0.0,
            "mean_ttft_s": float(np.mean(ttft)) if ttft else 0.0,
            "kv_utilization": self.kv.utilization(),
        }
