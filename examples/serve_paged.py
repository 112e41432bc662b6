"""Serving example: continuous batching over the BVLSM-style paged KV cache,
plus the paged flash-decode Pallas kernel consuming the same page tables
(interpret mode on CPU; native on TPU).

    PYTHONPATH=src python examples/serve_paged.py
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.kernels import paged_decode
from repro.kernels.ref import paged_decode_reference
from repro.models import build_model
from repro.serving.engine import Request, ServingEngine
from repro.serving.kv_cache import PagedKVCache

# --- 1. continuous-batching engine -----------------------------------------
cfg = get_config("qwen3-4b").reduced()
model = build_model(cfg)
params = model.init(jax.random.key(0))

engine = ServingEngine(cfg, params, max_batch=4, max_len=128, page_size=32)
rng = np.random.default_rng(0)
for rid in range(10):
    engine.submit(Request(rid, rng.integers(1, cfg.vocab, 24).astype(np.int32), max_new_tokens=12))
t0 = time.monotonic()
done = engine.run_until_drained()
dt = time.monotonic() - t0
m = engine.metrics()
print(f"served {m['requests']} requests / {m['tokens']} tokens in {dt:.1f}s")
print(f"mean latency {m['mean_latency_s']*1e3:.0f} ms, TTFT {m['mean_ttft_s']*1e3:.0f} ms")

# --- 2. the BVLSM read path on TPU: page table → page gather → attention ----
print("\npaged flash-decode kernel (page table = Key-ValueOffset metadata):")
B, H, K, hd, P, page, maxp = 4, 8, 4, 64, 32, 128, 4
kv = PagedKVCache(P, page, n_layers=1, n_kv_heads=K, head_dim=hd, max_pages_per_seq=maxp, dtype=jnp.float32)
for sid in range(B):
    kv.admit(sid, prompt_len=int(rng.integers(100, maxp * page)))
pt = jnp.asarray(kv.page_table(range(B)))
lengths = jnp.asarray(kv.lengths(range(B)))
pages_k = jnp.asarray(rng.normal(size=(P, page, K, hd)), jnp.float32)
pages_v = jnp.asarray(rng.normal(size=(P, page, K, hd)), jnp.float32)
q = jnp.asarray(rng.normal(size=(B, H, hd)), jnp.float32)

out = paged_decode(q, pages_k, pages_v, pt, lengths)  # Pallas kernel
ref = paged_decode_reference(q, pages_k, pages_v, pt, lengths)
print(f"  kernel vs oracle max|Δ| = {float(jnp.max(jnp.abs(out-ref))):.2e}")
print(f"  page-table bytes per seq: {pt.shape[1]*4} B — the only metadata the scheduler touches")
print(f"  arena utilization: {kv.utilization():.0%}")
