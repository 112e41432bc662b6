"""Serving launcher: batched decode with the BVLSM-style paged KV cache.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-4b --reduced \
        --requests 8 --prompt-len 32 --max-new 16
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs import get_config
from repro.launch.compile_cache import use_compile_cache
from repro.models import build_model
from repro.serving.engine import Request, ServingEngine, bf16_init


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    params = bf16_init(build_model(cfg))(jax.random.key(0))

    engine = ServingEngine(cfg, params, max_batch=args.max_batch, max_len=256)
    rng = np.random.default_rng(0)
    for rid in range(args.requests):
        prompt = rng.integers(1, cfg.vocab, size=args.prompt_len).astype(np.int32)
        engine.submit(Request(rid, prompt, max_new_tokens=args.max_new))
    done = engine.run_until_drained()
    print("served:", engine.metrics())
    for r in done[:3]:
        print(f"  req {r.req_id}: {len(r.tokens)} tokens, first 8 = {r.tokens[:8]}")


if __name__ == "__main__":
    main()
