#!/usr/bin/env python3
"""Chip smoke: drive the main paths once on a TPU, in one process.

    python chip_smoke.py [--seed N]     # one chip: every phase below
    python chip_smoke.py --four-chips   # four chips: elastic resume only

Phases, in order, each printing one line with its sizes, cuts and result:

  device   platform, device_kind and count. Anything but a TPU exits
           non-zero here: there is no CPU fallback.
  store    the paper's 64 KiB random-write workload on its Table I
           configuration (``paper_exact``): 16,384 values (1 GiB, 8x the
           BVCache) under 16 B keys, flush, close, reopen, then seeded
           keys read back byte for byte through get, multi_get and range.
  train    qwen3-4b at published width with 4 of its 36 layers, 4 steps
           with async checkpoints into ``BVCheckpointStore`` every 2 steps;
           then a fresh ``Trainer`` restores (every leaf checked against
           the manifest's content hash, pipeline cursor checked) and
           trains on to step 6.
  serve    full qwen3-4b (36 layers, bf16 weights) behind
           ``ServingEngine``: 8 requests, 128-token prompts, 16 new tokens
           each; one request's first decode steps checked against a full
           ``model.prefill`` of the same prefix.
  kernels  the four Pallas kernels compiled for the chip at model widths,
           checked against their oracles in ``repro.kernels.ref``.

``--four-chips`` runs only the elastic-restore phase: qwen3-4b at full
width with 8 layers (state too large for one chip), trained on a 2x2
mesh, checkpointed, and resumed on a 1x4 mesh; the resumed losses must
match an uninterrupted 2x2 run.

Weights and data are random, made from ``--seed``. The last line of
stdout is ``{"ok": true, "device": {...}}``, printed only when every phase
passed; any failure exits non-zero without it. Informational timings
(fill MB/s, checkpoint stall and save times) are single runs, not metrics.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import shutil
import sys
import tempfile
import time
from dataclasses import replace
from pathlib import Path

ROOT = Path(__file__).resolve().parent

# decode vs prefill logits: both paths run bf16 weights and activations
# through 36 layers in different orders (decode: fp32 softmax over the
# cache; prefill: bf16 probabilities), so they agree to a few bf16 ulps of
# the logit scale, not bitwise. The bound is 16 ulps (2^-8 each) of the
# largest reference logit.
LOGIT_TOL_ULPS = 16

# bf16 tolerance for the four-chip resumed losses (qwen3-4b: loss ~ 12):
# the same batches on another mesh change only reduction orders
LOSS_ATOL = 2e-2


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def report(phase: str, **fields) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()), flush=True)


def _gib(n: int) -> str:
    return f"{n / 2**30:.2f}"


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------

def device_phase(want: int) -> dict:
    import jax

    devs = jax.devices()
    d = devs[0]
    check(
        d.platform == "tpu",
        f"no TPU: JAX found platform {d.platform!r} ({d.device_kind}); "
        "this smoke runs only on a TPU",
    )
    check(len(devs) >= want, f"need {want} TPU devices, JAX found {len(devs)}")
    dev = {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}
    report("device", platform=d.platform, kind=repr(d.device_kind), count=len(devs))
    return dev


# ---------------------------------------------------------------------------
# store: the paper's 64 KiB random-write workload
# ---------------------------------------------------------------------------

def store_phase(seed: int, n_values: int = 16384, value_size: int = 64 << 10,
                n_checks: int = 1000, n_walks: int = 4, walk_len: int = 64) -> None:
    import numpy as np

    from repro.configs.bvlsm_paper import KEY_SIZE, paper_exact
    from repro.core import DB

    rng = np.random.default_rng(seed)
    pool = rng.bytes(64 * value_size)

    def key(i: int) -> bytes:
        return b"%0*d" % (KEY_SIZE, i)

    def value(i: int) -> bytes:  # unique per key: its index, then pool bytes
        off = (i * 40503) % (len(pool) - value_size)
        return i.to_bytes(8, "little") + pool[off + 8 : off + value_size]

    cfg = paper_exact("wal", "async")
    tmp = tempfile.mkdtemp(prefix="chip_smoke_store_")
    path = str(Path(tmp) / "db")
    try:
        t0 = time.perf_counter()
        db = DB.open(path, cfg)
        for i in rng.permutation(n_values):
            db.put(key(int(i)), value(int(i)))
        db.flush()
        fill_s = time.perf_counter() - t0
        db.close()

        db = DB.open(path, paper_exact("wal", "async"))
        try:
            sample = [int(i) for i in rng.choice(n_values, size=n_checks, replace=False)]
            for i in sample:
                check(db.get(key(i)) == value(i), f"store: get({key(i)!r}) differs after reopen")
            got = db.multi_get([key(i) for i in sample])
            for i, v in zip(sample, got):
                check(v == value(i), f"store: multi_get({key(i)!r}) differs after reopen")
            for start in rng.choice(n_values - walk_len, size=n_walks, replace=False):
                start = int(start)
                items = list(db.range(key(start), limit=walk_len))
                want = [(key(i), value(i)) for i in range(start, start + walk_len)]
                check(items == want, f"store: range({key(start)!r}, limit={walk_len}) differs")
        finally:
            db.close()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    total = n_values * value_size
    report(
        "store", config="paper_exact(wal,async)", values=n_values, value_bytes=value_size,
        key_bytes=KEY_SIZE, total_gib=_gib(total),
        bvcache_mib=cfg.bvcache_bytes >> 20, memtable_mib=cfg.memtable_size >> 20,
        order="seeded-random", reopened=True, get_checked=n_checks,
        multi_get_checked=n_checks, range_walks=f"{n_walks}x{walk_len}", result="pass",
    )
    report("store-info", fill_mb_s=f"{total / fill_s / 1e6:.1f}", fill_s=f"{fill_s:.2f}",
           note="single informational run, not a metric")


# ---------------------------------------------------------------------------
# train + checkpoint + resume
# ---------------------------------------------------------------------------

def _trainer_config(ckpt_dir: str, steps: int, interval: int, batch: int, seq: int, seed: int):
    from repro.training.optimizer import OptimizerConfig
    from repro.training.train_step import TrainConfig
    from repro.training.trainer import TrainerConfig

    return TrainerConfig(
        steps=steps, global_batch=batch, seq_len=seq, ckpt_dir=ckpt_dir,
        ckpt_interval=interval, ckpt_async=True, keep_last=2, seed=seed,
        log_every=10**9,
        train=TrainConfig(opt=OptimizerConfig(warmup_steps=2, total_steps=100)),
    )


def _losses(result: dict) -> list[float]:
    losses = [m["loss"] for m in result["metrics"]]
    check(all(math.isfinite(x) for x in losses), f"train: non-finite loss in {losses}")
    return losses


def _verified_restore(trainer, record: dict) -> None:
    """Make ``trainer``'s own restore also check every restored leaf
    against the content hash in the step's manifest, one leaf at a time on
    the host, and record the restored step and pipeline cursor."""
    import jax
    import numpy as np

    from repro.checkpoint.bvstore import content_hash

    restore = trainer._init_or_restore

    def restore_and_verify():
        t0 = time.perf_counter()
        step = restore()
        jax.block_until_ready(trainer.state)
        record["restore_s"] = time.perf_counter() - t0
        want = {e["path"]: e["hash"] for e in trainer.store.load_meta(step)["manifest"]}
        flat = jax.tree_util.tree_flatten_with_path(trainer.state)[0]
        check(len(flat) == len(want), f"restore: {len(flat)} leaves, manifest has {len(want)}")
        for kp, leaf in flat:
            path = jax.tree_util.keystr(kp)
            got = content_hash(np.asarray(jax.device_get(leaf)).tobytes())
            check(got == want.get(path), f"restore: leaf {path} does not match its manifest hash")
        record.update(step=step, cursor=trainer.pipeline.state_dict()["step"], leaves=len(flat))
        return step

    trainer._init_or_restore = restore_and_verify


def _state_bytes(trainer) -> int:
    import jax

    return sum(x.nbytes for x in jax.tree.leaves(trainer.state))


def train_phase(cfg, cut: str, seed: int, batch: int = 4, seq: int = 512) -> None:
    from repro.training.trainer import Trainer

    tmp = tempfile.mkdtemp(prefix="chip_smoke_ckpt_")
    try:
        tcfg = _trainer_config(tmp, steps=4, interval=2, batch=batch, seq=seq, seed=seed)
        t0 = time.perf_counter()
        tr = Trainer(cfg, tcfg)
        try:
            res = tr.run()
        finally:
            tr.close()
        losses = _losses(res)
        check(res["status"] == "done" and len(losses) == 4, f"train: {res['status']} {losses}")
        state_bytes = _state_bytes(tr)
        stall, saves = tr.ckpt.stall_seconds, list(tr.ckpt.save_seconds)
        tr.state = None
        del tr
        gc.collect()
        report(
            "train", model=cfg.name, d_model=cfg.d_model, heads=f"{cfg.n_heads}/{cfg.n_kv_heads}",
            head_dim=cfg.resolved_head_dim, d_ff=cfg.d_ff, vocab=cfg.vocab, qk_norm=cfg.qk_norm,
            tied=cfg.tie_embeddings, n_layers=cfg.n_layers, cut=repr(cut), batch=batch, seq=seq,
            steps=4, ckpt_interval=2, ckpt_async=True, state_gib=_gib(state_bytes),
            losses=[round(x, 4) for x in losses], secs=f"{time.perf_counter() - t0:.1f}",
            result="pass",
        )

        rec: dict = {}
        t0 = time.perf_counter()
        tr = Trainer(cfg, replace(tcfg, steps=6))
        _verified_restore(tr, rec)
        try:
            res = tr.run()
        finally:
            tr.close()
        losses = _losses(res)
        check(rec.get("step") == 4, f"resume: restored step {rec.get('step')}, want 4")
        check(rec["cursor"] == 4, f"resume: pipeline cursor {rec['cursor']}, want 4")
        check(res["step"] == 6 and len(losses) == 2, f"resume: ended at {res['step']} {losses}")
        resumed_saves = list(tr.ckpt.save_seconds)
        tr.state = None
        del tr
        gc.collect()
        report(
            "train-resume", restored_step=rec["step"], cursor=rec["cursor"],
            leaves_hash_checked=rec["leaves"], steps_to=6, losses=[round(x, 4) for x in losses],
            secs=f"{time.perf_counter() - t0:.1f}", result="pass",
        )
        report(
            "train-info", stall_s=f"{stall:.3f}", save_gib=_gib(state_bytes),
            save_s=[round(s, 2) for s in saves + resumed_saves],
            restore_s=f"{rec['restore_s']:.2f}", note="single informational run, not a metric",
        )
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def serve_phase(cfg, seed: int, n_requests: int = 8, prompt_len: int = 128, new_tokens: int = 16,
                max_batch: int = 4, max_len: int = 256, ref_steps: int = 4) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import build_model
    from repro.serving.engine import Request, ServingEngine, bf16_init

    t0 = time.perf_counter()
    model = build_model(cfg)
    params = bf16_init(model)(jax.random.key(seed))
    n_params = sum(p.size for p in jax.tree.leaves(params))
    param_bytes = sum(p.nbytes for p in jax.tree.leaves(params))

    rng = np.random.default_rng(seed)
    prompts = rng.integers(1, cfg.vocab, size=(n_requests, prompt_len)).astype(np.int32)
    engine = ServingEngine(cfg, params, max_batch=max_batch, max_len=max_len)
    for rid in range(n_requests):
        engine.submit(Request(rid, prompts[rid], max_new_tokens=new_tokens))
    done = engine.run_until_drained()
    check(len(done) == n_requests, f"serve: {len(done)} of {n_requests} requests finished")
    for r in done:
        check(len(r.tokens) == new_tokens, f"serve: request {r.req_id} got {len(r.tokens)} tokens")

    # one request's decode steps, replayed through the engine's own jitted
    # prefill/decode, against a full prefill of the same prefix
    req = min(done, key=lambda r: r.req_id)
    prefill = jax.jit(model.prefill)
    logits, cache = engine._prefill(params, jnp.asarray(prompts[req.req_id])[None])
    check(int(jnp.argmax(logits[0])) == req.tokens[0], "serve: prefill token differs on replay")
    worst = 0.0
    for j in range(1, ref_steps + 1):
        logits, cache = engine._decode(params, cache, jnp.asarray([[req.tokens[j - 1]]], jnp.int32))
        dec = np.asarray(logits[0], np.float32)[: cfg.vocab]
        check(int(np.argmax(dec)) == req.tokens[j], f"serve: decode step {j} differs on replay")
        prefix = np.concatenate([prompts[req.req_id], np.asarray(req.tokens[:j], np.int32)])
        ref = np.asarray(prefill(params, jnp.asarray(prefix)[None])[0][0], np.float32)[: cfg.vocab]
        tol = LOGIT_TOL_ULPS * 2.0**-8 * max(1.0, float(np.abs(ref).max()))
        diff = float(np.abs(dec - ref).max())
        worst = max(worst, diff / tol)
        check(diff <= tol, f"serve: decode step {j} logits differ from prefill by {diff:.4f} > {tol:.4f}")
        top2 = np.sort(ref)[-2:]
        check(int(np.argmax(ref)) == req.tokens[j] or top2[1] - top2[0] <= tol,
              f"serve: decode step {j} token {req.tokens[j]} is not the reference's clear top-1")
    m = engine.metrics()
    del engine, params, cache, logits
    gc.collect()
    report(
        "serve", model=cfg.name, n_layers=cfg.n_layers, d_model=cfg.d_model,
        params_b=f"{n_params / 1e9:.2f}", param_gib=_gib(param_bytes), dtype="bf16",
        init="jit(init+cast)", requests=n_requests, prompt=prompt_len, new_tokens=new_tokens,
        max_batch=max_batch, max_len=max_len, tokens=m["tokens"], ref_steps=ref_steps,
        logit_tol=f"{LOGIT_TOL_ULPS}ulp(bf16)*max|ref|", worst_diff_over_tol=f"{worst:.3f}",
        secs=f"{time.perf_counter() - t0:.1f}", result="pass",
    )


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

# model widths, the same as tests/test_tpu_compile.py compiles
KERNEL_SHAPES = {
    # qwen3-4b attention: 32 query / 8 kv heads of 128
    "flash": dict(B=1, T=4096, H=32, K=8, hd=128),
    "paged": dict(B=4, H=32, K=8, hd=128, P=64, pages=(64, 128), maxp=8),
    # mamba2-1.3b: 64 heads of 64, state 128, chunk 256
    "ssd": dict(b=1, t=512, h=64, p=64, n=128, chunk=256),
    # recurrentgemma-9b: RG-LRU width 4096
    "rglru": dict(B=4, T=512, W=4096),
}


def kernel_phase(seed: int, shapes: dict = KERNEL_SHAPES, interpret: bool = False) -> None:
    """Each kernel once against its oracle, at tests/test_kernels.py's
    tolerances. Oracles run at full fp32 matmul precision."""
    import jax
    import jax.numpy as jnp
    import numpy as np

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

    rng = np.random.default_rng(seed)
    bf16 = jnp.bfloat16

    def arr(shape, dtype, kind="normal"):
        x = rng.normal(size=shape) if kind == "normal" else rng.uniform(size=shape)
        return jnp.asarray(x, dtype)

    def oracle(fn, *args, **kw):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kw)

    def close(name, got, want, atol, rtol):
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        check(got.shape == want.shape and np.isfinite(got).all(), f"kernels: {name} shape/finite")
        np.testing.assert_allclose(got, want, atol=atol, rtol=rtol, err_msg=f"kernels: {name}")
        return float(np.abs(got - want).max())

    errs = {}
    s = shapes["flash"]
    q = arr((s["B"], s["T"], s["H"], s["hd"]), bf16)
    k = arr((s["B"], s["T"], s["K"], s["hd"]), bf16)
    v = arr((s["B"], s["T"], s["K"], s["hd"]), bf16)
    out = flash_attention(q, k, v, causal=True, interpret=interpret)
    errs["flash"] = close("flash", out, oracle(mha_reference, q, k, v, causal=True), 2e-2, 1e-2)
    del q, k, v, out

    s = shapes["paged"]
    for page in s["pages"]:
        q = arr((s["B"], s["H"], s["hd"]), bf16)
        pk = arr((s["P"], page, s["K"], s["hd"]), bf16)
        pv = arr((s["P"], page, s["K"], s["hd"]), bf16)
        pt = jnp.asarray(rng.integers(0, s["P"], size=(s["B"], s["maxp"])), jnp.int32)
        lengths = jnp.asarray(rng.integers(1, s["maxp"] * page, size=(s["B"],)), jnp.int32)
        out = paged_decode_attention(q, pk, pv, pt, lengths, interpret=interpret)
        errs[f"paged{page}"] = close(f"paged page={page}", out,
                                     oracle(paged_decode_reference, q, pk, pv, pt, lengths), 2e-2, 1e-2)

    s = shapes["ssd"]
    x = arr((s["b"], s["t"], s["h"], s["p"]), jnp.float32)
    dA = -jnp.abs(arr((s["b"], s["t"], s["h"]), jnp.float32)) * 0.3
    B_ = arr((s["b"], s["t"], 1, s["n"]), jnp.float32)
    C_ = arr((s["b"], s["t"], 1, s["n"]), jnp.float32)
    y, st = ssd_chunked_pallas(x, dA, B_, C_, s["chunk"], interpret=interpret)
    yr, sr = oracle(ssd_chunk_reference, x, dA, B_, C_)
    errs["ssd"] = max(close("ssd y", y, yr, 5e-4, 1e-3), close("ssd state", st, sr, 5e-4, 1e-3))

    s = shapes["rglru"]
    x = arr((s["B"], s["T"], s["W"]), bf16)
    r = arr((s["B"], s["T"], s["W"]), bf16, "uniform")
    i = arr((s["B"], s["T"], s["W"]), bf16, "uniform")
    lam = jnp.asarray(rng.uniform(0.5, 4.0, size=(s["W"],)), jnp.float32)
    y, h = rglru_pallas(x, r, i, lam, interpret=interpret)
    yr, hr = oracle(rglru_reference, x, r, i, lam)
    errs["rglru"] = max(close("rglru y", y, yr, 2e-2, 1e-2), close("rglru h", h, hr, 2e-2, 1e-2))
    report(
        "kernels", mode="interpret" if interpret else "compiled",
        shapes=json.dumps(shapes, separators=(",", ":")),
        max_abs_err=json.dumps({k: float(f"{v:.3g}") for k, v in errs.items()}, separators=(",", ":")),
        result="pass",
    )


# ---------------------------------------------------------------------------
# four chips: elastic restore across meshes
# ---------------------------------------------------------------------------

def four_chip_phase(cfg, cut: str, seed: int, batch: int = 4, seq: int = 512,
                    mesh_a=(2, 2), mesh_b=(1, 4)) -> None:
    import jax

    from repro.launch.mesh import make_host_mesh
    from repro.training.trainer import Trainer

    t0 = time.perf_counter()
    hbm = jax.devices()[0].memory_stats() or {}
    tmp = tempfile.mkdtemp(prefix="chip_smoke_elastic_")
    try:
        # run A: 4 uninterrupted steps on mesh_a
        tr = Trainer(cfg, _trainer_config(f"{tmp}/a", 4, 100, batch, seq, seed),
                     mesh=make_host_mesh(mesh_a))
        try:
            ref = _losses(tr.run())
        finally:
            tr.close()
        state_bytes = _state_bytes(tr)
        tr.state = None
        # run B: 2 steps and an async save on mesh_a ...
        tr = Trainer(cfg, _trainer_config(f"{tmp}/b", 2, 2, batch, seq, seed),
                     mesh=make_host_mesh(mesh_a))
        try:
            first = _losses(tr.run())
        finally:
            tr.close()
        tr.state = None
        # ... then a fresh trainer resumes on mesh_b for 2 more
        rec: dict = {}
        tr = Trainer(cfg, _trainer_config(f"{tmp}/b", 4, 100, batch, seq, seed),
                     mesh=make_host_mesh(mesh_b))
        _verified_restore(tr, rec)
        try:
            resumed = _losses(tr.run())
        finally:
            tr.close()
        tr.state = None
        gc.collect()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    check(rec.get("step") == 2 and rec["cursor"] == 2, f"elastic: restored {rec}")
    check(len(ref) == 4 and len(first) == 2 and len(resumed) == 2, "elastic: step counts")
    diff = max(abs(a - b) for a, b in zip(ref[2:], resumed))
    check(abs(first[0] - ref[0]) <= LOSS_ATOL and diff <= LOSS_ATOL,
          f"elastic: resumed losses {resumed} vs uninterrupted {ref[2:]} (tol {LOSS_ATOL})")
    report(
        "elastic", model=cfg.name, d_model=cfg.d_model, n_layers=cfg.n_layers, cut=repr(cut),
        state_gib=_gib(state_bytes), hbm_per_chip_gib=_gib(hbm.get("bytes_limit", 0)),
        batch=batch, seq=seq, run_a=f"{mesh_a} 4 steps", run_b=f"{mesh_a} 2 steps -> save -> {mesh_b}",
        losses_a=[round(x, 4) for x in ref], losses_b=[round(x, 4) for x in first + resumed],
        max_loss_diff=f"{diff:.2e}", loss_atol=LOSS_ATOL, leaves_hash_checked=rec["leaves"],
        secs=f"{time.perf_counter() - t0:.1f}", result="pass",
    )
    report("elastic-info", restore_s=f"{rec['restore_s']:.2f}",
           note="single informational run, not a metric")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip elastic restore phase")
    ap.add_argument("--seed", type=int, default=0, help="seed for weights and data")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no repository sources at {ROOT / 'src' / 'repro'}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import use_compile_cache

    cache_dir = use_compile_cache()
    try:
        dev = device_phase(4 if args.four_chips else 1)
        print(f"[cache] jax_compilation_cache_dir={cache_dir}", flush=True)

        from repro.configs import get_config

        qwen = get_config("qwen3-4b")
        if args.four_chips:
            check(dev["count"] == 4, f"--four-chips needs exactly 4 devices, found {dev['count']}")
            four_chip_phase(replace(qwen, n_layers=8), f"n_layers {qwen.n_layers}->8", args.seed)
        else:
            store_phase(args.seed)
            train_phase(replace(qwen, n_layers=4), f"n_layers {qwen.n_layers}->4", args.seed)
            serve_phase(qwen, args.seed)
            kernel_phase(args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
