"""Paged KV allocator invariants (hypothesis), host page cache semantics,
and the continuous-batching engine end-to-end."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import get_config
from repro.models import build_model
from repro.serving.engine import Request, ServingEngine
from repro.serving.kv_cache import HostPageCache, OutOfPages, PagedKVCache


def _kv(num_pages=16, page=8, maxp=4):
    return PagedKVCache(num_pages, page, n_layers=2, n_kv_heads=2, head_dim=8,
                        max_pages_per_seq=maxp)


def test_alloc_free_roundtrip():
    kv = _kv()
    kv.admit(1, prompt_len=20)  # 3 pages at page=8
    assert len(kv.seqs[1].pages) == 3
    assert kv.utilization() == 3 / 16
    kv.release(1)
    assert kv.utilization() == 0.0


def test_out_of_pages():
    kv = _kv(num_pages=4, maxp=8)
    kv.admit(1, prompt_len=30)  # needs 4 pages
    kv.admit(2)
    with pytest.raises(OutOfPages):
        kv.reserve(2, 10)


def test_page_table_and_lengths():
    kv = _kv()
    kv.admit(7, prompt_len=10)
    kv.admit(9, prompt_len=3)
    pt = kv.page_table([7, 9])
    assert pt.shape == (2, 4)
    assert (kv.lengths([7, 9]) == np.array([10, 3])).all()
    # no page shared between sequences
    assert set(kv.seqs[7].pages).isdisjoint(kv.seqs[9].pages)


@settings(max_examples=30, deadline=None)
@given(
    ops=st.lists(
        st.tuples(st.sampled_from(["admit", "reserve", "release"]), st.integers(0, 5), st.integers(1, 12)),
        max_size=60,
    )
)
def test_allocator_invariants(ops):
    """No page is ever owned by two sequences; free+owned == total."""
    kv = _kv(num_pages=12, page=4, maxp=6)
    for op, sid, n in ops:
        try:
            if op == "admit" and sid not in kv.seqs:
                kv.admit(sid)
            elif op == "reserve" and sid in kv.seqs:
                kv.reserve(sid, n)
            elif op == "release" and sid in kv.seqs:
                kv.release(sid)
        except OutOfPages:
            pass
        owned = [p for s in kv.seqs.values() for p in s.pages]
        assert len(owned) == len(set(owned))  # no double allocation
        assert sorted(owned + kv.free) == list(range(12))  # conservation


def test_host_page_cache_mrwf_pin():
    c = HostPageCache(capacity_pages=2)
    c.put(("s1", 0), np.zeros(4), pinned=True)
    c.put(("s1", 1), np.ones(4))
    c.put(("s1", 2), np.ones(4) * 2)  # evicts (s1,1) — (s1,0) pinned
    assert ("s1", 0) in c._map  # pinned survives
    assert ("s1", 1) not in c._map
    c.unpin(("s1", 0))
    c.put(("s1", 3), np.ones(4) * 3)
    assert ("s1", 0) not in c._map  # LRU + unpinned → evicted


def test_engine_end_to_end():
    cfg = get_config("llama3-8b").reduced(d_model=64, n_layers=2, vocab=256, vocab_pad_multiple=64)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    engine = ServingEngine(cfg, params, max_batch=2, max_len=64, page_size=16)
    rng = np.random.default_rng(0)
    for rid in range(5):
        engine.submit(Request(rid, rng.integers(1, cfg.vocab, 8).astype(np.int32), max_new_tokens=6))
    done = engine.run_until_drained()
    assert len(done) == 5
    assert all(len(r.tokens) == 6 for r in done)
    m = engine.metrics()
    assert m["tokens"] == 30
    assert engine.kv.utilization() == 0.0  # everything freed


def test_engine_greedy_matches_manual_decode():
    """Engine tokens == manual prefill+decode_step loop (same params)."""
    cfg = get_config("llama3-8b").reduced(d_model=64, n_layers=2, vocab=256, vocab_pad_multiple=64)
    model = build_model(cfg)
    params = model.init(jax.random.key(1))
    prompt = np.arange(1, 9, dtype=np.int32)

    engine = ServingEngine(cfg, params, max_batch=1, max_len=64, page_size=16)
    engine.submit(Request(0, prompt, max_new_tokens=5))
    (req,) = engine.run_until_drained()

    logits, cache = model.prefill(params, jnp.asarray(prompt)[None], pad_to=64)
    toks = [int(jnp.argmax(logits[0]))]
    for _ in range(4):
        logits, cache = model.decode_step(params, cache, jnp.asarray([[toks[-1]]], jnp.int32))
        toks.append(int(jnp.argmax(logits[0])))
    assert req.tokens == toks


def test_bf16_init_is_the_eager_init_cast():
    """One jitted program gives the same bf16 parameters as the eager
    fp32 init followed by a cast."""
    from repro.serving.engine import bf16_init

    cfg = get_config("qwen3-4b").reduced()
    model = build_model(cfg)
    got = bf16_init(model)(jax.random.key(0))
    want = jax.tree.map(lambda p: p.astype(jnp.bfloat16), model.init(jax.random.key(0)))
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.dtype == jnp.bfloat16
        np.testing.assert_array_equal(np.asarray(g, np.float32), np.asarray(w, np.float32))
