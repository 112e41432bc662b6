import jax.numpy as jnp
import numpy as np

from bench import digest as dg


def _state():
    return {"a": jnp.arange(12, dtype=jnp.float32).reshape(3, 4) / 7,
            "b": {"c": jnp.ones((5,), jnp.bfloat16), "n": jnp.int32(50_000)}}


def test_same_bytes_same_digest_in_any_layout():
    s = _state()
    host = {"a": np.asarray(s["a"]), "b": {"c": np.asarray(s["b"]["c"]), "n": np.int32(50_000)}}
    again = {"a": jnp.asarray(host["a"]), "b": {"c": jnp.asarray(host["b"]["c"]),
                                                "n": jnp.asarray(host["b"]["n"])}}
    assert dg.mismatches(dg.digest(again), dg.digest(s)) == 0


def test_changed_moved_or_rounded_words_are_seen():
    s = _state()
    want = dg.digest(s)
    flipped = dict(s, a=s["a"].at[1, 2].add(1e-6))
    swapped = dict(s, a=s["a"][::-1])
    rounded = dict(s, a=s["a"].astype(jnp.bfloat16).astype(jnp.float32))
    for bad in (flipped, swapped, rounded):
        assert dg.mismatches(dg.digest(bad), want) == 1
    assert dg.mismatches(None, want) == 3
