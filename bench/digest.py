"""Exact digests of a state pytree, computed on the device.

Each leaf's bytes are read as unsigned words and reduced to two words: their
sum and their position-weighted sum, both modulo 2**32. Integer sums do not
depend on the order of reduction, so the same bytes give the same digest on
any device and in any layout; a changed, lost or moved word changes it. The
check compares the state that was snapshotted for a save, or saved before a
restore, with what comes back, without keeping a second copy of a state that
fills the chip.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

_WORD = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}


def _words(x: jax.Array) -> jax.Array:
    size = jnp.dtype(x.dtype).itemsize
    if size == 8:
        return jax.lax.bitcast_convert_type(x, jnp.uint32).reshape(-1)
    return jax.lax.bitcast_convert_type(x, _WORD[size]).astype(jnp.uint32).reshape(-1)


def _leaf(x: jax.Array) -> jax.Array:
    w = _words(x)
    pos = jax.lax.iota(jnp.uint32, w.shape[0]) * jnp.uint32(2654435761) + jnp.uint32(1)
    return jnp.stack([jnp.sum(w, dtype=jnp.uint32), jnp.sum(w * pos, dtype=jnp.uint32)])


@jax.jit
def digest(tree) -> jax.Array:
    """(leaves, 2) uint32, in ``jax.tree.leaves`` order."""
    return jnp.stack([_leaf(x) for x in jax.tree.leaves(tree)])


def mismatches(got, want) -> int:
    """Leaves whose digests differ; a missing side counts every leaf."""
    want = np.asarray(want)
    if got is None:
        return int(want.shape[0])
    return int(np.any(np.asarray(got) != want, axis=1).sum())
