"""Faults planted under the timed path, for the tests and the calibration
of the check's limits. A run never plants one by itself.

Each fault breaks what one cell can get wrong: a step that returns its state
unchanged; half of the batch left out, the mean taken over the rest; a
checkpoint whose bytes are altered where they are written; a restore that
alters what it read. ``bf16_restore`` is the resume cell's control: the
restored state one precision step below the float32 it was saved in.
"""
from __future__ import annotations

import contextlib

import numpy as np


class Fault:
    name = "none"
    make_train_step = None

    def patch(self):
        return contextlib.nullcontext()


class _StepFault(Fault):
    def __init__(self, name, wrap):
        self.name = name
        self._wrap = wrap

    def make_train_step(self, model, train_cfg):
        from repro.training.train_step import make_train_step

        return self._wrap(make_train_step(model, train_cfg))


def _unchanged(step):
    def f(state, batch):
        _, metrics = step(state, batch)
        return state, metrics
    return f


def _half_batch(step):
    def f(state, batch):
        return step(state, {k: v[: v.shape[0] // 2] for k, v in batch.items()})
    return f


def _flip_one_bit(tree):
    """A copy of ``tree`` whose first array leaf has one bit flipped."""
    import jax

    leaves, treedef = jax.tree.flatten(tree)
    for i, leaf in enumerate(leaves):
        arr = np.array(leaf, copy=True)
        if arr.ndim:
            arr.reshape(-1).view(np.uint8)[0] ^= 1
            leaves[i] = arr
            break
    return jax.tree.unflatten(treedef, leaves)


def _through_bf16(tree):
    import jax
    import ml_dtypes

    def cast(a):
        a = np.asarray(a)
        return a.astype(ml_dtypes.bfloat16).astype(a.dtype) if a.dtype == np.float32 else a
    return jax.tree.map(cast, tree)


class _StoreFault(Fault):
    def __init__(self, name, method, on_input, alter):
        self.name, self._method, self._on_input, self._alter = name, method, on_input, alter

    @contextlib.contextmanager
    def patch(self):
        from repro.checkpoint.bvstore import BVCheckpointStore

        real = getattr(BVCheckpointStore, self._method)
        alter = self._alter

        if self._on_input:
            def patched(store, step, state, *a, **kw):
                return real(store, step, alter(state), *a, **kw)
        else:
            def patched(store, *a, **kw):
                state, meta = real(store, *a, **kw)
                return alter(state), meta
        setattr(BVCheckpointStore, self._method, patched)
        try:
            yield
        finally:
            setattr(BVCheckpointStore, self._method, real)


FAULTS = {
    "state_unchanged": _StepFault("state_unchanged", _unchanged),
    "half_batch": _StepFault("half_batch", _half_batch),
    "altered_save": _StoreFault("altered_save", "save", True, _flip_one_bit),
    "altered_restore": _StoreFault("altered_restore", "load", False, _flip_one_bit),
    "bf16_restore": _StoreFault("bf16_restore", "load", False, _through_bf16),
}
