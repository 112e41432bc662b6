"""Logical-axis sharding: named axes on params/activations, resolved to
mesh ``PartitionSpec``s by a rule table.

Model code annotates every tensor dimension with a *logical* name
(:class:`Axes` for param pytrees, plain tuples at ``constrain`` call
sites); :func:`logical_to_spec` maps those names onto the *physical* mesh
axes via :func:`default_rules`, with two safety valves:

* **divisibility fallback** — a dim that doesn't divide the candidate mesh
  axes is replicated instead (never a lowering error: the 104B dry-run and
  the 1-device test mesh share one rule table);
* **first-dim-wins conflict resolution** — a mesh axis claimed by an
  earlier dimension of the same tensor is unavailable to later dims, which
  fall through to their next candidate (or replicate).

The active mesh is ambient (:func:`mesh_context` / :func:`active_mesh`)
so model code stays mesh-agnostic: :func:`constrain` is the identity when
no mesh is installed, and a ``with_sharding_constraint`` under one.
"""
from __future__ import annotations

import contextlib
import contextvars

import jax
from jax.sharding import NamedSharding, PartitionSpec


class Axes:
    """Logical axis names for one tensor, e.g. ``Axes("layers", "param_embed",
    "heads")``. ``None`` marks a dimension that is always replicated.

    Deliberately NOT a pytree container: an ``Axes`` is a *leaf*, so a tree
    of them can be ``jax.tree.map``-ed in parallel with the matching params
    tree. The raw name tuple is exposed as ``.t`` for slicing (e.g. dropping
    the scanned ``"layers"`` dim: ``Axes(*ax.t[1:])``).
    """

    __slots__ = ("t",)

    def __init__(self, *names: str | None):
        self.t = names

    def __repr__(self) -> str:
        return f"Axes{self.t!r}"

    def __eq__(self, other) -> bool:
        return isinstance(other, Axes) and self.t == other.t

    def __hash__(self) -> int:
        return hash((Axes, self.t))

    def __len__(self) -> int:
        return len(self.t)


def default_rules() -> dict[str, tuple[tuple[str, ...], ...]]:
    """Logical name -> ordered candidate mesh-axis groups.

    Each candidate is a tuple of mesh axes the dim shards across jointly
    (``("pod", "data")`` spans DCN+ICI data parallelism). The first
    candidate whose axes all exist in the mesh, are unclaimed by an earlier
    dim, and divide the dim size wins. Names absent from the table (and
    ``None``) replicate.

    Conventions: ``batch``/``cache_batch`` are data-parallel; ``param_*``
    shards over ``data`` (FSDP); heads/ffn/experts/vocab and the other
    model-parallel dims shard over ``model`` (megatron TP); ``seq`` /
    ``layers`` / small state dims replicate.
    """
    dp = (("pod", "data"), ("data",), ("pod",))
    tp = (("model",),)
    fsdp = (("data",),)
    return {
        "batch": dp,
        "cache_batch": dp,
        "param_embed": fsdp,
        "param_seq": (),
        "vocab": tp,
        "act_vocab": tp,
        "heads": tp,
        "act_heads": tp,
        "kv": tp,
        "act_kv": tp,
        "kv_seq": tp,
        "mlp": tp,
        "act_mlp": tp,
        "experts": tp,
        "act_experts": tp,
        "rnn_width": tp,
        "conv_dim": tp,
        "ssm_heads": tp,
    }


def logical_to_spec(axes, shape, mesh, rules=None) -> PartitionSpec:
    """Resolve logical names to a ``PartitionSpec`` against ``mesh``.

    Only ``mesh.shape`` (a name -> size mapping) is read, so tests can pass
    lightweight fakes. ``axes`` may be shorter than ``shape``; trailing dims
    replicate (PartitionSpec semantics).
    """
    if rules is None:
        rules = _active_rules.get() or default_rules()
    mesh_shape = dict(mesh.shape)
    used: set[str] = set()
    out = []
    for name, dim in zip(axes, shape):
        pick = None
        for cand in rules.get(name, ()) if name is not None else ():
            cand_t = cand if isinstance(cand, tuple) else (cand,)
            if any(a not in mesh_shape or a in used for a in cand_t):
                continue
            n = 1
            for a in cand_t:
                n *= mesh_shape[a]
            if dim % n != 0:
                continue
            pick = cand_t[0] if len(cand_t) == 1 else cand_t
            used.update(cand_t)
            break
        out.append(pick)
    return PartitionSpec(*out)


def tree_shardings(mesh, sds_tree, axes_tree, rules=None):
    """NamedSharding pytree matching ``sds_tree``'s structure.

    ``sds_tree`` holds ShapeDtypeStructs (or arrays); ``axes_tree`` is the
    parallel tree of :class:`Axes` leaves. No device allocation happens —
    this is what lets the 104B dry-run build shardings abstractly.
    """

    def one(sds, ax):
        t = ax.t if isinstance(ax, Axes) else tuple(ax)
        return NamedSharding(mesh, logical_to_spec(t, sds.shape, mesh, rules))

    return jax.tree.map(one, sds_tree, axes_tree)


# ---------------------------------------------------------------------------
# ambient mesh
# ---------------------------------------------------------------------------

_active_mesh: contextvars.ContextVar = contextvars.ContextVar("repro_dist_mesh", default=None)
_active_rules: contextvars.ContextVar = contextvars.ContextVar("repro_dist_rules", default=None)


def active_mesh():
    """The mesh installed by the innermost :func:`mesh_context`, or None."""
    return _active_mesh.get()


@contextlib.contextmanager
def mesh_context(mesh, rules=None):
    """Install ``mesh`` (and optionally a rule table) as the ambient sharding
    context consulted by :func:`constrain` / :func:`active_mesh`. ``None``
    explicitly disables constraints (every ``constrain`` is the identity)."""
    t_mesh = _active_mesh.set(mesh)
    t_rules = _active_rules.set(rules)
    try:
        yield mesh
    finally:
        _active_mesh.reset(t_mesh)
        _active_rules.reset(t_rules)


def shard_map(f, mesh, in_specs, out_specs, axis_names=None, check_vma=True):
    """``jax.shard_map`` with ``axis_names`` (the manual axes) as any
    iterable; ``None`` makes every mesh axis manual."""
    kw = {} if axis_names is None else {"axis_names": frozenset(axis_names)}
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=check_vma, **kw
    )


def _bound_axis_names() -> set:
    """Mesh axes currently bound manually (we are tracing inside a
    ``shard_map``/``pmap`` body over them). JAX has no public API for
    this, hence the private ``jax._src.core`` call."""
    from jax._src import core as _core

    return set(_core.get_axis_env().axis_sizes)


def constrain(x, axes, rules=None):
    """``with_sharding_constraint(x)`` under the ambient mesh; identity (the
    SAME object) when no mesh is installed, so unsharded paths cost nothing
    and stay trace-identical.

    Axes that are already *manual* (bound by an enclosing ``shard_map``) are
    dropped from the constraint: the tensor is per-shard there, and GSPMD
    rejects constraints over manual axes.
    """
    mesh = active_mesh()
    if mesh is None:
        return x
    t = axes.t if isinstance(axes, Axes) else tuple(axes)
    spec = logical_to_spec(t, x.shape, mesh, rules)
    manual = _bound_axis_names()
    if manual and any(e is not None for e in spec):
        ents = []
        for e in spec:
            grp = e if isinstance(e, tuple) else (e,) if e is not None else ()
            grp = tuple(a for a in grp if a not in manual)
            ents.append(grp[0] if len(grp) == 1 else grp or None)
        spec = PartitionSpec(*ents)
        if all(e is None for e in spec):
            return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def constrain_tree(tree, axes_tree, drop_leading: int = 0, rules=None):
    """Constrain every leaf of ``tree`` per the parallel ``axes_tree``.

    ``drop_leading=1`` strips the logical name of a scanned-away leading dim
    (the per-layer params inside ``lax.scan`` have lost their ``"layers"``
    axis)."""
    if active_mesh() is None:
        return tree

    def one(x, ax):
        t = ax.t if isinstance(ax, Axes) else tuple(ax)
        return constrain(x, t[drop_leading:], rules)

    return jax.tree.map(one, tree, axes_tree)
