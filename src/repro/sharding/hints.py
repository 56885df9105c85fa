"""Logical sharding hints for model intermediates (maxtext-style).

GSPMD propagation alone picks pathological layouts for some of our layers
(observed: involuntary full rematerialization/replication of SSD states and
MoE dispatch buffers).  ``hint(x, *tokens)`` places an explicit
``with_sharding_constraint`` using *logical* dim tokens:

    "batch"  -> sharded over the data axes ("pod","data") when divisible
    "model"  -> sharded over the tensor-parallel axis when divisible
    None     -> unconstrained... replicated along that dim

Hints resolve against the *ambient* abstract mesh (``jax.set_mesh``); when
no mesh is set (unit tests, the CPU simulator) they are exact no-ops, so
model code stays mesh-agnostic.
"""
from __future__ import annotations

import jax
from jax.sharding import PartitionSpec as P


def _partitioned_axes() -> dict:
    """{axis name: size} of the ambient mesh's axes that the compiler still
    partitions; empty when no mesh is set.  Inside ``shard_map`` the mapped
    axes are Manual — the code there already sees its local shard — so they
    are left out, and a hint over them is an exact no-op."""
    mesh = jax.sharding.get_abstract_mesh()
    manual = set(mesh.manual_axes)
    return {a: n for a, n in mesh.shape.items() if a not in manual}


def _resolve(shape, tokens, axis_names, axis_sizes):
    data_axes = tuple(a for a in axis_names if a != "model")
    spec = []
    for i, tok in enumerate(tokens):
        if tok is None or i >= len(shape):
            spec.append(None)
            continue
        if tok == "batch":
            # try full data product, then single trailing data axis
            for axes in (data_axes,) + tuple((a,) for a in data_axes[::-1]):
                size = 1
                for a in axes:
                    size *= axis_sizes[a]
                if size > 1 and shape[i] % size == 0:
                    spec.append(axes if len(axes) > 1 else axes[0])
                    break
            else:
                spec.append(None)
        elif tok == "model":
            ms = axis_sizes.get("model", 1)
            spec.append("model" if ms > 1 and shape[i] % ms == 0 else None)
        else:
            raise ValueError(tok)
    # pad to full rank
    spec += [None] * (len(shape) - len(spec))
    return P(*spec)


def data_shards() -> int:
    """Product of the non-"model" (batch-carrying) mesh axis sizes; 1 if none."""
    s = 1
    for name, size in _partitioned_axes().items():
        if name != "model":
            s *= size
    return s


def mesh_axis_size(name: str) -> int:
    """Size of an ambient-mesh axis (1 when no mesh is set)."""
    return _partitioned_axes().get(name, 1)


def hint(x: jax.Array, *tokens) -> jax.Array:
    """Constrain ``x``'s sharding by logical dim tokens; no-op without mesh."""
    axis_sizes = _partitioned_axes()
    if not axis_sizes:
        return x
    spec = _resolve(x.shape, tokens, tuple(axis_sizes), axis_sizes)
    if all(entry is None for entry in spec):
        return x  # fully replicated constraint ⇒ exact no-op
    return jax.lax.with_sharding_constraint(x, spec)
