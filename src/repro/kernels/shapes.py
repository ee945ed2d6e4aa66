"""Output shapes for ``pallas_call`` that hold inside ``jax.shard_map``.

Under shard_map's varying-axes check a kernel's output must say over which
mesh axes it varies; a kernel's output varies wherever any of its inputs do.
Outside shard_map every input varies over nothing and this is a plain
``ShapeDtypeStruct``.
"""
from __future__ import annotations

import jax


def out_struct(shape, dtype, *inputs) -> jax.ShapeDtypeStruct:
    vma = frozenset().union(*(jax.typeof(x).vma for x in inputs))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
