"""Seeded weights made by the benchmark, for the program and for the plain
reference alike. Each leaf is a pure function of (seed, its path, its
shape): drawn in float32 on the device, then cast to the dtype it is served
in, all leaves in one jitted call. Leaves stacked over experts draw each
expert from its own key, so a reference can remake one expert alone.

The scale of a leaf follows its role, named by the last key of its path.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

# role -> how the leaf is drawn: "ones", "zeros", or the axis whose size
# sets std = size ** -0.5 (counted from the end), or "unit" for std 1
ROLES = {
    "ln1": "ones", "ln2": "ones", "ln_f": "ones",
    "sel_bias": "zeros",
    "embed": "unit",
    "lm_head": -1,          # [V, d]: fan-in d
    "wq": -3, "wk": -3, "wv": -3,       # [..., d, heads, hd]
    "wo": (-3, -2),         # [..., heads, hd, d]: fan-in heads * hd
    "router": -2,           # [..., d, E]
    "w_gate": -2, "w_up": -2,           # [..., E, d, f]
    "w_down": -2,           # [..., E, f, d]
}
EXPERT_STACKED = ("w_gate", "w_up", "w_down")


def leaf_key(seed: int, path: str) -> jax.Array:
    """Key of one leaf: the run's seed folded with the leaf's path."""
    base = jax.random.key(seed & 0xFFFFFFFF)
    base = jax.random.fold_in(base, (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(base, zlib.crc32(path.encode()))


def _std(role, shape) -> float:
    if role == "unit":
        return 1.0
    axes = role if isinstance(role, tuple) else (role,)
    return float(np.prod([shape[a] for a in axes])) ** -0.5


def draw(key, path: str, shape, dtype, expert: int | None = None):
    """The leaf at ``path`` from its key ``leaf_key(seed, path)`` (or only
    expert ``expert`` of an expert-stacked leaf [..., E, a, b]). The key is
    an argument, not a constant, so one compiled program serves every
    seed."""
    name = path.rsplit("/", 1)[-1]
    role = ROLES.get(name)
    if role is None:
        raise KeyError(f"no drawing rule for weight {path!r}")
    if role == "ones":
        return jnp.ones(shape, dtype)
    if role == "zeros":
        return jnp.zeros(shape, dtype)
    std = _std(role, shape)
    if name in EXPERT_STACKED:
        lead, (E, a, b) = tuple(shape[:-3]), shape[-3:]

        def one(e):
            return jax.random.normal(jax.random.fold_in(key, e),
                                     lead + (a, b), jnp.float32)
        if expert is not None:
            return (one(expert) * std).astype(dtype)        # lead + (a, b)
        x = jax.vmap(one, out_axes=len(lead))(jnp.arange(E))
        return (x * std).astype(dtype)
    return (jax.random.normal(key, tuple(shape), jnp.float32) * std
            ).astype(dtype)


def flat_shapes(tree) -> list[tuple[str, tuple, object]]:
    """[(path, shape, dtype)] of a tree of objects with .shape and .dtype;
    paths join dict keys with '/'."""
    out = []
    for kp, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        path = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                        for k in kp)
        out.append((path, tuple(leaf.shape), leaf.dtype))
    return out


def make(seed: int, shapes_tree, shardings=None):
    """Every leaf of ``shapes_tree`` (objects with .shape/.dtype), in one
    jitted call on the device."""
    treedef = jax.tree.structure(shapes_tree)
    spec = flat_shapes(shapes_tree)
    keys = [leaf_key(seed, p) for p, _, _ in spec]

    def build(keys):
        return [draw(k, p, s, d) for k, (p, s, d) in zip(keys, spec)]

    out = jax.jit(build, out_shardings=shardings)(keys)
    return jax.tree.unflatten(treedef, out)
