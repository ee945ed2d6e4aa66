"""Logical-axis sharding: every parameter/activation declares *logical* axes;
a rules table maps them onto mesh axes (the MaxText/T5X pattern). This keeps
model code mesh-agnostic — the same definitions lower on the single-pod
(16, 16) and multi-pod (2, 16, 16) production meshes and on tiny test meshes.

Rules (defaults; overridable per arch/shape config):
  batch      -> ("pod", "data")   data parallelism (pods are extra DP)
  vocab      -> "model"           TP embedding / logits
  heads      -> "model"           TP attention (q heads; kv replicated when
                                  n_kv doesn't divide the model axis)
  ffn        -> "model"           TP MLP
  expert     -> EP axis (the EpGroupConfig.ep_axis, usually "model")
  kv_seq     -> "model" (decode)  sequence-sharded KV caches; XLA inserts the
                                  softmax all-reduces (split-KV decode)
  kv_seq_long-> ("data","model")  524k contexts: KV over the whole pod
  stack      -> None              scan-over-layers leading axis, never sharded
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    """Shape + dtype + logical axes + initializer for one parameter."""

    shape: tuple[int, ...]
    dtype: Any = jnp.bfloat16
    axes: tuple[str | None, ...] = ()
    init: str = "normal"       # normal | zeros | ones | embed | scaled
    scale: float = 1.0

    def __post_init__(self):
        assert len(self.axes) in (0, len(self.shape)), (self.shape, self.axes)


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    rules: Mapping[str, tuple[str, ...] | str | None]

    def mesh_axes(self, logical: str | None, mesh: Mesh):
        if logical is None:
            return None
        target = self.rules.get(logical, None)
        if target is None:
            return None
        axes = (target,) if isinstance(target, str) else tuple(target)
        # drop axes not present in the mesh (e.g. "pod" on single-pod)
        axes = tuple(a for a in axes if a in mesh.shape)
        return axes if axes else None


DEFAULT_RULES = ShardingRules(rules={
    "batch": ("pod", "data"),
    "vocab": "model",
    "heads": "model",
    "kv_heads": "model",
    "ffn": "model",
    "expert": "model",
    "kv_seq": "model",
    "kv_seq_long": ("data", "model"),
    "mamba_heads": "model",
    "embed": None, "seq": None, "stack": None, "qk": None, "v": None,
    "lora": None, "state": None, "conv": None, "img": None,
})


def _divisible(dim: int, mesh: Mesh, axes: tuple[str, ...] | None) -> bool:
    if not axes:
        return True
    n = int(np.prod([mesh.shape[a] for a in axes]))
    return dim % n == 0


def logical_to_pspec(spec: ParamSpec, mesh: Mesh, rules: ShardingRules) -> P:
    """Logical axes -> PartitionSpec. A dimension is silently replicated when
    it doesn't divide its mesh extent (e.g. 2 kv heads over a 16-way model
    axis) or when its mesh axis was already claimed by an earlier dimension
    (first-come-wins, the T5X rule — e.g. decode caches map both kv_seq and
    kv_heads to "model"; kv_seq wins)."""
    if not spec.axes:
        return P()
    parts = []
    used: set[str] = set()
    for dim, ax in zip(spec.shape, spec.axes):
        m = rules.mesh_axes(ax, mesh)
        if m:
            m = tuple(a for a in m if a not in used)
        if m and _divisible(dim, mesh, m):
            parts.append(tuple(m) if len(m) > 1 else m[0])
            used.update(m)
        else:
            parts.append(None)
    return P(*parts)


def spec_to_named_sharding(spec: ParamSpec, mesh: Mesh,
                           rules: ShardingRules = DEFAULT_RULES) -> NamedSharding:
    return NamedSharding(mesh, logical_to_pspec(spec, mesh, rules))


def abstract_from_specs(specs, mesh: Mesh | None = None,
                        rules: ShardingRules = DEFAULT_RULES):
    """Pytree of ParamSpec -> pytree of ShapeDtypeStruct (dry-run inputs)."""
    def one(s: ParamSpec):
        sh = spec_to_named_sharding(s, mesh, rules) if mesh is not None else None
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh)
    return jax.tree.map(one, specs, is_leaf=lambda x: isinstance(x, ParamSpec))


def init_from_specs(key: jax.Array, specs, mesh: Mesh | None = None,
                    rules: ShardingRules = DEFAULT_RULES):
    """Materialize parameters (tests/examples; production uses checkpoint).

    One jitted program builds every leaf; with a mesh its ``out_shardings``
    place each leaf under its rules, so every device generates only its own
    shard and no whole leaf (nor its f32 draw) ever lands on one device."""
    leaves, treedef = jax.tree.flatten(specs, is_leaf=lambda x: isinstance(x, ParamSpec))

    def one(k, s: ParamSpec):
        if s.init == "zeros":
            return jnp.zeros(s.shape, s.dtype)
        if s.init == "ones":
            return jnp.ones(s.shape, s.dtype)
        fan_in = s.shape[-2] if len(s.shape) >= 2 else s.shape[-1]
        std = s.scale / np.sqrt(max(fan_in, 1))
        return (jax.random.normal(k, s.shape, jnp.float32) * std).astype(s.dtype)

    def build(key):
        keys = jax.random.split(key, len(leaves))
        return [one(k, s) for k, s in zip(keys, leaves)]

    shardings = (None if mesh is None else
                 [spec_to_named_sharding(s, mesh, rules) for s in leaves])
    return jax.tree.unflatten(treedef,
                              jax.jit(build, out_shardings=shardings)(key))


def arch_rules(cfg) -> ShardingRules:
    """Arch-aware rules: the expert dimension shards over the config's EP
    axis and the expert FFN dim over whatever model capacity EP leaves free.
    (Using DEFAULT_RULES for a MoE arch replicates expert FFNs — measured
    82 GB/chip on deepseek-v3; §Perf D5.)"""
    rules = dict(DEFAULT_RULES.rules)
    if getattr(cfg, "moe", None) is not None:
        rules["expert"] = cfg.moe.ep_axis
        rules["expert_ffn"] = ("model",) if "model" not in cfg.moe.ep_axis else None
    return ShardingRules(rules=rules)


def constrain(x: jax.Array, mesh: Mesh | None, *axes: str | None,
              rules: ShardingRules = DEFAULT_RULES):
    """with_sharding_constraint by logical axes (no-op without a mesh)."""
    if mesh is None or mesh.empty:
        return x
    spec = ParamSpec(shape=tuple(x.shape), axes=tuple(axes))
    return jax.lax.with_sharding_constraint(
        x, spec_to_named_sharding(spec, mesh, rules))
