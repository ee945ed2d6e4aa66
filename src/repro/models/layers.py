"""Shared primitive layers: norms, RoPE, gated FFNs, embeddings."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.parallel.sharding import ParamSpec


def rmsnorm(x: jax.Array, w: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    x2 = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(x2 + eps)).astype(x.dtype) * w


def rmsnorm_spec(d: int, dtype=jnp.bfloat16) -> ParamSpec:
    return ParamSpec((d,), dtype, ("embed",), init="ones")


# --------------------------------------------------------------------------
# RoPE
# --------------------------------------------------------------------------

def rope_frequencies(rot_dim: int, base: float) -> np.ndarray:
    return 1.0 / (base ** (np.arange(0, rot_dim, 2, dtype=np.float64) / rot_dim))


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's magnitude correction 0.1 · mscale · ln(factor) + 1 (1 when
    the context is not stretched)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_frequencies(rot_dim: int, base: float, y) -> np.ndarray:
    """YaRN inverse frequencies (DeepSeek-V3's ``rope_scaling``, ``y`` a
    ``YarnScaling``): pair i keeps base^(-2i/D) below the correction range
    of ``beta_fast`` rotations over the original context, takes it divided
    by ``factor`` above that of ``beta_slow``, and ramps linearly between."""
    extra = rope_frequencies(rot_dim, base)

    def corr_dim(rotations):
        return (rot_dim * math.log(y.original_max_position
                                   / (rotations * 2 * math.pi))
                / (2 * math.log(base)))
    lo = max(math.floor(corr_dim(y.beta_fast)), 0)
    hi = min(math.ceil(corr_dim(y.beta_slow)), rot_dim - 1)
    if lo == hi:
        hi += 0.001
    ramp = np.clip((np.arange(rot_dim // 2) - lo) / (hi - lo), 0.0, 1.0)
    return extra / y.factor * ramp + extra * (1.0 - ramp)


def apply_rope(x: jax.Array, positions: jax.Array, base: float,
               fraction: float = 1.0, scaling=None) -> jax.Array:
    """x: [B, S, H, D]; positions: [B, S]. Rotates the first
    ``fraction * D`` components (chatglm3's 2d RoPE == fraction 0.5).
    ``scaling`` (a ``YarnScaling``) takes YaRN's frequencies and scales
    cos and sin by mscale(factor, mscale) / mscale(factor, mscale_all_dim)."""
    B, S, H, D = x.shape
    rot = int(D * fraction)
    rot -= rot % 2
    if rot == 0:
        return x
    if scaling is None:
        freqs, amp = rope_frequencies(rot, base), 1.0
    else:
        freqs = yarn_frequencies(rot, base, scaling)
        amp = (yarn_mscale(scaling.factor, scaling.mscale)
               / yarn_mscale(scaling.factor, scaling.mscale_all_dim))
    inv = jnp.asarray(freqs, jnp.float32)                          # [rot/2]
    ang = positions.astype(jnp.float32)[:, :, None] * inv[None, None, :]  # [B,S,rot/2]
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    if amp != 1.0:
        cos, sin = cos * amp, sin * amp
    xr, xp = x[..., :rot], x[..., rot:]
    x1, x2 = xr[..., 0::2], xr[..., 1::2]
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    yr = jnp.stack([y1, y2], axis=-1).reshape(B, S, H, rot)
    return jnp.concatenate([yr.astype(x.dtype), xp], axis=-1)


# --------------------------------------------------------------------------
# gated FFN
# --------------------------------------------------------------------------

def ffn_spec(d: int, f: int, dtype=jnp.bfloat16, act: str = "swiglu"):
    if act == "gelu":
        return dict(
            w_in=ParamSpec((d, f), dtype, ("embed", "ffn")),
            w_out=ParamSpec((f, d), dtype, ("ffn", "embed")),
        )
    return dict(
        w_gate=ParamSpec((d, f), dtype, ("embed", "ffn")),
        w_up=ParamSpec((d, f), dtype, ("embed", "ffn")),
        w_down=ParamSpec((f, d), dtype, ("ffn", "embed")),
    )


def ffn_apply(p, x: jax.Array, act: str = "swiglu") -> jax.Array:
    if act == "gelu":
        h = jax.nn.gelu(x @ p["w_in"])
        return h @ p["w_out"]
    g = x @ p["w_gate"]
    u = x @ p["w_up"]
    h = (jax.nn.gelu(g) if act == "geglu" else jax.nn.silu(g)) * u
    return h @ p["w_down"]


# --------------------------------------------------------------------------
# embeddings / logits
# --------------------------------------------------------------------------

def embed_spec(vocab: int, d: int, dtype=jnp.bfloat16) -> ParamSpec:
    return ParamSpec((vocab, d), dtype, ("vocab", "embed"), init="embed")


def embed_lookup(table: jax.Array, tokens: jax.Array) -> jax.Array:
    return table[tokens]


def logits_out(x: jax.Array, table: jax.Array) -> jax.Array:
    """Final projection; f32 logits for a stable softmax-CE."""
    return jnp.einsum("bsd,vd->bsv", x.astype(jnp.float32),
                      table.astype(jnp.float32))


def cross_entropy(logits: jax.Array, targets: jax.Array,
                  mask: jax.Array | None = None) -> jax.Array:
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = lse - ll
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
