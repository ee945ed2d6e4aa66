"""Plain float32 reference of one EP round trip (route -> dispatch ->
per-expert step -> combine), for the ``ep_round_trip`` driver's check.

Routing is DeepSeek-V3's: sigmoid scores of the router logits; experts in
``n_group`` groups, only the ``topk_group`` groups with the highest sum of
their two best scores eligible; the top-k eligible experts chosen, their
scores renormalised and scaled by ``routed_scaling_factor``. Each chosen
expert e receives the token's payload and returns it times (1 + e); the
combine sums those, weighted. So for token t:

    y[t] = q(x[t]) * sum_k w[t, k] * (1 + e[t, k])

where q is the dispatch payload's rounding: fp8 e4m3 with one float32 scale
(absmax / 448) per 128 values, as the configuration states. ``quant="int4"``
is the control: the same round trip with a 4-bit payload (absmax / 7 per 128
values), the precision below fp8.

A token whose routing sits on a rounding-level tie (the k-th and (k+1)-th
eligible scores, or the groups at the group cut, within ``TIE``) has no
well-defined expert set; ``round_trip`` marks it, and the check leaves it
out and counts it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
TIE = 1e-6


def quantize(x, quant: str, block: int = 128):
    """x [T, H] f32 rounded as a payload: "fp8", "int4" or "none"."""
    if quant == "none":
        return x
    T, H = x.shape
    g = x.reshape(T, H // block, block)
    amax = jnp.max(jnp.abs(g), -1, keepdims=True)
    if quant == "fp8":
        s = jnp.where(amax > 0, amax / 448.0, 1.0)
        q = (g / s).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    elif quant == "int4":
        s = jnp.where(amax > 0, amax / 7.0, 1.0)
        q = jnp.clip(jnp.round(g / s), -7, 7)
    else:
        raise ValueError(f"unknown payload rounding {quant!r}")
    return (q * s).reshape(T, H)


def route(x, w, rc: dict):
    """-> (topk_idx [T, K], topk_w [T, K], tie [T] bool)."""
    T = x.shape[0]
    E, K = rc["n_routed_experts"], rc["num_experts_per_tok"]
    G, TG = rc["n_group"], rc["topk_group"]
    scores = jax.nn.sigmoid(jnp.dot(x, w, precision=HI))
    grouped = scores.reshape(T, G, E // G)
    gscore = jax.lax.top_k(grouped, 2)[0].sum(-1)                  # [T, G]
    gs = jnp.sort(gscore, -1)[:, ::-1]
    tie = (gs[:, TG - 1] - gs[:, TG]) < TIE if TG < G else jnp.zeros(T, bool)
    _, gidx = jax.lax.top_k(gscore, TG)
    gmask = jnp.zeros((T, G), bool).at[jnp.arange(T)[:, None], gidx].set(True)
    masked = jnp.where(jnp.repeat(gmask, E // G, -1), scores, -jnp.inf)
    top, idx = jax.lax.top_k(masked, K + 1)
    tie |= (top[:, K - 1] - top[:, K]) < TIE
    tw = top[:, :K]
    if rc["norm_topk_prob"]:
        tw = tw / tw.sum(-1, keepdims=True)
    return idx[:, :K], tw * rc["routed_scaling_factor"], tie


def _round_trip(x, w, rc_items, quant):
    rc = dict(rc_items)
    idx, tw, tie = route(x, w, rc)
    gain = (tw * (1.0 + idx.astype(jnp.float32))).sum(-1, keepdims=True)
    return quantize(x, quant) * gain, idx, tie


_round_trip_jit = jax.jit(_round_trip, static_argnums=(2, 3))


def round_trip(x, w, rc: dict, quant: str):
    """x [T, H] (any float dtype), w [H, E] f32 ->
    (y [T, H] f32, topk_idx [T, K], tie [T])."""
    return _round_trip_jit(
        x.astype(jnp.float32), w.astype(jnp.float32),
        tuple(sorted(rc.items())), quant)
