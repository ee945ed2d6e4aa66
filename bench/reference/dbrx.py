"""Plain float32 reference of the served DBRX decoder (no kernels, no cache,
no batching tricks), for the ``serve`` driver's check.

Per layer: x += Attn(RMSNorm(x)); x += MoE(RMSNorm(x)). Attention is causal
grouped-query attention with rotary positions on every head dimension
(rotating adjacent pairs); the MoE is a softmax router over all experts,
the top-k renormalised, each expert a SwiGLU FFN. Then a final RMSNorm and
the untied LM head. All matrix products run in float32 at
``precision="highest"``. Departures from the published DBRX (hf
databricks/dbrx-base), which the served model shares: RMSNorm where DBRX has
LayerNorm without bias, and no clip_qkv.

``mode="fp8"`` is the control: the same forward with every weight product
taken in float8 e4m3 (weights scaled per output channel, activations per
row), the precision below the bf16 the configuration states.

Weights come as the served tree of the ``weights`` module:
{embed, lm_head, ln_f, moe_stack/{ln1, ln2, attn/{wq, wk, wv, wo},
moe/{router, w_gate, w_up, w_down}}}, stacked over layers.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


def _q8(x, axis):
    """x rounded to float8 e4m3 with one scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(eq, x, w, mode, w_in_axes):
    """einsum of activations x (feature axis last) and weight w (input
    axes ``w_in_axes``) in f32 — or in fp8 for the control."""
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if mode == "fp8":
        x = _q8(x, -1)
        w = _q8(w, w_in_axes)
    return jnp.einsum(eq, x, w, precision=HI)


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, base):
    """x [S, H, D]: rotate adjacent pairs (2i, 2i+1) by pos * base^(-2i/D)."""
    S, H, D = x.shape
    inv = 1.0 / (base ** (np.arange(0, D, 2, dtype=np.float64) / D))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * jnp.asarray(inv, jnp.float32)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(S, H, D)


def _attention(p, h, cfg, mode):
    S = h.shape[0]
    q = _mm("sd,dhk->shk", h, p["wq"], mode, 0)
    k = _mm("sd,dhk->shk", h, p["wk"], mode, 0)
    v = _mm("sd,dhk->shk", h, p["wv"], mode, 0)
    q, k = rope(q, cfg["rope_theta"]), rope(k, cfg["rope_theta"])
    Hq, Hkv, D = q.shape[1], k.shape[1], q.shape[2]
    qg = q.reshape(S, Hkv, Hq // Hkv, D)
    s = jnp.einsum("qhgd,khd->hgqk", qg, k, precision=HI) * D ** -0.5
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal, s, -jnp.inf)
    o = jnp.einsum("hgqk,khd->qhgd", jax.nn.softmax(s, -1), v,
                   precision=HI).reshape(S, Hq, D)
    return _mm("shk,hkd->sd", o, p["wo"], mode, (0, 1))


def _moe(p, h, cfg, mode):
    """Softmax top-k MoE, each expert's SwiGLU run over every token and
    weighted by its gate (zero where not chosen); one expert at a time.
    Also returns each token's routing margin: its k-th router logit less
    its (k+1)-th."""
    logits = _mm("sd,de->se", h, p["router"], mode, 0)
    ranked = jax.lax.top_k(logits, cfg["top_k"] + 1)[0]
    margin = ranked[:, -2] - ranked[:, -1]
    probs = jax.nn.softmax(logits, -1)
    top_w, top_i = jax.lax.top_k(probs, cfg["top_k"])
    top_w = top_w / top_w.sum(-1, keepdims=True)
    E = probs.shape[-1]
    gate = jnp.einsum("sk,ske->se", top_w, jax.nn.one_hot(top_i, E))

    def expert(acc, e):
        wg, wu, wd = (p[n][e] for n in ("w_gate", "w_up", "w_down"))
        g = _mm("sd,df->sf", h, wg, mode, 0)
        u = _mm("sd,df->sf", h, wu, mode, 0)
        y = _mm("sf,fd->sd", jax.nn.silu(g) * u, wd, mode, 0)
        return acc + gate[:, e][:, None] * y, None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(h), jnp.arange(E))
    return y, margin


def hidden(params, seq, cfg, mode="f32"):
    """Final normed hidden states [S, d] of one token sequence [S], and each
    position's smallest routing margin over the layers."""
    eps = cfg["eps"]
    x = params["embed"][seq].astype(jnp.float32)
    stack = params["moe_stack"]
    margin = jnp.full(seq.shape, jnp.inf)
    for i in range(cfg["n_layers"]):
        lp = jax.tree.map(lambda a: a[i], stack)
        x = x + _attention(lp["attn"], rmsnorm(x, lp["ln1"].astype(jnp.float32),
                                               eps), cfg, mode)
        y, m = _moe(lp["moe"], rmsnorm(x, lp["ln2"].astype(jnp.float32), eps),
                    cfg, mode)
        x, margin = x + y, jnp.minimum(margin, m)
    return rmsnorm(x, params["ln_f"].astype(jnp.float32), eps), margin


def _logits(params, h, cfg, mode):
    return _mm("sd,vd->sv", h, params["lm_head"][:cfg["vocab"]], mode, 1)


@functools.partial(jax.jit, static_argnames=("cfg_items", "control"))
def _gaps(params, seq, targets, cfg_items, control):
    """Per position of ``seq`` [S]: the gap by which the target token's
    reference logit lies below the reference's best, the same for the token
    the fp8 forward puts first (with ``control``; else the first again),
    and the position's routing margin in the reference."""
    cfg = dict(cfg_items)
    h, margin = hidden(params, seq, cfg)
    ref = _logits(params, h, cfg, "f32")
    best = ref.max(-1)
    gap = best - jnp.take_along_axis(ref, targets[:, None], -1)[:, 0]
    if not control:
        return gap, gap, margin
    low = _logits(params, hidden(params, seq, cfg, "fp8")[0], cfg, "fp8")
    pick = jnp.argmax(low, -1)
    return (gap, best - jnp.take_along_axis(ref, pick[:, None], -1)[:, 0],
            margin)


def served_gaps(params, prompt, served, cfg: dict, *, pad_to: int,
                control: bool = False):
    """Gaps at each served position of one request: the reference runs once
    over prompt + served tokens (padded to ``pad_to``, which the causal mask
    makes inert), and position L-1+j is scored against served token j.
    Returns (program gaps, control gaps or None, routing margins) as numpy
    arrays."""
    prompt, served = np.asarray(prompt), np.asarray(served)
    seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    n = seq.size
    if n > pad_to:
        raise ValueError(f"sequence of {n} tokens exceeds pad_to={pad_to}")
    tgt = np.zeros(pad_to, np.int32)
    L = prompt.size
    tgt[L - 1:L - 1 + served.size] = served
    seq = np.pad(seq, (0, pad_to - n))
    g, c, m = _gaps(params, jnp.asarray(seq), jnp.asarray(tgt),
                    tuple(sorted(cfg.items())), control)
    sl = slice(L - 1, L - 1 + served.size)
    return (np.asarray(g)[sl], np.asarray(c)[sl] if control else None,
            np.asarray(m)[sl])
