"""Plain float32 reference of the served DeepSeek-V3 decoder (no kernels, no
cache, no batching tricks), for the ``serve_deepseek`` driver's check and
the CPU tests.

Per layer: x += MLA(RMSNorm(x)); x += FFN(RMSNorm(x)), where FFN is a dense
SwiGLU in the leading dense layers and the MoE in the others. Then a final
RMSNorm and the untied LM head. All matrix products run in float32 at
``precision="highest"``.

MLA in its published, un-absorbed form (hf deepseek-ai/DeepSeek-V3,
``modeling_deepseek.py``): q = W_qb·RMSNorm(W_qa·x) split into q_nope and
q_rope; [c_kv | k_rope] = W_kva·x, c_kv RMSNormed; per head k = [W_uk·c_kv ;
k_rope] (the rope key shared by all heads) and v = W_uv·c_kv; causal softmax
at scale (nope + rope)^-0.5 · mscale²; out = W_o·o. Rope takes YaRN's
frequencies (written out below from the published formulas) and rotates
adjacent pairs; the published code rotates the same pairs and lays the
rotated halves out apart, a permutation of the rope dims that q·k does not
see.

MoE (``noaux_tc``): scores = sigmoid(router logits) over all routed
experts; selection on scores + the correction bias; a group's score is the
sum of its top 2; the tokens keep the ``topk_group`` best groups; top-k
over the kept experts; weights = the unbiased scores of the chosen, summed
to 1, times ``routed_scaling_factor``. The layer adds the held experts'
part (``held``: the logical experts whose weights the tree holds, in its
order) and the shared expert. Holding every expert it is the whole layer.

``mode="fp8"`` is the control: the same forward with every weight product
taken in float8 e4m3 (weights scaled per output channel, activations per
row), the precision below the bf16 the configuration states.

The attention runs over blocks of queries and the LM head over blocks of
the vocabulary, so that the f32 forward of a 2304-token sequence fits on
one chip beside nothing else.

Weights come as the served tree: {embed, lm_head, ln_f, dense_stack/{ln1,
ln2, attn/{wq_a, q_norm, wq_b, wkv_a, kv_norm, wk_b, wv_b, wo},
ffn/{w_gate, w_up, w_down}}, moe_stack/{ln1, ln2, attn/{...},
moe/{router, sel_bias, w_gate, w_up, w_down, shared/{w_gate, w_up,
w_down}}}}, stacked over layers.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 256          # queries per attention block
V_BLOCK = 16384        # vocabulary rows per LM-head block


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
    return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
            * w.astype(jnp.float32))


def yarn_inv_freq(dim: int, base: float, factor: float, original: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """DeepSeek-V3's YaRN inverse frequencies (``yarn_find_correction_range``
    and ``yarn_linear_ramp_mask`` of the published code)."""
    def corr(rot):
        return (dim * math.log(original / (rot * 2 * math.pi))
                / (2 * math.log(base)))
    low = max(math.floor(corr(beta_fast)), 0)
    high = min(math.ceil(corr(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0, 1)
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    inter = 1.0 / (factor * base ** (np.arange(0, dim, 2, dtype=np.float64)
                                     / dim))
    mask = 1.0 - ramp
    return inter * (1 - mask) + extra * mask


def yarn_get_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def rope(x, cfg):
    """x [S, ..., D]: rotate adjacent pairs (2i, 2i+1) by pos · inv_freq[i],
    cos and sin scaled by mscale / mscale_all_dim (1 in DeepSeek-V3)."""
    S, D = x.shape[0], x.shape[-1]
    inv = yarn_inv_freq(D, cfg["rope_theta"], cfg["yarn_factor"],
                        cfg["yarn_original"], cfg["yarn_beta_fast"],
                        cfg["yarn_beta_slow"])
    amp = (yarn_get_mscale(cfg["yarn_factor"], cfg["yarn_mscale"])
           / yarn_get_mscale(cfg["yarn_factor"], cfg["yarn_mscale_all_dim"]))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * jnp.asarray(inv, jnp.float32)
    shape = (S,) + (1,) * (x.ndim - 2) + (D // 2,)
    cos = (jnp.cos(ang) * amp).reshape(shape)
    sin = (jnp.sin(ang) * amp).reshape(shape)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(x.shape)


def softmax_scale(cfg) -> float:
    m = yarn_get_mscale(cfg["yarn_factor"], cfg["yarn_mscale_all_dim"])
    return (cfg["qk_nope"] + cfg["qk_rope"]) ** -0.5 * m * m


def _mla(p, h, cfg, mode):
    S = h.shape[0]
    nope, r = cfg["qk_nope"], cfg["kv_rank"]
    eps = cfg["eps"]
    cq = rmsnorm(_mm("sd,dr->sr", h, p["wq_a"], mode, 0), p["q_norm"], eps)
    q = _mm("sr,rhk->shk", cq, p["wq_b"], mode, 0)
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], cfg)], -1)
    kv = _mm("sd,dr->sr", h, p["wkv_a"], mode, 0)
    ckv = rmsnorm(kv[:, :r], p["kv_norm"], eps)
    k_rope = rope(kv[:, r:], cfg)                         # [S, rope]
    k_nope = _mm("sr,rhk->shk", ckv, p["wk_b"], mode, 0)
    v = _mm("sr,rhk->shk", ckv, p["wv_b"], mode, 0)
    H = k_nope.shape[1]
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        k_rope[:, None], (S, H, k_rope.shape[-1]))], -1)  # [S, H, nope+rope]
    scale = softmax_scale(cfg)
    outs = []
    for q0 in range(0, S, Q_BLOCK):
        qb = q[q0:q0 + Q_BLOCK]
        s = jnp.einsum("qhk,shk->hqs", qb, k, precision=HI) * scale
        pos = jnp.arange(q0, q0 + qb.shape[0])
        s = jnp.where(jnp.arange(S)[None, :] <= pos[:, None], s, -jnp.inf)
        outs.append(jnp.einsum("hqs,shk->qhk", jax.nn.softmax(s, -1), v,
                               precision=HI))
    o = jnp.concatenate(outs, 0)
    return _mm("shk,hkd->sd", o, p["wo"], mode, (0, 1))


def _swiglu(p, h, mode):
    g = _mm("sd,df->sf", h, p["w_gate"], mode, 0)
    u = _mm("sd,df->sf", h, p["w_up"], mode, 0)
    return _mm("sf,fd->sd", jax.nn.silu(g) * u, p["w_down"], mode, 0)


def route(logits, bias, cfg):
    """(weights [S, E] of the chosen experts, zero elsewhere; the routing
    margin [S]): the smaller of the k-th less the (k+1)-th selection score
    among the kept groups' experts and the kept groups' last score less
    the first left out."""
    S, E = logits.shape
    G, kg, k = cfg["n_group"], cfg["topk_group"], cfg["top_k"]
    scores = jax.nn.sigmoid(logits)
    sel = scores + bias.astype(jnp.float32)[None]
    gscore = jax.lax.top_k(sel.reshape(S, G, E // G), 2)[0].sum(-1)   # [S, G]
    granked, gidx = jax.lax.top_k(gscore, kg + 1)
    keep = jnp.zeros((S, G), bool).at[jnp.arange(S)[:, None],
                                      gidx[:, :kg]].set(True)
    sel = jnp.where(jnp.repeat(keep, E // G, -1), sel, -jnp.inf)
    ranked, idx = jax.lax.top_k(sel, k + 1)
    chosen = jax.nn.one_hot(idx[:, :k], E).sum(1)                     # [S, E]
    w = scores * chosen
    w = w / w.sum(-1, keepdims=True) * cfg["routed_scaling"]
    margin = jnp.minimum(ranked[:, k - 1] - ranked[:, k],
                         granked[:, kg - 1] - granked[:, kg])
    return w, margin


def _moe(p, h, cfg, mode):
    """The held experts' part, weighted by the routing over all experts,
    each expert's SwiGLU run over every token (zero weight where not
    chosen), one expert at a time; then the shared expert."""
    logits = _mm("sd,de->se", h, p["router"], mode, 0)
    w, margin = route(logits, p["sel_bias"], cfg)
    held = jnp.asarray(cfg["held"], jnp.int32)

    def expert(acc, i):
        pe = {n: p[n][i] for n in ("w_gate", "w_up", "w_down")}
        return acc + w[:, held[i]][:, None] * _swiglu(pe, h, mode), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(h), jnp.arange(held.size))
    return y + _swiglu(p["shared"], h, mode), margin


def hidden(params, seq, cfg, mode="f32"):
    """Final normed hidden states [S, d] of one token sequence [S], and each
    position's smallest routing margin over the MoE layers."""
    eps = cfg["eps"]
    x = params["embed"][seq].astype(jnp.float32)
    margin = jnp.full(seq.shape, jnp.inf)
    for stack, moe in (("dense_stack", False), ("moe_stack", True)):
        if stack not in params:
            continue
        n = jax.tree.leaves(params[stack])[0].shape[0]
        for i in range(n):
            lp = jax.tree.map(lambda a: a[i], params[stack])
            x = x + _mla(lp["attn"], rmsnorm(x, lp["ln1"], eps), cfg, mode)
            h = rmsnorm(x, lp["ln2"], eps)
            if moe:
                y, m = _moe(lp["moe"], h, cfg, mode)
                margin = jnp.minimum(margin, m)
            else:
                y = _swiglu(lp["ffn"], h, mode)
            x = x + y
    return rmsnorm(x, params["ln_f"], eps), margin


def logits(params, h, cfg, mode="f32"):
    """Full logits [S, vocab] (for small vocabularies: the tests)."""
    return _mm("sd,vd->sv", h, params["lm_head"][:cfg["vocab"]], mode, 1)


def _best(params, h, cfg, mode):
    """Per position: the best logit over the vocabulary and its token, the
    head taken in blocks of ``V_BLOCK`` rows."""
    V = cfg["vocab"]
    best = jnp.full(h.shape[:1], -jnp.inf)
    arg = jnp.zeros(h.shape[:1], jnp.int32)
    for v0 in range(0, V, V_BLOCK):
        lg = _mm("sd,vd->sv", h, params["lm_head"][v0:min(v0 + V_BLOCK, V)],
                 mode, 1)
        m, a = lg.max(-1), jnp.argmax(lg, -1).astype(jnp.int32) + v0
        arg = jnp.where(m > best, a, arg)
        best = jnp.maximum(best, m)
    return best, arg


def _logit_of(params, h, tokens):
    """The f32 logit of ``tokens`` [S] at each position."""
    rows = params["lm_head"][tokens].astype(jnp.float32)
    return jnp.einsum("sd,sd->s", h, rows, precision=HI)


@functools.partial(jax.jit, static_argnames=("cfg_items", "control"))
def _gaps(params, seq, targets, cfg_items, control):
    """Per position of ``seq`` [S]: the gap by which the target token's
    reference logit lies below the reference's best, the same for the token
    the fp8 forward puts first (with ``control``; else the first again),
    and the position's routing margin in the reference."""
    cfg = dict(cfg_items)
    h, margin = hidden(params, seq, cfg)
    best, _ = _best(params, h, cfg, "f32")
    gap = best - _logit_of(params, h, targets)
    if not control:
        return gap, gap, margin
    _, pick = _best(params, hidden(params, seq, cfg, "fp8")[0], cfg, "fp8")
    return gap, best - _logit_of(params, h, pick), margin


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
    items = tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                         for k, v in cfg.items()))
    g, c, m = _gaps(params, jnp.asarray(seq), jnp.asarray(tgt), items, control)
    sl = slice(L - 1, L - 1 + served.size)
    return (np.asarray(g)[sl], np.asarray(c)[sl] if control else None,
            np.asarray(m)[sl])

