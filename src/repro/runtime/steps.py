"""Step factories: the jitted units the launcher, dry-run, and roofline all
share.

``train_step``: microbatched (gradient-accumulation scan) value_and_grad +
AdamW update. ``serve_step``: one decode token against the KV/state cache,
returning greedy next tokens (the paper's LL decode loop unit).
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.models import get_model
from repro.models.config import ArchConfig
from repro.optim import AdamWConfig, adamw_update
from repro.parallel.sharding import ParamSpec, abstract_from_specs


# --------------------------------------------------------------------------
# batch/state spec builders (ShapeDtypeStruct factories for the dry-run)
# --------------------------------------------------------------------------

def train_batch_specs(cfg: ArchConfig, global_batch: int, seq: int):
    """Returns pytree of ParamSpec for one *global* batch, shaped
    [microbatch, B/microbatch, ...] when gradient accumulation is on."""
    g = max(cfg.microbatch, 1)
    assert global_batch % g == 0, (global_batch, g)
    b = global_batch // g

    def tok(shape):
        return ParamSpec(shape, jnp.int32, (None, "batch") + (None,) * (len(shape) - 2))

    batch = dict(tokens=tok((g, b, seq)), targets=tok((g, b, seq)))
    if cfg.family == "vlm":
        batch["img_embeds"] = ParamSpec((g, b, cfg.img_tokens, cfg.d_model),
                                        cfg.dtype, (None, "batch", None, None))
    if cfg.family == "encdec":
        batch["src_embeds"] = ParamSpec((g, b, cfg.src_len, cfg.d_model),
                                        cfg.dtype, (None, "batch", None, None))
    return batch


def serve_state_specs(cfg: ArchConfig, batch: int, kv_len: int, *, long=False):
    m = get_model(cfg)
    state = m.decode_state_spec(cfg, batch, kv_len, long=long)
    tokens = ParamSpec((batch, 1), jnp.int32, ("batch", None))
    return state, dict(tokens=tokens)


def paged_serve_state_specs(cfg: ArchConfig, batch: int, num_pages: int,
                            page_size: int, max_pages: int):
    """Specs for the continuous-batching paged decode step: state = per-layer
    page pools; batch inputs = tokens + host-built page table / kv_lens /
    active mask (fixed shapes — join/leave/recycle never retraces)."""
    m = get_model(cfg)
    if m.paged_decode_state_spec is None:
        raise NotImplementedError(
            f"family {cfg.family!r} has no paged decode path")
    state = m.paged_decode_state_spec(cfg, num_pages, page_size)
    batch_specs = dict(
        tokens=ParamSpec((batch, 1), jnp.int32, ("batch", None)),
        page_tbl=ParamSpec((batch, max_pages), jnp.int32, ("batch", None)),
        kv_lens=ParamSpec((batch,), jnp.int32, ("batch",)),
        active=ParamSpec((batch,), jnp.int32, ("batch",)),
    )
    return state, batch_specs


# --------------------------------------------------------------------------
# step functions
# --------------------------------------------------------------------------

def make_train_step(cfg: ArchConfig, mesh, opt_cfg: AdamWConfig | None = None):
    opt_cfg = opt_cfg or AdamWConfig()
    model = get_model(cfg)

    def loss_fn(params, micro):
        loss, _ = model.forward(params, micro, cfg, mesh)
        return loss

    def train_step(params, opt_state, batch):
        g = jax.tree.leaves(batch)[0].shape[0]

        def acc_body(carry, micro):
            loss_sum, grad_sum = carry
            loss, grads = jax.value_and_grad(loss_fn)(params, micro)
            grad_sum = jax.tree.map(
                lambda a, b: a + b.astype(jnp.float32), grad_sum, grads)
            return (loss_sum + loss, grad_sum), None

        zero_grads = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (loss_sum, grads), _ = jax.lax.scan(
            acc_body, (jnp.float32(0), zero_grads), batch)
        grads = jax.tree.map(lambda x: x / g, grads)
        params, opt_state, om = adamw_update(params, grads, opt_state, opt_cfg)
        metrics = dict(loss=loss_sum / g, **om)
        return params, opt_state, metrics

    return train_step


def _greedy(logits, cfg: ArchConfig):
    """Greedy next token [B, 1] from the last position's logits, in the
    ``head`` named scope the model's final norm and LM head open."""
    with jax.named_scope("head"):
        nxt = jnp.argmax(logits[:, -1, :cfg.vocab], axis=-1).astype(jnp.int32)
        return nxt[:, None]


def make_serve_step(cfg: ArchConfig, mesh):
    model = get_model(cfg)

    def serve_step(params, state, batch):
        logits, state = model.decode_step(params, state, batch, cfg, mesh)
        return _greedy(logits, cfg), state

    return serve_step


def make_paged_serve_step(cfg: ArchConfig, mesh):
    """Greedy serve step over the paged decode path — same (params, state,
    batch) -> (tokens, state) signature as make_serve_step, so the server's
    compiled-step cache, placement re-jits, and fault recovery treat both
    identically."""
    model = get_model(cfg)
    if model.paged_decode_step is None:
        raise NotImplementedError(
            f"family {cfg.family!r} has no paged decode path")

    def serve_step(params, state, batch):
        logits, state = model.paged_decode_step(params, state, batch, cfg, mesh)
        return _greedy(logits, cfg), state

    return serve_step
