"""Unified EP API — the paper's headline contribution (§III).

One dispatch/combine pair for every workload; the algorithm (LL / HT /
baseline) is chosen **once, at group creation** (`EpGroupConfig.mode`,
"auto" selects by `max_tokens_per_rank` like the paper's planned
auto-detection). Call sites never change across modes:

    group  = ep_create_group(cfg, mesh=mesh)
    handle = ep_create_handle(group, topk_idx, topk_weights)
    xs, counts = ep_dispatch(group, handle, tokens)
    ...expert FFN...
    out = ep_combine(group, handle, expert_out)

All functions must be called *inside* the sharded region (shard_map over the
group's EP axes) — they are collectives, exactly like `jax.lax.psum`. The
handle is shared between forward and backward (the Megatron "cached dispatch"
integration, §VI-B): JAX AD transposes dispatch into combine and vice versa
through the same traced slot maps, so handle reuse is automatic.

Every entry point routes through the ``EpBackend`` registry
(core/backend.py) keyed by ``group.mode`` — the API layer contains no
per-mode branching and no pending-type ``isinstance`` chains. The staged
surface is part of the backend contract: ``send_only=True`` returns a
mode-tagged ``EpPending`` and ``ep_complete`` finishes it, for **every**
registered mode (LL decode overlap, HT prefill pipelining, baseline
apples-to-apples) — a backend may refuse with ``NotImplementedError`` but
may never accept the flag and silently run eager.

`ep_create_handle` also derives the complete slot-map chain for every phase
(the `EpPlan` engine, core/plan.py) — dispatch and combine are then pure
single-pass data movement over precomputed maps; no slot arithmetic runs
inside them (the one-pass-per-phase invariant).

The tagged-tensor entry points (`ep_dispatch_tensors`) mirror the C API's
``ncclNDTensor_t`` signature for framework integrations that want role
validation.
"""
from __future__ import annotations

from typing import Sequence

import jax

from repro.core.group import (EpGroup, EpGroupConfig, EpHandle, ep_create_group,
                              ep_handle_get_num_recv_tokens, ep_handle_destroy)
from repro.core.backend import EpPending, get_backend, registered_modes
# importing the mode modules registers their backends with the registry
from repro.core import ll as _ll        # noqa: F401
from repro.core import ht as _ht        # noqa: F401
from repro.core import baseline as _bl  # noqa: F401
from repro.core import plan as _plan
from repro.core.tensor import EpTensor, EpTensorTag, validate

__all__ = [
    "EpGroup", "EpGroupConfig", "EpHandle", "EpPending", "ep_create_group",
    "ep_create_handle", "ep_handle_refresh", "ep_dispatch", "ep_combine",
    "ep_complete", "ep_handle_get_num_recv_tokens", "ep_handle_destroy",
    "ep_dispatch_tensors", "ep_combine_tensors", "registered_modes",
]


def ep_create_handle(group: EpGroup, topk_idx: jax.Array,
                     topk_weights: jax.Array, num_tokens=None) -> EpHandle:
    """``ncclEpCreateHandle``: capture per-forward-pass routing state.

    HT/baseline run their metadata exchange here (paper §III-C2); LL's
    exchange is folded in too (strictly earlier than the paper's in-dispatch
    headers, see docs/DESIGN.md §2). Runs in the named scope
    ``ep.handle``."""
    with jax.named_scope("ep.handle"):
        return get_backend(group.mode).create_handle(group, topk_idx,
                                                     topk_weights, num_tokens)


def ep_handle_refresh(group: EpGroup, handle: EpHandle,
                      topk_weights: jax.Array,
                      topk_idx: jax.Array | None = None,
                      num_tokens=None) -> EpHandle:
    """``ncclEpHandleRefresh``-style steady-state path: rebind per-step
    routing state into an existing handle without rebuilding slot maps.

    ``topk_idx=None`` (or passing the handle's own array) rebinds weights
    only — every precomputed map is reused verbatim. With a new ``topk_idx``
    the routing-hash fast path decides at runtime: unchanged routing
    (speculative-decode replay, cached dispatch in backward) skips plan
    construction entirely; changed routing rebuilds like ``ep_create_handle``.
    Mode-agnostic — works for LL, HT, and baseline handles alike."""
    return _plan.refresh_handle(group, handle, topk_weights, topk_idx,
                                num_tokens)


def ep_dispatch(group: EpGroup, handle: EpHandle, tokens: jax.Array, *,
                send_only: bool = False):
    """``ncclEpDispatch``: route tokens to their experts.

    Returns (expert_major [L, A, H], tokens_per_expert [L]) — or, with
    send_only=True, a mode-tagged EpPending for staged overlap (honored by
    every registered backend)."""
    return get_backend(group.mode).dispatch(group, handle, tokens,
                                            send_only=send_only)


def ep_combine(group: EpGroup, handle: EpHandle, expert_out: jax.Array, *,
               send_only: bool = False):
    """``ncclEpCombine``: gather expert outputs, weighted-reduce to original
    token order. Input layout must match the group's dispatch output."""
    return get_backend(group.mode).combine(group, handle, expert_out,
                                           send_only=send_only)


def ep_complete(group: EpGroup, handle: EpHandle, pending: EpPending):
    """``ncclEpComplete``: finalize a staged (send_only) operation.

    Routes by the pending's mode/op tags through the backend registry; a
    pending created under a different mode than the group's fails loudly."""
    return get_backend(group.mode).complete(group, handle, pending)


# ---------------------------------------------------------------------------
# tagged-tensor surface (C-API parity)
# ---------------------------------------------------------------------------

def ep_dispatch_tensors(group: EpGroup, handle: EpHandle,
                        inputs: Sequence[EpTensor], *, send_only=False):
    toks = next(t for t in inputs if t.tag == EpTensorTag.TOKENS)
    tokens = validate(toks, tag=EpTensorTag.TOKENS, ndim=2)
    out, counts = ep_dispatch(group, handle, tokens, send_only=send_only)
    return (EpTensor(out, EpTensorTag.TOKENS),
            EpTensor(counts, EpTensorTag.TOKENS_PER_EXPERTS))


def ep_combine_tensors(group: EpGroup, handle: EpHandle,
                       inputs: Sequence[EpTensor], *, send_only=False):
    toks = next(t for t in inputs if t.tag == EpTensorTag.TOKENS)
    y = validate(toks, tag=EpTensorTag.TOKENS, ndim=3)
    out = ep_combine(group, handle, y, send_only=send_only)
    return EpTensor(out, EpTensorTag.TOKENS)
