"""Exact-sized LL transfers via ``jax.lax.ragged_all_to_all`` — the closest
TPU analogue of the paper's RDMA slot writes (only real tokens cross the
wire, receive regions are shared rather than per-pair).

With this path the LL buffer accounting matches Eq. 3 *exactly*:
dispatch ``N*B*P`` worst case but only actual bytes move; combine ``B*K*P``
shared slots. Entries destined to the same peer are made contiguous by the
same running-count maps the dense path uses (a stable sort by destination),
then each (src, dst) pair transfers exactly ``counts[src,dst]`` rows at
offsets both sides derive from the shared metadata.

**Gated**: XLA:CPU cannot compile ``ragged-all-to-all`` (verified on this
container: ThunkEmitter unimplemented), so this module is trace-tested only
here and selected via ``EpGroupConfig`` on TPU deployments.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.group import EpGroup, EpHandle
from repro.core import slots as S


def ragged_supported() -> bool:
    return jax.default_backend() == "tpu"


def ll_dispatch_ragged(group: EpGroup, handle: EpHandle, x: jax.Array):
    """Per-shard LL dispatch with exact-sized transfers.

    Returns (recv [N*C_d, H] shared buffer, recv_row_of_entry metadata) —
    unpack to the 3D layout reuses the dense path's maps."""
    if group.placement is not None:
        # this trace-only path still derives destinations contiguously; an
        # EpPlacement group must not silently route with stale arithmetic
        raise NotImplementedError(
            "ragged LL dispatch does not support explicit expert placements "
            "yet — route placement resolution through plan.dest_of when "
            "enabling it (docs/DESIGN.md §8)")
    N, L = group.ep_size, group.local_experts
    C = group.ll_disp_cap
    axis = group.cfg.ep_axis[0] if len(group.cfg.ep_axis) == 1 else group.cfg.ep_axis
    T, Kk = handle.topk_idx.shape
    dst = handle.topk_idx // L
    sends = jnp.zeros((T, N), bool).at[
        jnp.arange(T)[:, None], dst].set(True, mode="drop")
    pos = jnp.cumsum(sends.astype(jnp.int32), axis=0) - 1
    send_counts = sends.astype(jnp.int32).sum(0)               # [N]
    # pack send rows contiguous by destination: row = dst_block*C + pos
    t_idx = jnp.broadcast_to(jnp.arange(T)[:, None], (T, N)).reshape(-1)
    d_idx = jnp.broadcast_to(jnp.arange(N)[None, :], (T, N)).reshape(-1)
    gmap = S.build_gather_map(d_idx, pos.reshape(-1), t_idx, sends.reshape(-1),
                              N, C, sentinel=T)
    operand = S.gather_rows(x.astype(group.cfg.payload_dtype),
                            gmap).reshape(N * C, -1)
    output = jnp.zeros_like(operand)
    # offsets: sender reads block d at d*C; receiver writes block src at src*C
    input_offsets = jnp.arange(N, dtype=jnp.int32) * C
    send_sizes = send_counts
    me = jax.lax.axis_index(axis if isinstance(axis, str) else axis[0])
    output_offsets = jnp.full((N,), me * C, jnp.int32)  # my block on each peer
    # recv sizes: what each peer sends me == column me of the global counts
    recv_sizes = jax.lax.all_to_all(send_counts[:, None], axis,
                                    split_axis=0, concat_axis=1,
                                    tiled=False).reshape(N)
    recv = jax.lax.ragged_all_to_all(
        operand, output, input_offsets, send_sizes, output_offsets, recv_sizes,
        axis_name=axis)
    return recv, recv_sizes


def ll_dispatch_ragged_jaxpr(group: EpGroup, T: int, H: int):
    """Trace-only helper (tests): builds the jaxpr under an abstract mesh."""
    def f(x, topk):
        from repro.core.ll import ll_create_handle
        h = ll_create_handle(group, topk, jnp.ones(topk.shape, jnp.float32))
        return ll_dispatch_ragged(group, h, x)
    return f
