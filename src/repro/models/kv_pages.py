"""Paged KV cache: page-table-indexed pools + host-side free-list allocator.

The dense decode caches (``KVCache`` [B, S_max, ...], ``MLACache``) reserve
``batch × max_len`` tokens of HBM up front whether or not a slot is live.
The paged layout replaces them with a shared pool of fixed-size pages:

  pool      [num_pages + 1, page_size, Hkv, d]   (device, per layer)
  page_tbl  [B, max_pages] int32                  (host-built, per step)
  kv_lens   [B] int32                             (host-built, per step)

Row ``num_pages`` is the PAD page: idle slots and unallocated table entries
point at it, keeping every gather branch-free and jit-stable. The pad page's
content is irrelevant by construction — the decode kernel masks positions
``>= kv_lens`` with an exact zero (kernels/decode_attention.py), so neither
pad nor recycled-page garbage can perturb a live request. Memory now scales
with LIVE tokens (pages allocated) instead of ``batch × max_len``
(bench_memory's paged-KV accounting rows assert paged peak <= dense peak).

Allocation is host-side and strictly step-boundary (runtime/scheduler.py):
pages alloc when a request's next token crosses a page boundary, free when
the request completes. The allocator is a LIFO free list — recycling hot
pages quickly is deliberate, it stresses the masking contract that the
paged-KV tests pin.

GQA layers keep separate K and V pools; absorbed-MLA decode keeps the latent
``ckv`` (keys and values both, Hkv == 1) and the shared rotary key ``krope``
in two pools per layer, so each page's latent row is read from HBM once and
neither pool is padded to the other's width.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.models.config import ArchConfig
from repro.parallel.sharding import DEFAULT_RULES, ParamSpec, logical_to_pspec


class PagePoolExhausted(RuntimeError):
    """Raised when an alloc cannot be satisfied — always names the pool
    capacity so the failure is actionable (raise num_pages or admit less)."""


class PageAllocator:
    """Host-side LIFO free-list allocator over ``num_pages`` page ids.

    Invariants (pinned by tests/test_paged_kv.py): a page id is never handed
    to two live owners; double-free raises; exhaustion raises
    ``PagePoolExhausted`` naming the capacity."""

    def __init__(self, num_pages: int, page_size: int):
        if num_pages < 1 or page_size < 1:
            raise ValueError(f"need num_pages >= 1 and page_size >= 1, got "
                             f"{num_pages}, {page_size}")
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        self.pad_page = self.num_pages          # pool row used for idle slots
        self._free = list(range(num_pages - 1, -1, -1))   # pop() yields 0 first
        self._live: set[int] = set()
        self.peak_live = 0                      # high-water mark (bench_memory)

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def live_count(self) -> int:
        return len(self._live)

    def alloc(self, n: int = 1) -> list[int]:
        if n > len(self._free):
            raise PagePoolExhausted(
                f"page pool exhausted: requested {n} page(s) with "
                f"{len(self._free)} free of {self.num_pages} total "
                f"(page_size={self.page_size}); raise num_pages or lower "
                f"admission concurrency")
        ids = [self._free.pop() for _ in range(n)]
        self._live.update(ids)
        self.peak_live = max(self.peak_live, len(self._live))
        return ids

    def free(self, ids) -> None:
        for i in ids:
            if i not in self._live:
                raise ValueError(f"free of page {i} which is not live")
            self._live.remove(i)
            self._free.append(i)


def pages_for_tokens(tokens: int, page_size: int) -> int:
    """Pages needed to hold ``tokens`` KV entries."""
    return -(-tokens // page_size)


# --------------------------------------------------------------------------
# pool specs (per layer; the transformer stacks them with _stack)
# --------------------------------------------------------------------------

def paged_kv_pool_spec(cfg: ArchConfig, num_pages: int, page_size: int):
    """GQA per-layer pools: {"k", "v"} each [num_pages+1, page, n_kv, hd].
    Row num_pages is the pad page (init zeros, like the whole pool)."""
    a = cfg.attn
    arr = ParamSpec((num_pages + 1, page_size, a.n_kv, a.head_dim), cfg.dtype,
                    (None, None, "kv_heads", None))
    return {"k": arr, "v": arr}


def paged_mla_pool_spec(cfg: ArchConfig, num_pages: int, page_size: int):
    """Absorbed-MLA per-layer pools: {"ckv"} [num_pages+1, page, 1, r_kv]
    (the latent, keys and values both) and {"krope"} [.., 1, rope] (the
    shared rotary key). Two arrays, so each is read at its own width."""
    m = cfg.mla

    def pool(width):
        return ParamSpec((num_pages + 1, page_size, 1, width), cfg.dtype,
                         (None, None, None, None))
    return {"ckv": pool(m.kv_lora_rank), "krope": pool(m.qk_rope_dim)}


def write_token(pool: jax.Array, new: jax.Array, page_tbl: jax.Array,
                kv_lens: jax.Array) -> jax.Array:
    """Scatter one decode token's KV row per request into the pool.

    pool: [P+1, page, Hkv, d]; new: [B, Hkv, d] (this step's k/v/latent row);
    page_tbl: [B, max_pages] int32; kv_lens: [B] int32 tokens already held.
    The write lands at (tbl[b, kv_lens[b] // page], kv_lens[b] % page). Idle
    slots carry all-pad tables, so their rows land in the pad page — every
    idle row computes the identical value (same token-0 input), so the
    duplicate scatter is deterministic, and pad content is masked out of
    every live request's attention anyway."""
    B, max_pages = page_tbl.shape
    page = pool.shape[1]
    ord_ = jnp.clip(kv_lens // page, 0, max_pages - 1)
    page_ids = jnp.take_along_axis(page_tbl, ord_[:, None], axis=1)[:, 0]
    offs = kv_lens % page
    return pool.at[page_ids, offs].set(new.astype(pool.dtype))


def decode_attention(mesh, q, k_pages, v_pages, page_tbl, kv_lens, *,
                     rope_pages=None, **kw):
    """``kernels.ops.paged_decode_attention`` on a mesh: XLA cannot
    partition a Pallas kernel, so under a mesh each device runs it over its
    own share of the requests (the batch axes of the sharding rules)
    against the whole page pool. Pools sharded over a "model" axis are
    gathered first. Without a mesh it is the plain call."""
    from repro.kernels import ops as KOPS
    pools = {k: v for k, v in (("k_pages", k_pages), ("v_pages", v_pages),
                               ("rope_pages", rope_pages)) if v is not None}

    def attend(q, t, n, *vals):
        p = dict(zip(pools, vals))
        return KOPS.paged_decode_attention(
            q, p["k_pages"], p.get("v_pages"), t, n,
            rope_pages=p.get("rope_pages"), **kw)
    if mesh is None or mesh.empty:
        return attend(q, page_tbl, kv_lens, *pools.values())
    rows = logical_to_pspec(ParamSpec(q.shape[:1], axes=("batch",)), mesh,
                            DEFAULT_RULES)
    return jax.shard_map(
        attend, mesh=mesh, in_specs=(rows, rows, rows) + (P(),) * len(pools),
        out_specs=rows)(q, page_tbl, kv_lens, *pools.values())


def dense_equiv_tokens(batch: int, max_len: int) -> int:
    """Token capacity a dense [B, S_max] cache reserves — the baseline the
    paged accounting rows compare against (bench_memory)."""
    return batch * max_len
