"""Drive the system's main path once on a TPU and check what comes out.

One chip (the default) runs two phases through the library's own entry points:

  ep     The EP API (ep_create_group / ep_create_handle / ep_dispatch /
         ep_combine under jax.shard_map) on a 1-device ("data",) mesh at the
         paper's hidden size H=7168 in bf16, routed by the DeepSeek-V3 router
         (256 experts, top-8): LL at 128 tokens per rank, then HT at 4096
         tokens per rank with fp8 dispatch (one scale per 128 values). Expert
         e multiplies its rows by (1+e) through the grouped-GEMM kernel, so
         the output must equal x * sum_k w[t,k] * (1 + e[t,k]).
  serve  ContinuousDecodeServer.serve_requests on DBRX-132B at its published
         widths, cut to one layer, answering 8 seeded requests.

``--chips 4`` runs only what exists across chips: moe_block over a 4-device
("data",) mesh (LL, then HT with fp8) against the dense MoE reference in f32,
then the continuous server on that mesh with two DBRX layers.

Each phase compiles its step ahead of time and fails unless the compiled
program holds the Pallas kernels its shapes select (``tpu_custom_call``), so a
kernel gate that silently fell back to XLA is a failure. Off a TPU the script
refuses to run. Every phase prints one JSON line; the last line is
``{"ok": true, "device": {...}}`` and is printed only when every phase passed.

  python chip_smoke.py [--chips 4]
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import re
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.configs import dbrx_132b, deepseek_v3_671b  # noqa: E402
from repro.core import (EpGroupConfig, ep_combine, ep_create_group,  # noqa: E402
                        ep_create_handle, ep_dispatch)
from repro.core.routing import RouterConfig, route  # noqa: E402
from repro.kernels import ops as K  # noqa: E402
from repro.models.moe import (_moe_dense_fallback, _router_cfg,  # noqa: E402
                              moe_block, moe_spec)
from repro.parallel.sharding import arch_rules, init_from_specs  # noqa: E402
from repro.runtime.scheduler import Request  # noqa: E402
from repro.runtime.server import ContinuousDecodeServer  # noqa: E402
from repro.runtime.steps import paged_serve_state_specs  # noqa: E402

# Kernels each phase's shapes select (kernels/ops.py gates).
EP_KERNELS = ("dispatch_pack", "recv_unpack", "combine_gather_reduce",
              "grouped_gemm")
SERVE_KERNELS = ("paged_decode_stage1", "paged_decode_stage2")
# LL at the paper's widths; fewer than 128 rows per expert at the serving
# batch sizes miss the grouped-GEMM gate (A % 128), so the EP server step
# selects the EP kernels without it.
EP_SERVE_KERNELS = SERVE_KERNELS + EP_KERNELS[:3]

# Tolerances on ||y - ref|| / ||ref||.
# bf16 LL: the payload travels exactly; (1+e)x and the combined output are
# each rounded once to bf16 (8-bit mantissa, relative error <= 2^-9).
TOL_EP_LL = 1e-2
# fp8 HT: e4m3 keeps 3 mantissa bits, so each quantized value is off by up to
# 2^-4 of itself (rms about a third of that) before the bf16 roundings.
TOL_EP_HT = 5e-2
# moe_block vs the f32 dense reference: bf16 weights and activations through
# three GEMMs and SwiGLU (LL); fp8 dispatch adds the e4m3 error above (HT).
TOL_MOE_LL = 2e-2
TOL_MOE_HT = 8e-2


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or incomplete result."""


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def report(record: dict) -> None:
    print(json.dumps(record), flush=True)


def kernel_counts(hlo_text: str) -> dict[str, int]:
    """Pallas kernels in a compiled TPU program, by ``pallas_call`` name."""
    counts: dict[str, int] = {}
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        m = re.match(r"\s*(?:ROOT\s+)?%?([A-Za-z_][\w-]*?)(?:\.\d+)*\s*=", line)
        name = m.group(1) if m else "?"
        counts[name] = counts.get(name, 0) + 1
    return counts


def require_kernels(record: dict, expected) -> None:
    missing = [k for k in expected if not record["kernels"].get(k)]
    check(not missing, f"{record['phase']}: kernels {missing} missing from the "
          f"compiled step (found {record['kernels']})")


def peak_bytes(device) -> int | None:
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def compile_step(fn, *args):
    """AOT-compile ``fn`` for ``args``; returns (compiled, seconds, counts)."""
    t0 = time.perf_counter()
    compiled = fn.lower(*args).compile()
    return compiled, time.perf_counter() - t0, kernel_counts(compiled.as_text())


def rel_err(y, ref) -> float:
    y, ref = jnp.asarray(y, jnp.float32), jnp.asarray(ref, jnp.float32)
    return float(jnp.linalg.norm(y - ref) / jnp.linalg.norm(ref))


# --------------------------------------------------------------------------
# phase ep: the EP library on one rank
# --------------------------------------------------------------------------

def _scaled_experts(y3d, counts, scale_w):
    """Expert e's rows times (1+e), through the grouped-GEMM kernel: each
    row is viewed as H/128 rows of 128 lanes against a [128, 128] scaled
    identity, so the product is exact before the bf16 output rounding."""
    L, A, H = y3d.shape
    lanes = scale_w.shape[-1]
    z = K.grouped_gemm(y3d.reshape(L, A * H // lanes, lanes), scale_w,
                       counts * (H // lanes))
    return z.reshape(L, A, H)


def ep_step(mesh, router: RouterConfig, *, mode: str, tokens: int,
            hidden: int, quantize: bool):
    """(jitted EP step, jitted input maker) for ``tokens`` per rank: the
    step returns (y, oracle, per-expert counts)."""
    N, E, Kk = mesh.size, router.num_experts, router.top_k
    group = ep_create_group(EpGroupConfig(
        num_experts=E, max_tokens_per_rank=tokens, hidden=hidden, top_k=Kk,
        mode=mode, payload_dtype=jnp.bfloat16, quantize_dispatch=quantize,
        # HT sizes expert regions from the mean load; twice it keeps
        # random routing drop-free
        expert_capacity_factor=2.0 if mode == "ht" else None), mesh=mesh)

    def body(xt, router_w, scale_w):
        r = route(xt.astype(jnp.float32) @ router_w, router)
        handle = ep_create_handle(group, r.topk_idx, r.topk_weights)
        y3d, counts = ep_dispatch(group, handle, xt)
        y = ep_combine(group, handle, _scaled_experts(y3d, counts, scale_w))
        # oracle: every expert copy of x_t comes back scaled by (1+e)
        gain = (r.topk_weights * (1.0 + r.topk_idx)).sum(-1, keepdims=True)
        return y, xt.astype(jnp.float32) * gain, counts

    step = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P("data"), P(), P("data")),
        out_specs=(P("data"), P("data"), P("data"))))
    data = NamedSharding(mesh, P("data"))
    lanes = 128

    @functools.partial(jax.jit, out_shardings=(
        data, NamedSharding(mesh, P()), data))
    def make_inputs(key):
        kx, kr = jax.random.split(key)
        x = jax.random.normal(kx, (N * tokens, hidden), jnp.bfloat16)
        router_w = (jax.random.normal(kr, (hidden, E), jnp.float32)
                    * hidden ** -0.5)
        scale_w = ((1.0 + jnp.arange(E, dtype=jnp.float32))[:, None, None]
                   * jnp.eye(lanes, dtype=jnp.float32))
        return x, router_w, scale_w.astype(jnp.bfloat16)

    return step, make_inputs


def ep_phase(mesh, router: RouterConfig, *, mode: str, tokens: int,
             hidden: int, quantize: bool, seed: int = 0) -> dict:
    """Dispatch -> per-expert (1+e) -> combine on ``mesh``; checks the
    result against x * sum_k w (1+e) and that nothing was dropped."""
    N, E, Kk = mesh.size, router.num_experts, router.top_k
    step, make_inputs = ep_step(mesh, router, mode=mode, tokens=tokens,
                                hidden=hidden, quantize=quantize)
    args = make_inputs(jax.random.PRNGKey(seed))
    compiled, compile_s, kernels = compile_step(step, *args)
    t0 = time.perf_counter()
    y, ref, counts = jax.block_until_ready(compiled(*args))
    run_s = time.perf_counter() - t0
    routed = int(counts.sum())
    err = rel_err(y, ref)
    tol = TOL_EP_HT if quantize else TOL_EP_LL
    rec = dict(phase="ep", mode=mode, fp8=quantize, ranks=N,
               tokens_per_rank=tokens, hidden=hidden, experts=E, top_k=Kk,
               compile_s=compile_s,
               run_s=run_s, rel_err=err, tol=tol, routed=routed,
               kernels=kernels, peak_bytes=peak_bytes(mesh.devices.flat[0]))
    check(routed == N * tokens * Kk,
          f"ep {mode}: {N * tokens * Kk - routed} routed copies dropped")
    check(bool(np.isfinite(err)) and err <= tol,
          f"ep {mode}: relative error {err} above {tol}")
    return rec


# --------------------------------------------------------------------------
# phase serve: the continuous-batching engine
# --------------------------------------------------------------------------

def seeded_requests(vocab: int, *, count: int, prompt_lens: tuple[int, int],
                    new_tokens: int, seed: int = 0) -> list[Request]:
    rng = np.random.RandomState(seed)
    lens = rng.randint(prompt_lens[0], prompt_lens[1] + 1, size=count)
    return [Request(i, rng.randint(0, vocab, n), new_tokens)
            for i, n in enumerate(lens)]


def expert_shards_split(params, ep_size: int) -> bool:
    """True when every expert-stacked leaf holds 1/ep_size of the experts
    per device (its expert axis is the third from last)."""
    found = False
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        if getattr(path[-1], "key", None) not in ("w_gate", "w_up", "w_down"):
            continue
        found = True
        ax = leaf.ndim - 3
        if leaf.sharding.shard_shape(leaf.shape)[ax] * ep_size != leaf.shape[ax]:
            return False
    return found


def serve_phase(cfg, mesh, requests: list[Request], *, page_size: int = 8,
                seed: int = 0) -> dict:
    """serve_requests to completion; checks every request finished with its
    token budget inside the vocabulary and every page went back."""
    max_len = max(r.prompt.size + r.max_new_tokens for r in requests)
    t0 = time.perf_counter()
    srv = ContinuousDecodeServer(cfg, batch=len(requests), max_len=max_len,
                                 mesh=mesh, page_size=page_size, seed=seed)
    jax.block_until_ready(srv.params)
    init_s = time.perf_counter() - t0
    ep_size = 1 if mesh is None else mesh.shape["data"]
    split = ep_size == 1 or expert_shards_split(srv.params, ep_size)
    _, feed = paged_serve_state_specs(cfg, srv.batch, srv.num_pages,
                                      srv.page_size, srv.max_pages)
    feed = {k: jax.ShapeDtypeStruct(v.shape, v.dtype) for k, v in feed.items()}
    _, compile_s, kernels = compile_step(srv.step, srv.params, srv.state, feed)
    t0 = time.perf_counter()
    metrics = srv.serve_requests(requests)
    run_s = time.perf_counter() - t0
    sched = srv.reqsched
    srv.close()
    streams = {r.rid: sched.tokens_for(r.rid) for r in requests
               if r.rid in sched.finished}
    device = (jax.devices()[0] if mesh is None else mesh.devices.flat[0])
    rec = dict(phase="serve", model=cfg.name, layers=cfg.num_layers,
               ranks=ep_size, requests=len(requests),
               completed=metrics.requests_completed,
               tokens=metrics.total_tokens, steps=metrics.serve_steps,
               pages_peak=metrics.pages_peak, pages_live=sched.alloc.live_count,
               experts_split=split, init_s=init_s, compile_s=compile_s,
               run_s=run_s, kernels=kernels, peak_bytes=peak_bytes(device))
    check(split, "serve: expert weights are not split over 'data'")
    check(metrics.requests_completed == len(requests) and sched.done,
          f"serve: {metrics.requests_completed}/{len(requests)} completed")
    for r in requests:
        toks = streams.get(r.rid, np.zeros(0, np.int32))
        check(toks.size == r.max_new_tokens,
              f"serve: request {r.rid} got {toks.size}/{r.max_new_tokens} tokens")
        check(bool(np.all((toks >= 0) & (toks < cfg.vocab))),
              f"serve: request {r.rid} emitted ids outside the vocabulary")
    check(sched.alloc.free_count == srv.num_pages,
          f"serve: {sched.alloc.live_count} pages still held")
    return rec


# --------------------------------------------------------------------------
# --chips 4: moe_block over the EP mesh against the dense reference
# --------------------------------------------------------------------------

def moe_phase(cfg, mesh, *, mode: str, tokens: int, quantize: bool,
              ref_tokens: int, seed: int = 0) -> dict:
    """moe_block over ``mesh`` vs _moe_dense_fallback in f32 on the first
    ``ref_tokens`` tokens of every rank (tokens route independently when
    nothing is dropped, so a prefix is a fair sample)."""
    N = mesh.shape["data"]
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, ep_mode=mode, quantize_dispatch=quantize,
        capacity_factor=None, expert_capacity_factor=2.0))
    params = init_from_specs(jax.random.PRNGKey(seed), moe_spec(cfg), mesh,
                             arch_rules(cfg))
    split = expert_shards_split(params, N)
    x = jax.jit(lambda k: jax.random.normal(k, (N, tokens, cfg.d_model),
                                            jnp.bfloat16),
                out_shardings=NamedSharding(mesh, P("data")))(
        jax.random.PRNGKey(seed + 1))
    step = jax.jit(lambda p, x: moe_block(p, x, cfg, mesh)[0])
    compiled, compile_s, kernels = compile_step(step, params, x)
    t0 = time.perf_counter()
    y = jax.block_until_ready(compiled(params, x))
    run_s = time.perf_counter() - t0

    @jax.jit
    def reference(p, x):
        p32 = jax.tree.map(lambda a: a.astype(jnp.float32), p)
        return _moe_dense_fallback(p32, x.astype(jnp.float32), cfg)

    xs = jax.device_put(x[:, :ref_tokens], NamedSharding(mesh, P()))
    err = rel_err(y[:, :ref_tokens], reference(params, xs))
    tol = TOL_MOE_HT if quantize else TOL_MOE_LL
    rec = dict(phase="moe", mode=mode, fp8=quantize, ranks=N,
               tokens_per_rank=tokens, ref_tokens_per_rank=ref_tokens,
               d_model=cfg.d_model, experts=cfg.moe.num_experts,
               top_k=cfg.moe.top_k, experts_split=split, compile_s=compile_s,
               run_s=run_s, rel_err=err, tol=tol, kernels=kernels,
               peak_bytes=peak_bytes(mesh.devices.flat[0]))
    check(split, "moe: expert weights are not split over 'data'")
    check(bool(np.isfinite(err)) and err <= tol,
          f"moe {mode}: relative error {err} above {tol}")
    return rec


# --------------------------------------------------------------------------
# entry point
# --------------------------------------------------------------------------

def ep_mesh(devices):
    """The ("data",) EP mesh over ``devices``."""
    return jax.make_mesh((len(devices),), ("data",), devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,))


def deepseek_router() -> RouterConfig:
    return _router_cfg(deepseek_v3_671b.full_config("decode_32k").moe)


def dbrx(layers: int):
    return dataclasses.replace(dbrx_132b.full_config("decode_32k"),
                               num_layers=layers)


def one_chip(device) -> None:
    mesh = ep_mesh([device])
    router = deepseek_router()
    for mode, tokens, fp8 in (("ll", 128, False), ("ht", 4096, True)):
        rec = ep_phase(mesh, router, mode=mode, tokens=tokens, hidden=7168,
                       quantize=fp8)
        report(rec)
        require_kernels(rec, EP_KERNELS)
    cfg = dbrx(layers=1)
    report(dict(phase="serve", cut=f"{cfg.name}: depth 40 -> 1 layer (one "
                "whole MoE period); widths as published"))
    rec = serve_phase(cfg, None, seeded_requests(
        cfg.vocab, count=8, prompt_lens=(32, 64), new_tokens=16))
    report(rec)
    require_kernels(rec, SERVE_KERNELS)


def four_chips(devices) -> None:
    mesh = ep_mesh(devices)
    cfg = dbrx(layers=2)
    for mode, tokens, fp8, ref_tokens in (("ll", 32, False, 32),
                                          ("ht", 4096, True, 256)):
        rec = moe_phase(cfg, mesh, mode=mode, tokens=tokens, quantize=fp8,
                        ref_tokens=ref_tokens)
        report(rec)
        require_kernels(rec, EP_KERNELS)
    report(dict(phase="serve", cut=f"{cfg.name}: depth 40 -> 2 layers; "
                "widths as published"))
    rec = serve_phase(cfg, mesh, seeded_requests(
        cfg.vocab, count=8, prompt_lens=(32, 64), new_tokens=16))
    report(rec)
    require_kernels(rec, EP_SERVE_KERNELS)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devices[0].platform}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    report(dict(compile_cache=enable_compile_cache()))
    if args.chips == 1:
        one_chip(devices[0])
    else:
        four_chips(devices[:args.chips])
    report(dict(ok=True, device=dict(platform=devices[0].platform,
                                     kind=devices[0].device_kind,
                                     count=len(devices))))
    return 0


if __name__ == "__main__":
    sys.exit(main())
