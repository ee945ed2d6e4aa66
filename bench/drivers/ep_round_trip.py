"""Driver ``ep_round_trip``: the EP API's dispatch/combine round trip.

``ep_create_group`` once; then per round trip, inside ``jax.shard_map`` over
a ("data",) mesh of the cell's chips: the DeepSeek-V3 router on this round
trip's input -> ``ep_create_handle`` -> ``ep_dispatch`` -> a cheap
per-expert step (expert e multiplies its rows by 1 + e) -> ``ep_combine``.
Round trips chain like consecutive MoE layers: each routes its own input,
and the next input is the output, rolled by one along the hidden axis and
normalised to unit RMS (so every round trip routes afresh). A jitted loop
runs ``round_trips_per_chunk`` of them per call, so the host syncs once per
chunk. The EP layer does nearly all the work, yet a row delivered to the
wrong expert still changes the output.

``ep_layer_us`` is the window over the round trips completed in it.

The check: each chunk keeps the input and output of one round trip, drawn
from the seed; a seeded sample of those kept is compared, token by token,
with the plain reference (reference/ep.py) run on the same input. The
number compared is the worst token's relative error. It covers routing and
plan, dispatch with the fp8 payload, the all-to-all, receive and combine.
"""
from __future__ import annotations

import functools
import importlib
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import (EpGroupConfig, ep_combine, ep_create_group,
                        ep_create_handle, ep_dispatch)
from repro.core.routing import RouterConfig, route
from repro.models.moe import _router_cfg

import costs
import device
import weights
from reference import ep as REF

SCOPES = ("ep_dispatch", "ep_combine")
KERNELS = ("dispatch_pack", "recv_unpack", "combine_gather_reduce")


def router_config(conf: dict) -> RouterConfig:
    """The router the file describes, checked against the program's preset
    for it (unless the file names a smoke preset)."""
    rc = RouterConfig(
        num_experts=conf["n_routed_experts"], top_k=conf["num_experts_per_tok"],
        gating=conf["scoring_func"], n_groups=conf["n_group"],
        topk_groups=conf["topk_group"],
        routed_scaling_factor=conf["routed_scaling_factor"],
        norm_topk_prob=conf["norm_topk_prob"])
    prog = conf["program"]
    if not prog.get("smoke"):
        mod = importlib.import_module(f"repro.configs.{prog['preset']}")
        ref = _router_cfg(mod.full_config(prog["shape"]).moe)
        keys = ("num_experts", "top_k", "gating", "n_groups", "topk_groups",
                "routed_scaling_factor", "norm_topk_prob")
        bad = {k: (getattr(ref, k), getattr(rc, k)) for k in keys
               if getattr(ref, k) != getattr(rc, k)}
        if bad:
            raise ValueError(f"program router differs from the file: {bad}")
    return rc


def ref_router(conf: dict) -> dict:
    return {k: conf[k] for k in ("n_routed_experts", "num_experts_per_tok",
                                 "n_group", "topk_group", "norm_topk_prob",
                                 "routed_scaling_factor")}


def build(cell, mesh):
    """(jitted chunk, group, inputs maker) for the cell."""
    conf, tr = cell.config, cell.traffic
    N = mesh.size
    H, T = conf["hidden_size"], tr["tokens_per_rank"]
    rc = router_config(conf)
    E, K = rc.num_experts, rc.top_k
    group = ep_create_group(EpGroupConfig(
        num_experts=E, max_tokens_per_rank=T, hidden=H, top_k=K,
        mode=tr["mode"], ll_layout="nccl_ep",
        capacity_factor=tr["capacity_factor"],
        expert_capacity_factor=tr["expert_capacity_factor"],
        payload_dtype=jnp.bfloat16, quantize_dispatch=tr["fp8"]), mesh=mesh)
    L = group.local_experts
    R = tr["round_trips_per_chunk"]
    alter = cell.hooks.get("alter")

    def round_trip(x, w):
        with jax.named_scope("ep_dispatch"):
            logits = jnp.dot(x.astype(jnp.float32), w,
                             precision=jax.lax.Precision.HIGHEST)
            r = route(logits, rc)
            handle = ep_create_handle(group, r.topk_idx, r.topk_weights)
            y3d, _ = ep_dispatch(group, handle, x)
        first = jax.lax.axis_index("data") * L
        gain = (1 + first + jnp.arange(L)).astype(y3d.dtype)
        y3d = y3d * gain[:, None, None]
        with jax.named_scope("ep_combine"):
            y = ep_combine(group, handle, y3d)
        return y if alter is None else alter(y)

    def body(x, w, pick):
        def step(i, carry):
            x, kx, ky = carry
            y = round_trip(x, w)
            hit = i == pick[0]
            kx = jnp.where(hit, x, kx)
            ky = jnp.where(hit, y, ky)
            z = jnp.roll(y.astype(jnp.float32), 1, axis=-1)
            z = z * jax.lax.rsqrt(jnp.mean(z * z, -1, keepdims=True) + 1e-12)
            return z.astype(x.dtype), kx, ky
        return jax.lax.fori_loop(0, R, step, (x, x, x))

    data = P("data")
    chunk = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(data, P(), P()),
                                  out_specs=(data, data, data)))

    shard = NamedSharding(mesh, data)

    @functools.partial(jax.jit, out_shardings=shard)
    def tokens(key):
        return jax.random.normal(key, (N * T, H), jnp.float32).astype(jnp.bfloat16)

    def inputs(seed):
        x = tokens(weights.leaf_key(seed, "tokens"))
        w = weights.make(seed, {"router": jax.ShapeDtypeStruct((H, E), jnp.float32)})
        return x, jax.device_put(w["router"], NamedSharding(mesh, P()))

    return chunk, group, inputs


def run(cell) -> dict:
    conf, tr = cell.config, cell.traffic
    devices = cell.devices[:cell.chips]
    mesh = jax.make_mesh((len(devices),), ("data",), devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,))
    chunk, group, inputs = build(cell, mesh)
    R = tr["round_trips_per_chunk"]
    x, w = inputs(cell.seed)
    rng = np.random.default_rng(cell.seed)
    repl = NamedSharding(mesh, P())

    def pick():
        return jax.device_put(np.asarray([rng.integers(R)], np.int32), repl)

    compiled = chunk.lower(x, w, pick()).compile()
    hlo = compiled.as_text()
    for _ in range(tr["warm_chunks"]):
        x = jax.block_until_ready(compiled(x, w, pick())[0])

    traced = device.TracedWindow(cell.trace_dir)
    keep, n_keep = [], tr["check_samples"]
    chunks = traced_chunks = 0
    t_open = time.perf_counter()
    traced.begin()
    while True:
        with device.span("bench.chunk", traced.active):
            x, kx, ky = compiled(x, w, pick())
            x = jax.block_until_ready(x)
        chunks += 1
        if traced.active:
            traced_chunks += 1
            if time.perf_counter() - t_open >= tr["trace_seconds"]:
                traced.end()
        # reservoir sample of the kept round trips, drawn from the seed
        if len(keep) < n_keep:
            keep.append((kx, ky))
        else:
            j = rng.integers(chunks)
            if j < n_keep:
                keep[j] = (kx, ky)
        t_close = time.perf_counter()
        if t_close - t_open >= cell.seconds:
            break
    traced.end()
    window_s = t_close - t_open
    mem = device.peak_bytes(devices)
    del compiled, x, kx, ky
    err, ties, counts = check(cell, conf, w, keep, group.local_experts,
                              tr["fp8"])
    control = None
    if cell.hooks.get("control"):
        control = dict(worst_token_rel_err=control_reading(conf, w, keep,
                                                           tr["fp8"]))
    round_trips = chunks * R
    return dict(
        t_open=t_open, e2e=dict(ep_layer_us=window_s / round_trips * 1e6),
        control=control,
        checks=[dict(name="worst_token_rel_err", value=err,
                     limit=float(tr["limit_rel_err"]))],
        attempted=round_trips, failed=0, memory_peak_bytes=mem,
        kernels=device.kernel_counts(hlo), hlo=hlo,
        info=dict(round_trips=round_trips, chunks=chunks, window_s=window_s,
                  checked_round_trips=len(keep), tie_tokens=ties),
        counters=dict(round_trips=traced_chunks * R, routing=counts,
                      hidden=conf["hidden_size"], fp8=tr["fp8"]),
    )


def _payloads(fp8: bool) -> tuple[str, str]:
    """(the configuration's payload rounding, the one below it)."""
    return ("fp8", "int4") if fp8 else ("none", "fp8")


def _worst(y, y_ref, tie) -> float:
    """Worst token's ||y - y_ref|| / ||y_ref||, tied tokens left out."""
    num = jnp.linalg.norm(jnp.asarray(y, jnp.float32) - y_ref, axis=-1)
    den = jnp.maximum(jnp.linalg.norm(y_ref, axis=-1), 1e-30)
    return float(np.where(np.asarray(tie), 0.0, np.asarray(num / den)).max())


def check(cell, conf, w, keep, experts_per_rank, fp8):
    """Worst relative error of any checked token against the reference
    (ties left out), the tied tokens' count, and the routing counts of the
    checked round trips (for the byte model)."""
    rc, quant = ref_router(conf), _payloads(fp8)[0]
    worst, ties, counts = 0.0, 0, []
    for kx, ky in keep:
        y_ref, idx, tie = REF.round_trip(jnp.asarray(kx), w, rc, quant)
        worst = max(worst, _worst(ky, y_ref, tie))
        ties += int(np.asarray(tie).sum())
        counts.append(costs.routing_counts(
            np.asarray(idx).reshape(cell.chips, -1, idx.shape[-1]),
            experts_per_rank))
    return worst, ties, counts


def control_reading(conf, w, keep, fp8):
    """The control's number: the reference in the payload precision below
    the configuration's (int4 for fp8, fp8 for bf16) put in the program's
    place, judged against the reference like the program."""
    rc, (quant, low) = ref_router(conf), _payloads(fp8)
    worst = 0.0
    for kx, _ in keep:
        y_ref, _, tie = REF.round_trip(jnp.asarray(kx), w, rc, quant)
        y_low, _, _ = REF.round_trip(jnp.asarray(kx), w, rc, low)
        worst = max(worst, _worst(y_low, y_ref, tie))
    return worst
