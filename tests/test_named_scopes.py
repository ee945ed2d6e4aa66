"""Named scopes at the model's and the EP API's layer boundaries: each
lands in the compiled program's ``op_name`` metadata, where the benchmark's
trace reduction (bench/trace.py ``scopes_from_hlo``) finds the device time
of each layer. Compiled here on the CPU; the scopes only name ops."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_smoke
from repro.core import (EpGroupConfig, ep_combine, ep_complete,
                        ep_create_group, ep_create_handle, ep_dispatch)
from repro.runtime.server import ContinuousDecodeServer
from repro.runtime.steps import paged_serve_state_specs

EP_SCOPES = ("ep.handle", "ep.dispatch_send", "ep.dispatch_recv",
             "ep.combine_send", "ep.combine_recv")


def _scoped(bench_trace, hlo: str, scope: str) -> list[str]:
    scopes = bench_trace.scopes_from_hlo(hlo)
    return [n for n in scopes if bench_trace.in_scope(scopes, scope)(n)]


def test_paged_serve_step_names_its_layers(bench_trace):
    srv = ContinuousDecodeServer(get_smoke("dbrx-132b"), batch=2, max_len=32,
                                 page_size=4)
    _, feed = paged_serve_state_specs(srv.cfg, srv.batch, srv.num_pages,
                                      srv.page_size, srv.max_pages)
    feed = {k: jax.ShapeDtypeStruct(v.shape, v.dtype) for k, v in feed.items()}
    hlo = srv.step.lower(srv.params, srv.state, feed).compile().as_text()
    srv.close()
    for scope in ("attn", "paged_decode", "moe", "head"):
        assert _scoped(bench_trace, hlo, scope), scope
    # paged decode runs inside attention
    scopes = bench_trace.scopes_from_hlo(hlo)
    assert all("/attn/" in f"/{scopes[n]}/"
               for n in _scoped(bench_trace, hlo, "paged_decode"))


@pytest.mark.parametrize("mode", ["ll", "ht"])
@pytest.mark.parametrize("staged", [False, True], ids=["eager", "staged"])
def test_ep_round_trip_names_every_phase(bench_trace, mode, staged):
    """Every mode gets the same five EP scopes, eager or staged
    (``send_only`` + ``ep_complete``), on 4 virtual CPU devices."""
    N, E, K, T, H = 4, 8, 2, 8, 32
    group = ep_create_group(EpGroupConfig(
        num_experts=E, max_tokens_per_rank=T, hidden=H, top_k=K, mode=mode,
        payload_dtype=jnp.float32), ep_size=N)
    mesh = jax.make_mesh((N,), ("data",), devices=jax.devices()[:N],
                         axis_types=(jax.sharding.AxisType.Auto,))

    def round_trip(x, topk, w):
        handle = ep_create_handle(group, topk[0], w[0])
        if staged:
            y3d, _ = ep_complete(group, handle,
                                 ep_dispatch(group, handle, x[0],
                                             send_only=True))
            y = ep_complete(group, handle,
                            ep_combine(group, handle, y3d * 2.0,
                                       send_only=True))
        else:
            y3d, _ = ep_dispatch(group, handle, x[0])
            y = ep_combine(group, handle, y3d * 2.0)
        return y[None]

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((N, T, H)), jnp.float32)
    topk = jnp.asarray(np.stack([np.stack([rng.choice(E, K, replace=False)
                                           for _ in range(T)])
                                 for _ in range(N)]), jnp.int32)
    w = jnp.full((N, T, K), 1.0 / K, jnp.float32)
    fn = jax.jit(jax.shard_map(round_trip, mesh=mesh,
                               in_specs=(P("data"),) * 3,
                               out_specs=P("data")))
    hlo = fn.lower(x, topk, w).compile().as_text()
    for scope in EP_SCOPES:
        assert _scoped(bench_trace, hlo, scope), scope
