"""The ragged (exact-size) path is TPU-only: XLA:CPU cannot compile
ragged-all-to-all. We verify (a) it TRACES and LOWERS correctly (the jaxpr
contains the primitive with the right shapes), (b) the gate reports
unsupported here, (c) compile on CPU raises — pinning the documented reason
the dense path is the container default."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core.group import EpGroupConfig, ep_create_group
from repro.core import ll
from repro.core.ragged import ll_dispatch_ragged, ragged_supported


def test_gate_reports_cpu_unsupported():
    assert not ragged_supported()


def test_ragged_traces_and_lowers():
    N, E, K, T, H = 8, 16, 4, 8, 32
    mesh = jax.make_mesh((8,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    cfg = EpGroupConfig(num_experts=E, max_tokens_per_rank=T, hidden=H,
                        top_k=K, mode="ll", payload_dtype=jnp.float32)
    group = ep_create_group(cfg, ep_size=N)

    def step(x, topk):
        h = ll.ll_create_handle(group, topk[0], jnp.ones((T, K), jnp.float32))
        recv, sizes = ll_dispatch_ragged(group, h, x[0])
        return recv[None], sizes[None]

    f = jax.jit(jax.shard_map(step, mesh=mesh,
                              in_specs=(P("data"), P("data")),
                              out_specs=(P("data"), P("data"))))
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(N, T, H), jnp.float32)
    topk = jnp.asarray(np.stack([
        np.stack([rng.choice(E, K, replace=False) for _ in range(T)])
        for _ in range(N)]), jnp.int32)
    lowered = f.lower(x, topk)
    txt = lowered.as_text()
    assert "ragged_all_to_all" in txt or "ragged-all-to-all" in txt
    with pytest.raises(Exception, match="(?i)ragged|unimplemented|not supported"):
        lowered.compile()
