"""chip_smoke.py on the CPU: its phases at tiny sizes, its kernel check and
its refusal to run anywhere but on a TPU.

The EP phases run inside ``jax.shard_map``, where interpret-mode Pallas
trips JAX's varying-axes check, so they run on the jnp oracles here (the
``oracles`` fixture); the one-chip serve phase runs its paged-decode kernel
in interpret mode.
"""
import dataclasses
import importlib.util
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.compile_cache import CHECKOUT_CACHE, compile_cache_dir
from repro.configs import dbrx_132b
from repro.models.config import AttnSpec
from repro.runtime.server import DecodeServer

ROOT = Path(__file__).resolve().parents[1]
_spec = importlib.util.spec_from_file_location("chip_smoke",
                                               ROOT / "chip_smoke.py")
cs = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(cs)


def _tiny_dbrx(layers=1, experts=8):
    cfg = dbrx_132b.smoke_config()
    return dataclasses.replace(
        cfg, num_layers=layers,
        moe=dataclasses.replace(cfg.moe, num_experts=experts))


@pytest.fixture
def oracles(monkeypatch):
    """Kernels on the jnp oracles: interpret-mode Pallas cannot run inside
    jax.shard_map, where these phases run."""
    monkeypatch.delenv("REPRO_FORCE_PALLAS", raising=False)


def _requests(cfg, count=4):
    return cs.seeded_requests(cfg.vocab, count=count, prompt_lens=(4, 8),
                              new_tokens=4)


def test_main_refuses_off_tpu(capsys):
    assert cs.main([]) != 0
    out = capsys.readouterr()
    assert '"ok"' not in out.out and "needs a TPU" in out.err


def test_script_alone_fails(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       capture_output=True, text=True, timeout=120,
                       env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0 and '"ok"' not in r.stdout


HLO = """
  %dispatch_pack.1 = (f8e4m3fn[8,1,256]{2,1,0}) custom-call(%a), custom_call_target="tpu_custom_call"
  %grouped_gemm.12.3 = bf16[4,128,128]{2,1,0} custom-call(%b), custom_call_target="tpu_custom_call"
  ROOT %paged_decode_stage2 = f32[8,48,128]{2,1,0} custom-call(%c), custom_call_target="tpu_custom_call"
  %gather.4 = f32[8,256]{1,0} gather(%d, %e)
"""


def test_kernel_counts_reads_pallas_names():
    assert cs.kernel_counts(HLO) == {"dispatch_pack": 1, "grouped_gemm": 1,
                                     "paged_decode_stage2": 1}


def test_missing_kernel_fails_the_phase():
    rec = dict(phase="ep", kernels=cs.kernel_counts(HLO))
    cs.require_kernels(rec, ("dispatch_pack", "grouped_gemm"))
    with pytest.raises(cs.SmokeFailure, match="recv_unpack"):
        cs.require_kernels(rec, ("dispatch_pack", "recv_unpack"))


@pytest.mark.parametrize("mode,tokens,fp8", [("ll", 8, False),
                                             ("ht", 32, True)])
def test_ep_phase(oracles, mode, tokens, fp8):
    router = dataclasses.replace(cs.deepseek_router(), num_experts=16, top_k=4)
    rec = cs.ep_phase(cs.ep_mesh(jax.devices()[:1]), router, mode=mode,
                      tokens=tokens, hidden=256, quantize=fp8)
    assert rec["routed"] == tokens * 4
    assert rec["rel_err"] <= rec["tol"]


def test_ep_phase_catches_a_wrong_combine(oracles, monkeypatch):
    router = dataclasses.replace(cs.deepseek_router(), num_experts=16, top_k=4)
    real = cs.ep_combine
    monkeypatch.setattr(cs, "ep_combine", lambda g, h, y: real(g, h, y) * 2)
    with pytest.raises(cs.SmokeFailure, match="relative error"):
        cs.ep_phase(cs.ep_mesh(jax.devices()[:1]), router, mode="ll",
                    tokens=8, hidden=256, quantize=False)


def test_serve_phase_one_chip_interpret_kernel(monkeypatch):
    # head_dim 128 and page 8 pass the paged-decode gate, so the Pallas
    # kernel body runs (interpreted) inside the real serve step
    monkeypatch.setenv("REPRO_FORCE_PALLAS", "interpret")
    cfg = dataclasses.replace(_tiny_dbrx(),
                              attn=AttnSpec(n_heads=2, n_kv=1, head_dim=128))
    rec = cs.serve_phase(cfg, None, _requests(cfg), page_size=8)
    assert rec["completed"] == 4 and rec["pages_live"] == 0
    assert rec["tokens"] == 16


@pytest.mark.parametrize("mode,tokens,fp8", [("ll", 8, False),
                                             ("ht", 32, True)])
def test_moe_phase_four_devices(oracles, mode, tokens, fp8):
    cfg = dataclasses.replace(_tiny_dbrx(), d_model=256)
    rec = cs.moe_phase(cfg, cs.ep_mesh(jax.devices()[:4]), mode=mode,
                       tokens=tokens, quantize=fp8, ref_tokens=8)
    assert rec["experts_split"] and rec["rel_err"] <= rec["tol"]


def test_serve_phase_four_devices(oracles):
    cfg = _tiny_dbrx(layers=2)
    rec = cs.serve_phase(cfg, cs.ep_mesh(jax.devices()[:4]), _requests(cfg),
                         page_size=4)
    assert rec["ranks"] == 4 and rec["experts_split"]
    assert rec["completed"] == 4 and rec["pages_live"] == 0


def test_server_expert_leaves_split_over_data():
    """The server builds expert weights sharded over the EP axis, each
    device holding its own experts — not replicated on every device."""
    cfg = _tiny_dbrx(layers=2)
    mesh = cs.ep_mesh(jax.devices()[:4])
    srv = DecodeServer(cfg, batch=4, max_len=8, mesh=mesh)
    w = srv.params["moe_stack"]["moe"]["w_gate"]        # [layers, E, D, F]
    srv.close()
    assert w.sharding.spec[1] in ("data", ("data",))
    assert {s.data.shape[1] for s in w.addressable_shards} == {8 // 4}
    assert cs.expert_shards_split(srv.params, 4)
    router = srv.params["moe_stack"]["moe"]["router"]
    assert not cs.expert_shards_split({"router": router}, 4)


def test_compile_cache_dir(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert compile_cache_dir() == "/elsewhere/cache"
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert compile_cache_dir() == str(CHECKOUT_CACHE)
    assert CHECKOUT_CACHE.parent == ROOT
    ignored = (ROOT / ".gitignore").read_text().split()
    assert f"{CHECKOUT_CACHE.name}/" in ignored


def test_seeded_requests_are_reproducible():
    a = cs.seeded_requests(1000, count=8, prompt_lens=(32, 64), new_tokens=16)
    b = cs.seeded_requests(1000, count=8, prompt_lens=(32, 64), new_tokens=16)
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert all(32 <= r.prompt.size <= 64 for r in a)
