"""Loading every cell by name, the format of BENCHMARK.json, and the
refusal off a TPU."""
import json
import os
import re
import subprocess
import sys

import pytest

import run as RUN

BENCH_JSON = json.loads((RUN.ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


@pytest.mark.parametrize("w", [w["name"] for w in BENCH_JSON["workloads"]])
def test_every_workload_resolves(w):
    spec = RUN.resolve(BENCH_JSON, w)
    assert spec["driver"].is_file()
    drv = RUN.load_module(spec["driver"])
    assert callable(drv.run)
    names = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in names and len(names) >= 2
    assert spec["per_layer"]
    for m in spec["per_layer"]:
        reader = RUN.load_module(RUN.BENCH / "metrics" / f"{m['name']}.py")
        assert callable(reader.read)
        assert m["moves"] in names


@pytest.mark.parametrize("field,value,msg", [
    ("config", "no-such-config", "unknown config"),
    ("traffic", "no-such-traffic", "no such file"),
])
def test_unknown_names_fail_loudly(field, value, msg):
    bench = json.loads(json.dumps(BENCH_JSON))
    bench["workloads"][0][field] = value
    with pytest.raises(RUN.SetupError, match=msg):
        RUN.resolve(bench, bench["workloads"][0]["name"])


def test_unknown_workload_and_driver_fail_loudly(tmp_path, monkeypatch):
    with pytest.raises(RUN.SetupError, match="unknown workload"):
        RUN.resolve(BENCH_JSON, "no-such-workload")
    bench = json.loads(json.dumps(BENCH_JSON))
    traffic = json.loads((RUN.BENCH / "traffic" / "chat.json").read_text())
    traffic["driver"] = "no_such_driver"
    (tmp_path / "traffic").mkdir()
    (tmp_path / "traffic" / "chat.json").write_text(json.dumps(traffic))
    monkeypatch.setattr(RUN, "BENCH", tmp_path)
    with pytest.raises(RUN.SetupError, match="unknown driver"):
        RUN.resolve(bench, "dbrx-chat")


def test_benchmark_json_format():
    b = BENCH_JSON
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= b["run_seconds"] <= 51
    cells = {w["name"] for w in b["workloads"]}
    configs = {c["name"] for c in b["configs"]}
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert (RUN.ROOT / c["file"]).is_file()
        assert c["file"].startswith(tuple(p + "/" for p in b["paths"]))
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
    assert sum(w["chips"] == 4 for w in b["workloads"]) <= max(
        1, len(b["workloads"]) // 2)
    names = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(names) == len(set(names))
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in b["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert set(m.get("workloads", cells)) <= cells
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    for n in names + list(cells) + list(configs):
        assert NAME.match(n), n
    for c in b["configs"]:
        for k in c["reduced"]:
            assert NAME.match(k)


def test_catalog_config_keeps_every_published_number():
    conf = json.loads((RUN.BENCH / "configs" / "deepseek-v3-ep.json").read_text())
    entry = next(c for c in BENCH_JSON["configs"] if c["name"] == "deepseek-v3-ep")
    published = conf["published"]
    assert set(published) == set(entry["reduced"])
    assert conf["hidden_size"] == 7168 and conf["n_routed_experts"] == 256
    assert conf["num_experts_per_tok"] == 8


def test_refuses_off_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(RUN.BENCH / "run.py"),
                        "--workload", "dbrx-chat", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_refuses_without_the_program(tmp_path):
    """A directory holding only BENCHMARK.json and the benchmark's files
    gives no result."""
    import shutil
    shutil.copy(RUN.ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(RUN.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "_out"))
    p = subprocess.run([sys.executable, str(tmp_path / "bench" / "run.py"),
                        "--workload", "dsv3-ep-ht", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, timeout=300,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0 and p.stdout.strip() == ""
