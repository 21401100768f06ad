"""The command and ``BENCHMARK.json``: it refuses to run without a TPU or
outside a full checkout, and every name in the file is found in a file of
its own."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench import spec

ROOT = spec.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def _env():
    env = {k: v for k, v in os.environ.items()
           if k in ("PATH", "HOME", "TMPDIR", "LANG")}
    env["JAX_PLATFORMS"] = "cpu"
    return env


def _run(cwd):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen3-4b.decode",
         "--seed", str(2**31 + 5), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=_env(), capture_output=True, text=True, timeout=300)


def test_run_refuses_without_a_tpu():
    r = _run(ROOT)
    assert r.returncode == 2, r.stderr
    assert r.stdout.strip() == "" or not r.stdout.strip().splitlines()[
        -1].startswith("{")
    assert "TPU" in r.stderr


def test_run_refuses_with_only_the_benchmark_files(tmp_path):
    b = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in b["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(tmp_path)
    assert r.returncode != 0
    assert not r.stdout.strip()


def test_benchmark_json_follows_its_contract():
    b = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["command"] == ["python3", "bench/run.py"]
    assert 1 <= b["run_seconds"] <= 51
    assert all(os.path.isdir(os.path.join(ROOT, p)) for p in b["paths"])
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in b[k]]
    assert len(names) == len(set(names)) and all(map(NAME.match, names))
    cfgs = {c["name"]: c for c in b["configs"]}
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        f = json.load(open(os.path.join(ROOT, c["file"])))
        assert f["name"] == c["name"] and f["reduced"] == c["reduced"]
        assert not any(k.endswith(("_dim", "_rank", "_size"))
                       for k in c["reduced"])
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in cfgs and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
        spec.cell(w["name"])          # traffic and check files are found
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e
    for m in b["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25 and UNIT.match(m["unit"])
    for m in b["per_layer"]:
        assert m["moves"] in e2e and UNIT.match(m["unit"])
        assert callable(spec.metric_reader(m["name"]))
        for w in m.get("workloads", []):
            assert w in {x["name"] for x in b["workloads"]}
            assert w in e2e[m["moves"]].get("workloads", [w])
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    for w in b["workloads"]:
        c = spec.cell(w["name"])
        assert {m["name"] for m in c["end_to_end"]} >= {"setup_s"}
        assert len(c["end_to_end"]) >= 2 and c["per_layer"]


@pytest.mark.parametrize("name", ["qwen3-4b.decode", "yi-9b-l24.rag"])
def test_each_cell_fits_its_engine(name):
    c = spec.cell(name)
    e, t = c["config"]["engine"], c["traffic"]
    assert t["prompt"]["max"] + t["output"]["max"] <= e["max_seq"]
    assert e["max_seq"] % e["block_size"] == 0
    assert e["kv_blocks"] * e["block_size"] >= t["prompt"]["max"] + t[
        "output"]["max"]
    assert e["compile_analysis_bytes"] < 0.87 * 16e9   # bring-up margin
