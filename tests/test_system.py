"""End-to-end behaviour tests for the paper's system: sharded lowering on a
multi-device mesh (subprocess) and the dry-run machinery itself."""
import json
import os
import subprocess
import sys

import pytest

ENV = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "HOME": "/root",
       "JAX_PLATFORMS": "cpu"}  # host-platform test: skip TPU probing

SHARDED_LOWER = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
import jax
from repro.launch import dryrun
from repro.launch.mesh import make_mesh

def mini_mesh(multi_pod):
    if multi_pod:
        return make_mesh((2, 2, 4), ("pod", "data", "model"),
                         jax.devices()[:16])
    return make_mesh((4, 4), ("data", "model"), jax.devices()[:16])

dryrun._mesh = mini_mesh
rec = dryrun.lower_cell("stablelm_1_6b", "train_4k", False)
assert rec["status"] == "ok", rec
r = rec["roofline"]
assert r["flops"] > 1e15, r                 # scan-aware count (24 layers)
assert r["coll_bytes"] > 0, r               # TP/DP collectives present
rec2 = dryrun.lower_cell("stablelm_1_6b", "train_4k", True)
assert rec2["status"] == "ok", rec2         # the pod axis shards
print("SYSTEM_OK")
"""


@pytest.mark.slow
def test_sharded_lowering_subprocess():
    r = subprocess.run([sys.executable, "-c", SHARDED_LOWER],
                       capture_output=True, text=True, timeout=900, env=ENV)
    assert "SYSTEM_OK" in r.stdout, r.stdout[-2000:] + r.stderr[-3000:]


def test_dryrun_results_if_present():
    """Validate the committed dry-run results: every non-skipped cell ok."""
    path = "experiments/dryrun.json"
    if not os.path.exists(path):
        pytest.skip("dry-run results not generated yet")
    with open(path) as f:
        results = json.load(f)
    bad = {k: v.get("error", "") for k, v in results.items()
           if v.get("status") == "error"}
    assert not bad, bad
    ok = [k for k, v in results.items() if v.get("status") == "ok"]
    assert len(ok) >= 30, f"only {len(ok)} cells compiled"


def test_examples_quickstart():
    r = subprocess.run([sys.executable, "examples/quickstart.py"],
                       capture_output=True, text=True, timeout=600, env=ENV)
    assert "== oracle OK" in r.stdout, r.stdout[-1500:] + r.stderr[-1500:]


CACHE_PROBE = r"""
import os, sys
import jax, jax.numpy as jnp
from repro.launch import compile_cache
print("DIR", compile_cache.enable(root=sys.argv[1]))
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.jit(lambda x: jnp.sin(x) * 3)(jnp.ones(8)).block_until_ready()
"""


@pytest.mark.parametrize("from_env", [True, False], ids=["env", "checkout"])
def test_compile_cache_placement(tmp_path, from_env):
    """JAX_COMPILATION_CACHE_DIR wins when set; otherwise the cache goes to
    <root>/.jax_cache (the checkout by default), and nowhere else."""
    env_dir, root = tmp_path / "from-env", tmp_path / "root"
    env = dict(ENV)
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    r = subprocess.run([sys.executable, "-c", CACHE_PROBE, str(root)],
                       capture_output=True, text=True, timeout=300, env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    want, other = ((env_dir, root / ".jax_cache") if from_env
                   else (root / ".jax_cache", env_dir))
    assert f"DIR {want}" in r.stdout
    assert want.is_dir() and any(want.iterdir())
    assert not other.exists()


def test_compile_cache_default_is_the_checkout():
    from repro.launch import compile_cache
    assert os.path.isfile(os.path.join(compile_cache.CHECKOUT,
                                       "chip_smoke.py"))
