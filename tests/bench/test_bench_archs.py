"""An architecture is a module of ``bench/archs/`` found by the name its
configurations give: a new one arrives as new files and entries in
``BENCHMARK.json``, with no file of the benchmark edited."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import spec

ROOT = spec.ROOT

TOY_ARCH = '''"""A toy architecture: the dense decoder under a name of its own."""
from bench.archs.LlamaForCausalLM import *  # noqa: F401,F403
'''

TOY_TRAFFIC = {"name": "toy", "kind": "poisson", "rate": 2.0,
               "prompt": {"dist": "lognormal", "median": 256, "sigma": 0.5,
                          "min": 32, "max": 1024},
               "output": {"dist": "uniform", "min": 16, "max": 128},
               "warmup_s": 2}

# one whole run of the new cell at smoke widths on the CPU, in a checkout
# that holds only the benchmark's files and the four new ones
RUN = """
import json, sys, time
sys.path[:0] = [".", "tests/bench"]
from bench import harness, spec, system
from bench_cells import CPU_PEAKS, smoke_cell
try:
    c = smoke_cell("toy-2l.toy")
except LookupError as e:
    print(json.dumps({"error": str(e)}))
    sys.exit(0)
out = harness.run(c, 2**31 + 23, 2.0, False, t_process=time.perf_counter(),
                  make_engine=system.Engine, peaks=CPU_PEAKS)
print(json.dumps({"arch": spec.arch(c["config"]).__name__,
                  "numbers": out["numbers"], **out["result"]}))
"""


def _checkout_with_a_new_architecture(dest, with_module: bool):
    """The benchmark's files copied to ``dest``, plus a toy architecture's
    module (unless not ``with_module``), a configuration naming it, a
    traffic mix and a check, and their entries in ``BENCHMARK.json``."""
    b = spec.benchmark()
    for p in b["paths"]:
        shutil.copytree(os.path.join(ROOT, p), dest / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    if with_module:
        (dest / "bench" / "archs" / "ToyForCausalLM.py").write_text(TOY_ARCH)
    cfg = dict(spec.cell("qwen3-4b.decode")["config"], name="toy-2l",
               architecture="ToyForCausalLM", num_hidden_layers=2,
               reduced=["num_hidden_layers"])
    (dest / "bench" / "configs" / "toy-2l.json").write_text(json.dumps(cfg))
    (dest / "bench" / "traffic" / "toy.json").write_text(
        json.dumps(TOY_TRAFFIC))
    (dest / "bench" / "checks" / "toy-2l.toy.json").write_text(json.dumps(
        {"sample": 3, "limits": {"max_gap": {"limit": 0.4}}}))
    b["configs"].append({"name": "toy-2l", "source": "a test",
                         "file": "bench/configs/toy-2l.json",
                         "reduced": ["num_hidden_layers"], "why": "a test"})
    b["workloads"].append({"name": "toy-2l.toy", "config": "toy-2l",
                           "traffic": "toy", "chips": 1, "why": "a test"})
    (dest / "BENCHMARK.json").write_text(json.dumps(b))


@pytest.mark.parametrize("with_module", [True, False],
                         ids=["with_module", "without_module"])
def test_a_new_architecture_needs_only_new_files(tmp_path, with_module):
    _checkout_with_a_new_architecture(tmp_path, with_module)
    env = {k: v for k, v in os.environ.items()
           if k in ("PATH", "HOME", "TMPDIR", "LANG")}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=os.path.join(ROOT, "src"))
    r = subprocess.run([sys.executable, "-c", RUN], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    if with_module:
        assert out["arch"] == "bench.archs.ToyForCausalLM"
        assert out["correct"], out
        assert out["numbers"]["tokens"] > 0
    else:
        assert "bench/archs/ToyForCausalLM.py" in out["error"]


def test_an_unknown_architecture_names_the_file_to_add():
    with pytest.raises(LookupError, match=r"bench/archs/MambaForX\.py"):
        spec.arch({"name": "m", "architecture": "MambaForX"})
    with pytest.raises(LookupError, match="bench/archs/None.py"):
        spec.arch({"name": "m"})


def test_both_dense_names_share_one_implementation():
    llama = spec.arch({"architecture": "LlamaForCausalLM"})
    qwen3 = spec.arch({"architecture": "Qwen3ForCausalLM"})
    assert qwen3 is not llama
    for name in llama.__all__:
        assert getattr(qwen3, name) is getattr(llama, name), name
