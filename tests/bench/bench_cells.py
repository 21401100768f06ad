"""The cells of ``BENCHMARK.json`` for the benchmark's CPU tests, and each
at the smoke widths of its architecture (``bench/archs/``): the same code
paths and checks, in float32, at a size the CPU can run."""
import copy

from bench import spec

CELLS = [w["name"] for w in spec.benchmark()["workloads"]]


def smoke_cell(name: str, dtype: str = "float32") -> dict:
    """The cell ``name`` at smoke widths, a 256-position engine and short
    traffic: same code paths, same check, a size the CPU can run."""
    c = copy.deepcopy(spec.cell(name))
    cfg = c["config"]
    cfg.update(spec.arch(cfg).SMOKE, torch_dtype=dtype)
    cfg["engine"].update(max_seq=256, slots=4, kv_blocks=4 * 16)
    t = c["traffic"]
    t["prompt"].update(min=16, max=120, median=40)
    t["output"].update(min=8, max=64)
    if t["output"]["dist"] == "lognormal":
        t["output"]["median"] = 24
    t["warmup_s"] = 0.3
    if t["kind"] == "closed_loop":
        t["clients"] = 6
    else:
        t["rate"] = 6.0
    c["check"].update(sample=3)
    return c


CPU_PEAKS = {"flops": 1e12, "hbm_bytes_per_s": 1e11}
