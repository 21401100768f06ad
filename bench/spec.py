"""What ``BENCHMARK.json`` names, found by name in files of their own:
``bench/configs/<config>.json``, ``bench/traffic/<traffic>.json``,
``bench/checks/<cell>.json`` and ``bench/metrics/<metric>.py``."""
from __future__ import annotations

import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    return _json(root, "BENCHMARK.json")


def cell(name: str, root: str = ROOT) -> dict:
    """Everything one cell needs: its entry, configuration, traffic, check
    and the metric entries that apply to it."""
    b = benchmark(root)
    cells = {w["name"]: w for w in b["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(cells)}")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in b["configs"]}[w["config"]]

    def applies(m):
        return name in m.get("workloads", [name])

    return {
        "workload": w,
        "config": _json(root, cfg_entry["file"]),
        "traffic": _json(BENCH, "traffic", w["traffic"] + ".json"),
        "check": _json(BENCH, "checks", name + ".json"),
        "end_to_end": [m for m in b["end_to_end"] if applies(m)],
        "per_layer": [m for m in b["per_layer"] if applies(m)],
        "run_seconds": b["run_seconds"],
    }


def metric_reader(name: str):
    """The ``read(ctx)`` function of ``bench/metrics/<name>.py``."""
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
