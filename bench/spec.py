"""What ``BENCHMARK.json`` names, found by name in files of their own:
``bench/configs/<config>.json``, ``bench/traffic/<traffic>.json``,
``bench/checks/<cell>.json`` and ``bench/metrics/<metric>.py``; and the
module ``bench/archs/<architecture>.py`` of each configuration's
``architecture``."""
from __future__ import annotations

import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)

# the program's jitted decode-chunk and prefill functions, by the names
# they have in a device trace
DECODE_PROGRAM = "chunk_fn"
PREFILL_PROGRAM = "fn"


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

    config = _json(root, cfg_entry["file"])
    arch(config)        # an architecture with no module fails here
    return {
        "workload": w,
        "config": config,
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


def arch(cfg: dict):
    """The module of the configuration's ``architecture``
    (``bench/archs/<architecture>.py``); what it provides is listed in
    ``bench/archs/__init__.py``."""
    name = cfg.get("architecture")
    if not (isinstance(name, str) and name.isidentifier() and os.path.isfile(
            os.path.join(BENCH, "archs", name + ".py"))):
        raise LookupError(
            f"configuration {cfg.get('name')!r} names architecture "
            f"{name!r}, which has no module: add bench/archs/{name}.py "
            f"(bench/archs/__init__.py lists what it provides)")
    return importlib.import_module("bench.archs." + name)
