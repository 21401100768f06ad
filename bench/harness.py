"""One run of one cell: set-up, the measured window, the trace reduction,
then the comparison with the reference.  ``bench/run.py`` is the command;
``bench/sweep.py`` and ``bench/calibrate.py`` reuse these pieces."""
from __future__ import annotations

import dataclasses
import gc
import glob
import os
import shutil
import sys
import tempfile
import time
from typing import Callable, List, Optional

import jax

from bench import check, loop, spec, traffic
from bench import trace as tr

TRACE_S = 10.0          # the traced run traces the window's last seconds


def log(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


class CompileCount:
    """Programs lowered (compiled, or fetched from the persistent cache)
    since start, from JAX's monitoring events."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax.monitoring
        self.n = 0
        self.on = True
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, *args, **kwargs):
        if self.on and event == self.EVENT:
            self.n += 1


def enable_cache(root: str) -> str:
    """The program's persistent compile cache at its fixed place in the
    checkout, keeping every program however quick to compile, so that only
    a cell's first run in a checkout compiles."""
    sys.path.insert(0, os.path.join(root, "src"))
    from repro.launch import compile_cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return compile_cache.enable(root)


def trace_options():
    """Device ops and host annotations, without Python call tracing (which
    would slow the loop and swell the trace)."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


@dataclasses.dataclass
class Ctx:
    """What a per-layer metric reader sees."""
    cfg: dict
    peaks: dict
    window: loop.Window
    trace: Optional[tr.Summary]
    traced: List[loop.Boundary]
    notes: List[str] = dataclasses.field(default_factory=list)

    def note(self, msg: str) -> None:
        self.notes.append(msg)


def device_info() -> dict:
    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


def peak_bytes() -> Optional[int]:
    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    peaks = [s.get("peak_bytes_in_use") for s in stats]
    return max(p for p in peaks if p is not None) if any(
        p is not None for p in peaks) else None


def run(c: dict, seed: int, seconds: float, trace: bool, *,
        t_process: float, make_engine: Optional[Callable] = None,
        peaks: Optional[dict] = None, control: bool = False) -> dict:
    """One run of the cell ``c`` (:func:`bench.spec.cell`); returns the
    result line's fields plus what is printed beside them.
    ``make_engine(cfg, seed)`` replaces the chip engine (the CPU tests pass
    a wrapped one); ``control`` also reads the fp8 control's numbers."""
    from bench.peaks import peaks as peak_table
    cfg, tspec, cspec = c["config"], c["traffic"], c["check"]
    if make_engine is None:
        from bench.system import Engine as make_engine
    eng = make_engine(cfg, seed)
    lengths = traffic.prompt_lengths(tspec, seconds)
    jit = eng.warm(lengths)
    log(f"warm: {jit}, {len(lengths)} prompt lengths")
    gen = traffic.make(tspec, seed, cfg["vocab_size"], seconds)
    compiles = CompileCount()
    tdir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None

    def on_trace(start: bool):
        if start:
            jax.profiler.start_trace(tdir, profiler_options=trace_options())
        else:
            jax.profiler.stop_trace()

    win = loop.drive(eng, gen, warmup_s=tspec.get("warmup_s", 0),
                     seconds=seconds,
                     trace_from=min(TRACE_S, seconds) if trace else None,
                     on_trace=on_trace if trace else None)
    compiles.on = False
    e2e = loop.end_to_end(win)
    setup_s = win.start - t_process
    jit_after = eng.jit_counts()
    attempted = [f for f in win.flights if win.start <= f.due < win.end]
    failed = sum(1 for f in attempted
                 if eng.state(f.rid) not in (None, "ok"))
    finished = [(f.req.prompt, eng.tokens(f.rid)) for f in win.flights
                if eng.state(f.rid) == "ok"]
    device = device_info()
    device["memory_peak_bytes"] = peak_bytes()
    eng.free()
    del eng
    gc.collect()

    summary = traced = None
    if trace:
        files = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                          recursive=True)
        summary = tr.summarise(tr.load(files[0])) if files else None
        shutil.rmtree(tdir, ignore_errors=True)
        a, b = win.trace_span
        traced = win.boundaries[a:b + 1]
        if summary is not None:
            device["busy_s"] = summary.busy_s
            device["window_s"] = summary.window_s

    t_check = time.perf_counter()
    pairs = check.sample(finished, cspec["sample"], seed)
    numbers = check.compare(cfg, cspec["sample"], tspec["output"]["max"],
                            seed, pairs, control=control)
    ok, lines = check.verdict(numbers, cspec)
    correct = ok and failed == 0
    check_s = time.perf_counter() - t_check

    info = {
        "setup_s": setup_s, "window_s": e2e["window_s"],
        "compiles_in_window": compiles.n,
        "recompiles_after_warm": jit_after["recompiles_after_warm"],
        "jit": jit_after, "boundaries": len(win.boundaries),
        "due_in_window": len(attempted), "ttft_n": e2e["ttft_n"],
        "tpot_n": e2e["tpot_n"], "completed_in_window": e2e["completed"],
        "tokens_in_window": e2e["tokens"],
        "ttft_p50_ms": _ms(e2e["ttft_p50_s"]),
        "e2e_p95_s": e2e["e2e_p95_s"],
        "lateness_p95_ms": _ms(e2e["lateness_p95_s"]),
        "lateness_max_ms": _ms(e2e["lateness_max_s"]),
        "check_s": check_s, "check_tokens": numbers.get("tokens"),
        "check_argmax_share": numbers.get("argmax_share"),
        "finished": len(finished),
        "slowest_boundaries": _slowest(win),
    }
    values = {"ttft_p95_ms": _ms(e2e["ttft_p95_s"]),
              "tpot_p95_ms": _ms(e2e["tpot_p95_s"]),
              "output_tok_s": e2e["output_tok_s"], "setup_s": setup_s}
    result = {"correct": correct, "attempted": len(attempted),
              "failed": failed, "metrics": {}, "device": device}
    notes = []
    if trace:
        ctx = Ctx(cfg, (peaks or peak_table(device["kind"])), win, summary,
                  traced or [])
        for m in c["per_layer"]:
            v = spec.metric_reader(m["name"])(ctx)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v,
                                                "unit": m["unit"]}
        notes = ctx.notes
        if summary is not None:
            result["breakdown"] = tr.breakdown(summary)
    else:
        for m in c["end_to_end"]:
            v = values.get(m["name"])
            if v is not None:
                result["metrics"][m["name"]] = {"value": v,
                                                "unit": m["unit"]}
    result["check"] = {n: {"value": v, "limit": lim}
                       for n, (v, lim) in lines.items()}
    result["check"]["failed_requests"] = {"value": failed, "limit": 0}
    return {"result": result, "info": info, "values": values,
            "notes": notes, "numbers": numbers}


def _slowest(win, n: int = 3) -> list:
    """The window's slowest boundaries: (ms, prompt lengths prefilled,
    decode steps run)."""
    inside = [b for b in win.boundaries if b.t1 > win.start]
    inside.sort(key=lambda b: b.t0 - b.t1)
    return [(round((b.t1 - b.t0) * 1e3, 1), b.prefills, len(b.decode))
            for b in inside[:n]]


def _ms(s):
    return None if s is None else s * 1e3
