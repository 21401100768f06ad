"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read.

A device plane (``/device:TPU:<n>``) has a line of program executions
(``XLA Modules``) and a line of operations (``XLA Ops``); the host plane
(``/host:CPU``) has the benchmark's annotations (``bench.*``) on the
thread that ran the loop.  The traced window runs from the start of the
first ``bench.step_chunk`` annotation to the end of the last one.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

MODULES, OPS = "XLA Modules", "XLA Ops"
STEP = "bench.step_chunk"
_SUFFIX = re.compile(r"\(\d+\)$")


@dataclasses.dataclass
class Trace:
    # per device plane: {line name: [(name, start_ns, dur_ns)]}
    devices: Dict[str, Dict[str, List[Tuple[str, float, float]]]]
    host: List[Tuple[str, float, float]]      # bench.* annotations


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            lines = {}
            for line in plane.lines:
                if line.name in (MODULES, OPS):
                    lines[line.name] = [(e.name, e.start_ns, e.duration_ns)
                                        for e in line.events]
            if lines:
                devices[plane.name] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.duration_ns)
                            for e in line.events
                            if e.name.startswith("bench."))
    return Trace(devices, host)


def program_name(name: str) -> str:
    """``jit_chunk_fn(123)`` -> ``chunk_fn``: the jitted function's name."""
    name = _SUFFIX.sub("", name)
    return name[4:] if name.startswith("jit_") else name


def _union(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                      # mean over the device planes
    # name -> (runs, device seconds), each the mean over the device planes
    programs: Dict[str, Tuple[int, float]]
    gaps: List[Tuple[str, float]]      # longest idle gaps, by host activity
    n_devices: int


def summarise(tr: Trace, n_gaps: int = 10) -> Optional[Summary]:
    """None when the trace holds no boundary or no device plane."""
    steps = [(s, s + d) for n, s, d in tr.host if n == STEP]
    if not steps or not tr.devices:
        return None
    w0, w1 = min(a for a, _ in steps), max(b for _, b in steps)
    busy_total, programs, gaps = 0.0, {}, []
    for lines in tr.devices.values():
        ops = lines.get(OPS) or lines.get(MODULES) or []
        busy = _union((max(s, w0), min(s + d, w1)) for _, s, d in ops
                      if s < w1 and s + d > w0)
        busy_total += sum(b - a for a, b in busy)
        for name, s, d in lines.get(MODULES, []):
            if w0 <= s < w1:
                n, t = programs.get(program_name(name), (0, 0.0))
                programs[program_name(name)] = (n + 1, t + d * 1e-9)
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        gaps.extend((a, b) for a, b in zip(edges[::2], edges[1::2])
                    if b > a)
    host = sorted(tr.host, key=lambda e: e[2])      # innermost first

    def doing(t):
        for n, s, d in host:
            if s <= t < s + d:
                return n
        return "host.other"

    gaps.sort(key=lambda g: g[0] - g[1])
    named = [(doing((a + b) / 2), (b - a) * 1e-9) for a, b in gaps[:n_gaps]]
    k = len(tr.devices)
    programs = {n: (c // k, t / k) for n, (c, t) in programs.items()}
    return Summary((w1 - w0) * 1e-9, busy_total * 1e-9 / k, programs, named,
                   k)


def breakdown(s: Summary) -> dict:
    top = sorted(s.programs.items(), key=lambda kv: -kv[1][1])[:10]
    return {"device_ops": [[n, t] for n, (_, t) in top],
            "idle_gaps": [[n, t] for n, t in s.gaps]}
