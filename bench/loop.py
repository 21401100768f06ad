"""The measured window: the benchmark's own client loop around the engine.

The loop submits each request once its due time has passed, calls the
engine's chunk boundary until the window ends, and after every boundary
stamps how many tokens each request in flight has.  Every request is timed
on the client side from its due time, so a stall also delays the requests
queued behind it.  Its own calls are wrapped in profiler annotations
(``bench.submit``, ``bench.step_chunk``, ``bench.wait_for_arrival``,
``bench.record``) so that a traced run can say what the host was doing
while the device sat idle.

``engine`` is any object with ``submit(prompt, max_new) -> rid``,
``step() -> finished rids``, ``count(rid)``, ``state(rid)`` and
``busy_share()``: :class:`bench.system.Engine` on the chip, a fake in tests.
"""
from __future__ import annotations

import dataclasses
import heapq
import itertools
import time
from typing import Callable, Dict, List, Optional

import numpy as np
from jax.profiler import TraceAnnotation


@dataclasses.dataclass
class Flight:
    req: object
    due: float
    submitted: float
    rid: int
    stamps: List[tuple] = dataclasses.field(default_factory=list)  # (t, n)
    done: Optional[float] = None


@dataclasses.dataclass
class Boundary:
    t0: float
    t1: float
    prefills: List[int]              # prompt lengths prefilled here
    # per chunk step, the positions each slot owed a token attends: token
    # j of a prompt of p is made from position p + j - 1 over p + j keys
    decode: List[List[int]]


@dataclasses.dataclass
class Window:
    start: float
    end: float
    flights: List[Flight]
    boundaries: List[Boundary]
    occupancy: List[float]           # busy share after each boundary
    lateness: List[float]            # submit - due, per request due in window
    trace_span: Optional[tuple] = None   # (first, last) traced boundary idx


def drive(engine, traffic, *, warmup_s: float, seconds: float,
          clock: Callable[[], float] = time.perf_counter,
          sleep: Callable[[float], None] = time.sleep,
          trace_from: Optional[float] = None,
          on_trace: Optional[Callable[[bool], None]] = None) -> Window:
    """Run ``traffic`` through ``warmup_s`` seconds and then a window of
    ``seconds``; the window closes at the first boundary that ends at or
    after its target.  With ``trace_from`` (seconds before the window's
    target end), ``on_trace(True)`` is called at the first boundary past
    that point and ``on_trace(False)`` after the last one."""
    t_start = clock()
    w0 = t_start + warmup_s
    target = w0 + seconds
    order = itertools.count()
    due: list = [(t_start + off, next(order), r) for off, r in
                 traffic.start()]
    heapq.heapify(due)
    live: Dict[int, Flight] = {}
    flights: List[Flight] = []
    bounds: List[Boundary] = []
    occ: List[float] = []
    tracing, trace_span = False, None
    while True:
        now = clock()
        with TraceAnnotation("bench.submit"):
            while due and due[0][0] <= now:
                t_due, _, r = heapq.heappop(due)
                rid = engine.submit(r.prompt, r.max_new)
                f = Flight(r, t_due, clock(), rid)
                live[rid] = f
                flights.append(f)
        if not live:
            nxt = min(due[0][0] if due else target, target)
            if now >= target:
                break
            with TraceAnnotation("bench.wait_for_arrival"):
                sleep(max(nxt - now, 0.0))
            continue
        if (trace_from is not None and not tracing and trace_span is None
                and now >= target - trace_from):
            on_trace(True)
            tracing, trace_span = True, (len(bounds), None)
        before = {rid: (f.stamps[-1][1] if f.stamps else 0)
                  for rid, f in live.items()}
        t0 = clock()
        with TraceAnnotation("bench.step_chunk"):
            finished = engine.step()
        t1 = clock()
        with TraceAnnotation("bench.record"):
            prefills, decoded = [], []
            for rid, f in live.items():
                n = engine.count(rid)
                if n != before[rid]:
                    f.stamps.append((t1, n))
                    plen = int(f.req.prompt.shape[0])
                    a = before[rid]
                    if a == 0:
                        prefills.append(plen)
                        a = 1
                    decoded.append((plen, a, n))
            steps = [[p + a + k for p, a, n in decoded if a + k < n]
                     for k in range(engine.chunk)]
            bounds.append(Boundary(t0, t1, prefills,
                                   [s for s in steps if s]))
            if t1 > w0:
                occ.append(engine.busy_share())
            for rid in finished:
                f = live.pop(rid, None)
                if f is None:
                    continue
                f.done = t1
                for t_due, r in traffic.completed(f.req, t1):
                    heapq.heappush(due, (t_due, next(order), r))
        if t1 >= target:
            break
    if tracing:
        on_trace(False)
        trace_span = (trace_span[0], len(bounds) - 1)
    end = bounds[-1].t1 if bounds and bounds[-1].t1 >= target else target
    late = [f.submitted - f.due for f in flights if w0 <= f.due < end]
    return Window(w0, end, flights, bounds, occ, late, trace_span)


def _p95(values) -> Optional[float]:
    return float(np.percentile(values, 95)) if len(values) else None


def end_to_end(win: Window) -> dict:
    """The end-to-end numbers of a window (seconds, tokens, counts)."""
    w0, w1 = win.start, win.end
    ttft, tpot, e2e = [], [], []
    tokens = 0
    for f in win.flights:
        first = f.stamps[0][0] if f.stamps else None
        if w0 <= f.due < w1:
            ttft.append((first if first is not None and first <= w1
                         else w1) - f.due)
            if f.done is not None and f.done <= w1:
                e2e.append(f.done - f.due)
        inside = [(t, n) for t, n in f.stamps if w0 < t <= w1]
        prior = [n for t, n in f.stamps if t <= w0]
        if inside:
            tokens += inside[-1][1] - (prior[-1] if prior else 0)
        if len(inside) >= 2 and inside[-1][1] > inside[0][1]:
            tpot.append((inside[-1][0] - inside[0][0])
                        / (inside[-1][1] - inside[0][1]))
    return {
        "window_s": w1 - w0,
        "tokens": tokens,
        "output_tok_s": tokens / (w1 - w0),
        "ttft_p95_s": _p95(ttft), "ttft_p50_s": (
            float(np.percentile(ttft, 50)) if ttft else None),
        "ttft_n": len(ttft),
        "tpot_p95_s": _p95(tpot), "tpot_n": len(tpot),
        "e2e_p95_s": _p95(e2e), "completed": len(e2e),
        "lateness_p95_s": _p95(win.lateness),
        "lateness_max_s": max(win.lateness) if win.lateness else None,
    }
