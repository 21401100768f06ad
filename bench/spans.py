"""The program's own spans in the traced window, for the engine layer's
metrics.

``repro.obs`` records its spans on the ``time.perf_counter_ns`` clock
whenever a profiler session is collecting, which the traced run's window
is; the loop stamps its boundaries with ``time.perf_counter``, the same
clock.  A program that records no spans, or has no ``obs.spans``, gives
an empty list, and each reader then returns None.
"""
from __future__ import annotations

from typing import List

from repro import obs


def window(ctx) -> list:
    """The spans and instants that end within the traced boundaries,
    ``ctx.traced[0].t0`` to ``ctx.traced[-1].t1``, oldest first."""
    read = getattr(obs, "spans", None)
    if read is None or not ctx.traced:
        return []
    return read(int(ctx.traced[0].t0 * 1e9), int(ctx.traced[-1].t1 * 1e9))


def named(spans, name: str) -> list:
    return [s for s in spans if s.name == name]


def within(outer, spans) -> List:
    """The spans of ``spans`` on ``outer``'s thread lying inside it."""
    return [s for s in spans if s.tid == outer.tid
            and outer.t0_ns <= s.t0_ns and s.t1_ns <= outer.t1_ns]


def by_boundary(ctx):
    """Each traced boundary with the spans and instants that end within
    it."""
    sp = window(ctx)
    for b in ctx.traced:
        t0, t1 = b.t0 * 1e9, b.t1 * 1e9
        yield b, [s for s in sp if t0 <= s.t1_ns <= t1]
