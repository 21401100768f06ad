"""Span-based tracer with Chrome/Perfetto trace-event export, on the
profiler's clock while a JAX profiler session is collecting.

Design constraints, in priority order:

  1. **Near-zero overhead when off.**  A span records while tracing is
     enabled (``enable()``, ``$REPRO_TRACE``) *or* while a JAX profiler
     session is collecting (``jax.profiler.start_trace``).  With neither,
     ``span(...)`` returns a shared no-op context manager and ``@traced``
     functions call straight through — the cost is one attribute read and
     the profiler's ``TraceAnnotation.is_enabled()`` check.  Nothing is
     allocated, no generator frames, no locks.
  2. **One timeline with the device.**  While a profiler session is
     collecting, every span also opens a ``jax.profiler.TraceAnnotation``
     of the same name and args, so it lands on the host plane of the
     session's ``.xplane.pb``, nested under the caller's own annotations
     and on the same clock as the device's ops.
  3. **Thread-safe.**  Each thread keeps its own span *stack*
     (``threading.local``) so nesting is per-thread; completed spans are
     appended to one shared, bounded buffer under a lock (one append per
     span exit).  The buffer keeps the newest :data:`CAPACITY` records
     and counts what it drops in the ``obs.trace.dropped`` counter, so a
     server profiled for hours cannot grow it without limit.
  4. **Readable in process and out.**  :func:`spans` returns the recorded
     spans of an interval on the ``time.perf_counter_ns`` clock (name,
     parent, args, start and end), so a caller timing its own loop with
     ``time.perf_counter`` can window them.  ``to_chrome()`` emits the
     Chrome trace-event JSON object form (``{"traceEvents": [...]}``) that
     ``chrome://tracing`` and https://ui.perfetto.dev load directly:
     complete events (``ph: "X"``) for spans, instant events (``ph: "i"``)
     for point events, microsecond timestamps relative to the trace epoch.

Spans nest lexically::

    with trace.span("serve.step_chunk", slots=4):
        with trace.span("serve.decode_chunk"):
            ...

and the exporter's ``X`` events reconstruct the hierarchy from the
timestamps; the explicit per-thread stack additionally gives each event its
parent's name (``args["parent"]``) so a flat JSON consumer can group
without interval math.  :func:`complete` records a span whose ends were
stamped elsewhere (a request's time in the queue); the profiler cannot
take a span after the fact, so those live only in this buffer and its
export.
"""
from __future__ import annotations

import collections
import json
import os
import threading
import time
from typing import Deque, Dict, List, NamedTuple, Optional

from jax.profiler import TraceAnnotation

from . import metrics

__all__ = ["Tracer", "Span", "tracer", "span", "traced", "instant",
           "complete", "enable", "disable", "enabled", "recording", "spans",
           "events", "clear", "to_chrome", "export", "set_span_sink",
           "CAPACITY"]

# the buffer keeps this many newest records: the benchmark's serving cells
# record 46-60 a second while profiled, so this holds about ten minutes
CAPACITY = 1 << 15

# True while a JAX profiler session is collecting (about 100 ns a call)
_profiling = TraceAnnotation.is_enabled

# Optional tap on span completions (the flight recorder registers here).
# Only consulted from _record, i.e. while recording — the off path stays
# one attribute read + the profiler check.
_span_sink = None


def set_span_sink(fn) -> None:
    """Register ``fn(name, dur_us, args, error)`` to observe every span
    completion while recording; ``None`` unregisters."""
    global _span_sink
    _span_sink = fn


class Span(NamedTuple):
    """One recorded span (``ph == "X"``) or instant (``ph == "i"``, whose
    ends coincide), stamped with ``time.perf_counter_ns``."""
    name: str
    t0_ns: int
    t1_ns: int
    parent: Optional[str]
    args: Optional[dict]
    tid: int
    ph: str = "X"
    error: Optional[str] = None

    @property
    def dur_ns(self) -> int:
        return self.t1_ns - self.t0_ns


class _NullSpan:
    """The off-mode context manager: one shared instance, no state."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class _Span:
    """A live span: records a complete ("ph": "X") event on exit, and
    mirrors itself as a profiler annotation while a session collects."""
    __slots__ = ("_tracer", "name", "args", "_t0", "_ta")

    def __init__(self, tracer: "Tracer", name: str, args: Optional[dict]):
        self._tracer = tracer
        self.name = name
        self.args = args

    def __enter__(self):
        self._ta = None
        if _profiling():
            self._ta = TraceAnnotation(self.name, **(self.args or {}))
            self._ta.__enter__()
        self._t0 = time.perf_counter_ns()
        self._tracer._stack().append(self.name)
        return self

    def __exit__(self, exc_type, exc, tb):
        t1 = time.perf_counter_ns()
        if self._ta is not None:
            self._ta.__exit__(exc_type, exc, tb)
        stack = self._tracer._stack()
        stack.pop()
        self._tracer._record(self.name, self._t0, t1,
                             parent=stack[-1] if stack else None,
                             args=self.args,
                             error=exc_type.__name__ if exc_type else None)
        return False


class Tracer:
    """Process-wide bounded span buffer + the enabled flag the hot paths
    read."""

    def __init__(self):
        self._enabled = False
        self._events: Deque[Span] = collections.deque(maxlen=CAPACITY)
        self._lock = threading.Lock()
        self._local = threading.local()
        self._epoch_ns = time.perf_counter_ns()
        self._pid = os.getpid()

    # -- state ---------------------------------------------------------------

    def enable(self) -> None:
        self._enabled = True

    def disable(self) -> None:
        self._enabled = False

    @property
    def enabled(self) -> bool:
        return self._enabled

    def recording(self) -> bool:
        """True while spans record: tracing enabled, or a JAX profiler
        session collecting.  Callers that compute span args worth more
        than a read gate them on this."""
        return self._enabled or _profiling()

    def clear(self) -> None:
        with self._lock:
            self._events.clear()
            self._epoch_ns = time.perf_counter_ns()

    def _stack(self) -> List[str]:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def depth(self) -> int:
        """Current span nesting depth on the calling thread."""
        return len(self._stack())

    # -- recording -----------------------------------------------------------

    def span(self, name: str, **args):
        """Context manager timing a region; a shared no-op when neither
        tracing nor a profiler session is on."""
        if not (self._enabled or _profiling()):
            return _NULL_SPAN
        return _Span(self, name, args or None)

    def instant(self, name: str, **args) -> None:
        """A point event ("ph": "i"); dropped (one if) when not
        recording."""
        if not (self._enabled or _profiling()):
            return
        t = time.perf_counter_ns()
        self._append(Span(name, t, t, None,
                          _jsonable(args) if args else None,
                          threading.get_ident(), "i"))

    def complete(self, name: str, t0_ns: int, t1_ns: int, **args) -> None:
        """Record a span whose ends were stamped elsewhere, on the
        ``perf_counter_ns`` clock; dropped when not recording."""
        if not (self._enabled or _profiling()):
            return
        self._record(name, t0_ns, t1_ns, parent=None, args=args or None,
                     error=None)

    def _record(self, name: str, t0_ns: int, t1_ns: int, *,
                parent: Optional[str], args: Optional[dict],
                error: Optional[str]) -> None:
        # args are made JSON-safe now, so the buffer holds no reference to
        # an array or object passed as an arg
        self._append(Span(name, t0_ns, t1_ns, parent,
                          _jsonable(args) if args else None,
                          threading.get_ident(), "X", error))
        if _span_sink is not None:
            _span_sink(name, (t1_ns - t0_ns) / 1e3, args, error)

    def _append(self, rec: Span) -> None:
        with self._lock:
            full = len(self._events) == self._events.maxlen
            self._events.append(rec)
        if full:
            metrics.counter("obs.trace.dropped").inc()

    # -- reading -------------------------------------------------------------

    def spans(self, t0_ns: Optional[int] = None,
              t1_ns: Optional[int] = None) -> List[Span]:
        """The recorded spans and instants that END within
        ``[t0_ns, t1_ns]`` (either bound None: open), oldest first."""
        with self._lock:
            recs = list(self._events)
        return [r for r in recs
                if (t0_ns is None or r.t1_ns >= t0_ns)
                and (t1_ns is None or r.t1_ns <= t1_ns)]

    def events(self) -> List[dict]:
        """The recorded events as Chrome trace-event dicts (fresh copies;
        safe to mutate)."""
        with self._lock:
            recs, epoch = list(self._events), self._epoch_ns
        return [self._chrome(r, epoch) for r in recs]

    def _chrome(self, r: Span, epoch_ns: int) -> dict:
        ev = {"name": r.name, "ph": r.ph, "ts": (r.t0_ns - epoch_ns) / 1e3}
        if r.ph == "X":
            ev["dur"] = r.dur_ns / 1e3
        else:
            ev["s"] = "t"
        ev["pid"], ev["tid"] = self._pid, r.tid
        extra = dict(r.args) if r.args else {}
        if r.parent is not None:
            extra["parent"] = r.parent
        if r.error is not None:
            extra["error"] = r.error
        if extra:
            ev["args"] = extra
        return ev

    def to_chrome(self) -> Dict[str, object]:
        """The Chrome trace-event JSON document (Perfetto-loadable)."""
        return {"traceEvents": self.events(), "displayTimeUnit": "ms"}

    def export(self, path: str) -> str:
        """Write the Chrome trace JSON to ``path`` (atomic tmp + rename)."""
        doc = self.to_chrome()
        d = os.path.dirname(path) or "."
        os.makedirs(d, exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(doc, f)
        os.replace(tmp, path)
        return path


def _jsonable(d: dict) -> dict:
    """Coerce span args to JSON-safe scalars (repr anything exotic) so a
    stray array/object in an arg can never make the export unloadable."""
    out = {}
    for k, v in d.items():
        if isinstance(v, (str, int, float, bool)) or v is None:
            out[k] = v
        elif isinstance(v, (list, tuple)):
            out[k] = [x if isinstance(x, (str, int, float, bool)) or x is None
                      else repr(x) for x in v]
        elif isinstance(v, dict):
            out[k] = _jsonable(v)
        else:
            out[k] = repr(v)
    return out


# ---------------------------------------------------------------------------
# module-level singleton + convenience API
# ---------------------------------------------------------------------------

tracer = Tracer()

span = tracer.span
instant = tracer.instant
complete = tracer.complete
enable = tracer.enable
disable = tracer.disable
recording = tracer.recording
spans = tracer.spans
events = tracer.events
clear = tracer.clear
to_chrome = tracer.to_chrome
export = tracer.export


def enabled() -> bool:
    return tracer._enabled


def traced(name: Optional[str] = None, **attrs):
    """Decorator: wrap calls in a span.  Off, calls straight through —
    one attribute read + the profiler check of overhead."""
    def deco(fn):
        label = name or fn.__qualname__
        import functools

        @functools.wraps(fn)
        def wrapper(*a, **kw):
            if not (tracer._enabled or _profiling()):
                return fn(*a, **kw)
            with tracer.span(label, **attrs):
                return fn(*a, **kw)
        return wrapper
    return deco


# $REPRO_TRACE=1 enables tracing at import; a path value ("…/trace.json")
# additionally registers an atexit export so ad-hoc runs need no code
_env = os.environ.get("REPRO_TRACE", "")
if _env and _env.lower() not in ("0", "false", "no", "off"):
    tracer.enable()
    if _env.lower() not in ("1", "true", "yes", "on"):
        import atexit
        atexit.register(lambda: tracer.export(_env))
