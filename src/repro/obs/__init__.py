"""repro.obs — tracing, metrics, provenance, flight recorder, drift audit.

The compiler's claim ("the chosen strategy is preserved end to end") and
the serving engines' invariants ("token-identical, zero recompiles after
warm-up") are asserted by tests; this package makes them *observable* in
any run:

  trace       span tracer (thread-local stacks, monotonic clocks,
              near-zero overhead off, a bounded buffer) that also
              writes each span into a collecting JAX profiler session's
              trace — ``obs.enable()``, ``with obs.span("name"): ...``,
              ``obs.spans(t0_ns, t1_ns)``,
              ``obs.export_trace("trace.json")`` (Chrome/Perfetto JSON,
              load in https://ui.perfetto.dev)
  metrics     always-on process registry of counters / gauges /
              histograms (with interpolated p50/p95/p99 in every
              snapshot) — ``obs.counter("x").inc()``,
              ``obs.metrics_snapshot()``
  provenance  a record per tuned decision (kernel strategy, mesh
              placement, KV layout): inputs, predicted roofline terms,
              measured time, cache origin — ``print(obs.explain())``
  recorder    always-on flight recorder: a bounded ring of recent
              boundary events/spans/counter deltas, dumped as one JSON
              black box when a request fails, a degradation fires, a
              failure domain dies (reason ``host_lost`` — exactly one
              dump per host-loss event), or an artefact is quarantined —
              ``obs.flight_dump/flight_dumps``
  audit       roofline drift audit: baseline-relative per-key cost
              statistics plus cached-ranking re-checks that fire
              ``tune.drift`` and mark provenance ``[stale]`` —
              ``obs.drift_observe``, ``obs.audit_cache``
  report      one human-readable rendering of all of the above —
              ``python -m repro.obs.report``

The instrumented spine: ``Program.check/lower/compile`` spans, executor
cache build/hit/AOT events, autotune enumeration + measurement spans,
serving boundary spans (every stretch of host time in
``serve.step_chunk`` named, each blocking host sync a
``serve.device_wait``), request-scoped lifecycle events (submit / admit
/ first_token / retire carry ``req_id``; decode chunks carry the
co-batched ``req_ids``) and lifecycle spans (queued / prefill / decode),
per-request latency histograms (queue wait, TTFT, decode tok/s), KV pool
occupancy gauges, and a recompile detector that flags jit-cache growth
after engine warm-up.  ``Engine.stats()`` is the one-call summary.  See
docs/observability.md.

Tracing defaults off; enable programmatically or with ``REPRO_TRACE=1``
(a path value also exports at exit).  Spans also record, and land in
the profiler's trace, whenever a JAX profiler session is collecting.
Metrics, provenance, the recorder, and the audit are always on — they
only run at boundaries (tuning, staging, chunk edges), never in a hot
loop.  ``REPRO_FLIGHT_DIR`` makes the recorder write its dumps as
``flight-*.json`` artefacts.
"""
from __future__ import annotations

from . import metrics, provenance, trace  # noqa: F401
from . import audit, recorder, report  # noqa: F401  (after the base trio)
from .audit import audit_cache, audit_record, auditor  # noqa: F401
from .audit import observe as drift_observe  # noqa: F401
from .provenance import annotate  # noqa: F401
from .recorder import FlightRecorder  # noqa: F401
from .recorder import clear as flight_clear  # noqa: F401
from .recorder import configure as configure_flight  # noqa: F401
from .recorder import dump as flight_dump  # noqa: F401
from .recorder import dumps as flight_dumps  # noqa: F401
from .recorder import tail as flight_tail  # noqa: F401
from .metrics import (  # noqa: F401
    MetricsRegistry, counter, gauge, histogram, registry,
)
from .metrics import export as export_metrics  # noqa: F401
from .metrics import reset as metrics_reset  # noqa: F401
from .metrics import snapshot as metrics_snapshot  # noqa: F401
from .provenance import (  # noqa: F401
    Decision, ProvenanceLog, decisions, explain, record,
)
from .provenance import clear as clear_decisions  # noqa: F401
from .provenance import log as provenance_log  # noqa: F401
from .trace import (  # noqa: F401
    Span, Tracer, complete, disable, enable, enabled, instant, recording,
    span, spans, to_chrome, traced, tracer,
)
from .trace import clear as clear_trace  # noqa: F401
from .trace import events as trace_events  # noqa: F401
from .trace import export as export_trace  # noqa: F401

# ``event`` is the structured point event: always lands in the flight
# recorder's ring, additionally in the trace when tracing is enabled
from .recorder import emit as event  # noqa: F401, E402

__all__ = [
    # tracing
    "Tracer", "Span", "tracer", "enable", "disable", "enabled", "recording",
    "span", "traced", "instant", "complete", "spans", "event",
    "trace_events", "clear_trace", "to_chrome", "export_trace",
    # metrics
    "MetricsRegistry", "registry", "counter", "gauge", "histogram",
    "metrics_snapshot", "metrics_reset", "export_metrics",
    # provenance
    "Decision", "ProvenanceLog", "record", "decisions", "explain",
    "annotate", "clear_decisions", "provenance_log",
    # flight recorder
    "FlightRecorder", "flight_dump", "flight_dumps", "flight_tail",
    "flight_clear", "configure_flight",
    # drift audit
    "auditor", "drift_observe", "audit_record", "audit_cache",
    "metrics", "provenance", "trace", "recorder", "audit", "report",
]
