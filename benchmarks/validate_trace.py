"""Schema validation for the observability artefacts CI uploads.

Checks (stdlib only, no jsonschema dependency):

  * a trace file is Chrome/Perfetto trace-event JSON — a ``traceEvents``
    list whose every event has a string ``name``, a known phase (``X``
    complete events carry numeric ``ts``/``dur``; ``i`` instants carry
    ``ts`` and scope ``s``), and integer ``pid``/``tid``;
  * a metrics file is a ``{name: snapshot}`` dict whose every snapshot has
    a known ``type`` with that type's required fields;
  * a BENCH_serve.json carries its embedded ``metrics`` snapshot with the
    benchmark's reported gauges present;
  * a strategy-trace artefact (``--strategy``) carries well-formed
    serialised ``repro.strategy.StrategyTrace`` docs — version 1, every
    step with a non-empty string ``rule``, a ``path`` of slot-name strings
    and JSON-scalar ``params``.  Accepts a bare trace doc, a tuning-cache
    file (every record's ``strategy_trace``), or any JSON object whose
    (nested) ``strategy_trace`` fields are then checked;
  * a flight-recorder dump (``--flight``, a ``flight-*.json`` file or a
    directory of them) is version 1, names a ``reason``, and carries a
    well-formed ring (``events``: entries with a known ``kind`` + name),
    an embedded metrics snapshot, and well-formed drift stats;
  * a scheduler journal (``--journal``, JSONL from
    ``repro.serve.domains.SchedulerJournal``) has every line's sha256
    checksum recomputed and verified, every record kind known
    (submit/progress/terminal/evacuate/shrink), and the required fields
    per kind present — independently of the repro tree, so a journal CI
    uploads is provably replayable.

Usage:
  python benchmarks/validate_trace.py --trace trace.json \
      [--metrics metrics.json] [--bench BENCH_serve.json] \
      [--strategy tuning_cache.json] [--flight flight-dumps/] \
      [--journal journal.jsonl]

Exits non-zero with a message naming the first offending record, so a CI
failure points at the event, not just the file.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

_PHASES = {"X", "i", "B", "E", "M"}
_METRIC_FIELDS = {
    "counter": ("value",),
    "gauge": ("value",),
    "histogram": ("count", "total", "mean", "buckets"),
}


def fail(msg: str) -> None:
    print(f"validate_trace: FAIL: {msg}", file=sys.stderr)
    raise SystemExit(1)


def validate_trace(path: str) -> int:
    with open(path) as f:
        doc = json.load(f)
    if not isinstance(doc, dict) or "traceEvents" not in doc:
        fail(f"{path}: not a trace-event document (no 'traceEvents')")
    events = doc["traceEvents"]
    if not isinstance(events, list) or not events:
        fail(f"{path}: 'traceEvents' must be a non-empty list")
    for i, ev in enumerate(events):
        where = f"{path}: traceEvents[{i}]"
        if not isinstance(ev, dict):
            fail(f"{where}: not an object")
        if not isinstance(ev.get("name"), str) or not ev["name"]:
            fail(f"{where}: missing/empty 'name'")
        ph = ev.get("ph")
        if ph not in _PHASES:
            fail(f"{where} ({ev['name']!r}): unknown phase {ph!r}")
        if ph in ("X", "i"):
            if not isinstance(ev.get("ts"), (int, float)):
                fail(f"{where} ({ev['name']!r}): non-numeric 'ts'")
        if ph == "X":
            if not isinstance(ev.get("dur"), (int, float)) or ev["dur"] < 0:
                fail(f"{where} ({ev['name']!r}): bad 'dur'")
        if ph == "i" and ev.get("s") not in ("t", "p", "g"):
            fail(f"{where} ({ev['name']!r}): instant scope {ev.get('s')!r}")
        for k in ("pid", "tid"):
            if not isinstance(ev.get(k), int):
                fail(f"{where} ({ev['name']!r}): non-integer {k!r}")
    return len(events)


def validate_metrics(snap: dict, where: str) -> int:
    if not isinstance(snap, dict) or not snap:
        fail(f"{where}: metrics snapshot must be a non-empty dict")
    for name, m in snap.items():
        if not isinstance(m, dict):
            fail(f"{where}: metric {name!r} is not an object")
        t = m.get("type")
        if t not in _METRIC_FIELDS:
            fail(f"{where}: metric {name!r} has unknown type {t!r}")
        for field in _METRIC_FIELDS[t]:
            if field not in m:
                fail(f"{where}: {t} {name!r} missing field {field!r}")
    return len(snap)


def validate_bench(path: str) -> int:
    with open(path) as f:
        doc = json.load(f)
    if "metrics" not in doc:
        fail(f"{path}: no embedded 'metrics' snapshot")
    n = validate_metrics(doc["metrics"], f"{path}[metrics]")
    for gauge in ("bench.fused.tok_s", "bench.continuous.tok_s",
                  "bench.prefill.latency_ms"):
        if gauge not in doc["metrics"]:
            fail(f"{path}: reported gauge {gauge!r} absent from metrics")
    return n


_TRACE_VERSION = 1  # repro.strategy.lang.TRACE_VERSION (stdlib-only here)


def validate_strategy_trace_doc(doc, where: str) -> int:
    if not isinstance(doc, dict):
        fail(f"{where}: strategy trace is not an object")
    if doc.get("version") != _TRACE_VERSION:
        fail(f"{where}: unsupported strategy-trace version "
             f"{doc.get('version')!r}")
    steps = doc.get("steps")
    if not isinstance(steps, list):
        fail(f"{where}: 'steps' must be a list")
    for i, s in enumerate(steps):
        w = f"{where}.steps[{i}]"
        if not isinstance(s, dict):
            fail(f"{w}: not an object")
        if not isinstance(s.get("rule"), str) or not s["rule"]:
            fail(f"{w}: missing/empty 'rule'")
        path = s.get("path", [])
        if not isinstance(path, list) or \
                not all(isinstance(p, str) and p for p in path):
            fail(f"{w} ({s['rule']!r}): 'path' must be a list of slot names")
        params = s.get("params", {})
        if not isinstance(params, dict):
            fail(f"{w} ({s['rule']!r}): 'params' must be an object")
        for k, v in params.items():
            if not isinstance(v, (str, int, float, bool)) and v is not None:
                fail(f"{w} ({s['rule']!r}): param {k!r} is not a JSON "
                     f"scalar: {type(v).__name__}")
    return len(steps)


def _find_strategy_traces(doc, where: str):
    """Yield (trace_doc, where) for every strategy trace in an artefact."""
    if isinstance(doc, dict):
        if "steps" in doc and "version" in doc:
            yield doc, where
            return
        for k, v in doc.items():
            if k == "strategy_trace" and v is not None:
                yield v, f"{where}.strategy_trace"
            elif isinstance(v, (dict, list)):
                yield from _find_strategy_traces(v, f"{where}.{k}")
    elif isinstance(doc, list):
        for i, v in enumerate(doc):
            if isinstance(v, (dict, list)):
                yield from _find_strategy_traces(v, f"{where}[{i}]")


def validate_strategy(path: str) -> int:
    with open(path) as f:
        doc = json.load(f)
    found = list(_find_strategy_traces(doc, path))
    n = 0
    for trace, where in found:
        validate_strategy_trace_doc(trace, where)
        n += 1
    if n == 0:
        fail(f"{path}: no strategy traces found (neither a trace doc nor "
             f"any 'strategy_trace' field)")
    return n


_RING_KINDS = {"event", "span", "metric"}


def validate_flight_doc(doc: dict, where: str) -> int:
    """One flight-recorder dump document; returns its ring length."""
    if not isinstance(doc, dict):
        fail(f"{where}: not an object")
    if doc.get("version") != 1:
        fail(f"{where}: unsupported flight-dump version "
             f"{doc.get('version')!r}")
    if not isinstance(doc.get("reason"), str) or not doc["reason"]:
        fail(f"{where}: missing/empty 'reason'")
    if not isinstance(doc.get("ctx"), dict):
        fail(f"{where}: 'ctx' must be an object")
    events = doc.get("events")
    if not isinstance(events, list):
        fail(f"{where}: 'events' must be a list")
    for i, e in enumerate(events):
        w = f"{where}.events[{i}]"
        if not isinstance(e, dict):
            fail(f"{w}: not an object")
        if e.get("kind") not in _RING_KINDS:
            fail(f"{w}: unknown ring-entry kind {e.get('kind')!r}")
        if not isinstance(e.get("name"), str) or not e["name"]:
            fail(f"{w}: missing/empty 'name'")
        if not isinstance(e.get("t"), (int, float)):
            fail(f"{w} ({e['name']!r}): non-numeric 't'")
        if e["kind"] == "span" and not isinstance(e.get("dur_us"),
                                                  (int, float)):
            fail(f"{w} ({e['name']!r}): span without numeric 'dur_us'")
        if e["kind"] == "metric" and not isinstance(e.get("delta"),
                                                    (int, float)):
            fail(f"{w} ({e['name']!r}): metric without numeric 'delta'")
    validate_metrics(doc.get("metrics", {}), f"{where}[metrics]")
    drift = doc.get("drift")
    if drift not in (None, {}):
        validate_drift_doc(drift, f"{where}[drift]")
    return len(events)


def validate_drift_doc(doc: dict, where: str) -> int:
    """A drift-auditor snapshot (embedded in dumps, or standalone)."""
    if not isinstance(doc, dict):
        fail(f"{where}: not an object")
    keys = doc.get("keys", {})
    if not isinstance(keys, dict):
        fail(f"{where}: 'keys' must be an object")
    for k, st in keys.items():
        w = f"{where}.keys[{k}]"
        if not isinstance(st, dict):
            fail(f"{w}: not an object")
        if not isinstance(st.get("n"), int) or st["n"] < 1:
            fail(f"{w}: bad sample count {st.get('n')!r}")
        if not isinstance(st.get("fired"), bool):
            fail(f"{w}: 'fired' must be a bool")
    ranking = doc.get("ranking", {})
    if not isinstance(ranking, dict):
        fail(f"{where}: 'ranking' must be an object")
    for k, f_ in ranking.items():
        w = f"{where}.ranking[{k}]"
        if not isinstance(f_, dict):
            fail(f"{w}: not an object")
        for field in ("measured_best", "predicted_best"):
            if not isinstance(f_.get(field), str):
                fail(f"{w}: missing '{field}'")
    return len(keys) + len(ranking)


def validate_flight(path: str) -> int:
    """A dump file, or a directory of flight-*.json dumps; returns the
    number of dump documents validated."""
    paths = [path]
    if os.path.isdir(path):
        paths = sorted(
            os.path.join(path, n) for n in os.listdir(path)
            if n.startswith("flight-") and n.endswith(".json"))
        if not paths:
            fail(f"{path}: directory holds no flight-*.json dumps")
    for p in paths:
        with open(p) as f:
            validate_flight_doc(json.load(f), p)
    return len(paths)


# repro.serve.domains.JOURNAL_KINDS + the fields a replay needs per kind
# (stdlib-only mirror: this validator must not import the repro tree)
_JOURNAL_FIELDS = {
    "submit": ("rid", "prompt", "max_new", "temperature", "top_k", "stream"),
    "progress": ("rid", "tokens", "n"),
    "terminal": ("rid", "state"),
    "evacuate": ("rid", "host"),
    "shrink": ("frm", "to", "host"),
}


def validate_journal(path: str) -> int:
    """A scheduler journal: per-line checksum recompute + schema check.
    An empty journal (no traffic recorded) is valid; a torn or tampered
    line is a failure — CI uploads must verify, the lenient torn-tail
    recovery is the engine restart path's job, not the validator's."""
    import hashlib
    with open(path) as f:
        lines = [ln for ln in f.read().splitlines() if ln.strip()]
    for i, line in enumerate(lines):
        where = f"{path}:{i + 1}"
        try:
            rec = json.loads(line)
        except ValueError as e:
            fail(f"{where}: unparseable record ({e})")
        if not isinstance(rec, dict):
            fail(f"{where}: record is not an object")
        want = rec.pop("checksum", None)
        if not isinstance(want, str) or not want.startswith("sha256:"):
            fail(f"{where}: missing/malformed 'checksum'")
        blob = json.dumps(rec, sort_keys=True, separators=(",", ":"),
                          default=str)
        got = "sha256:" + hashlib.sha256(blob.encode()).hexdigest()
        if got != want:
            fail(f"{where}: checksum mismatch (journal tampered or torn)")
        kind = rec.get("kind")
        if kind not in _JOURNAL_FIELDS:
            fail(f"{where}: unknown record kind {kind!r}")
        for field in _JOURNAL_FIELDS[kind]:
            if field not in rec:
                fail(f"{where} ({kind}): missing field {field!r}")
    return len(lines)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--trace", default=None)
    ap.add_argument("--metrics", default=None)
    ap.add_argument("--bench", default=None)
    ap.add_argument("--strategy", default=None)
    ap.add_argument("--flight", default=None,
                    help="flight-recorder dump file or directory of dumps")
    ap.add_argument("--journal", default=None,
                    help="scheduler journal (JSONL) to checksum-verify")
    args = ap.parse_args()
    if not (args.trace or args.metrics or args.bench or args.strategy
            or args.flight or args.journal):
        fail("nothing to validate: pass --trace/--metrics/--bench/"
             "--strategy/--flight/--journal")
    if args.trace:
        n = validate_trace(args.trace)
        print(f"validate_trace: {args.trace}: {n} events OK")
    if args.metrics:
        with open(args.metrics) as f:
            n = validate_metrics(json.load(f), args.metrics)
        print(f"validate_trace: {args.metrics}: {n} metrics OK")
    if args.bench:
        n = validate_bench(args.bench)
        print(f"validate_trace: {args.bench}: embedded metrics "
              f"({n}) OK")
    if args.strategy:
        n = validate_strategy(args.strategy)
        print(f"validate_trace: {args.strategy}: {n} strategy trace"
              f"{'s' if n != 1 else ''} OK")
    if args.flight:
        n = validate_flight(args.flight)
        print(f"validate_trace: {args.flight}: {n} flight dump"
              f"{'s' if n != 1 else ''} OK")
    if args.journal:
        n = validate_journal(args.journal)
        print(f"validate_trace: {args.journal}: {n} journal record"
              f"{'s' if n != 1 else ''} checksum-verified OK")


if __name__ == "__main__":
    main()
