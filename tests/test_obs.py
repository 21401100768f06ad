"""Observability tests: tracer semantics + overhead, metrics registry,
Chrome-JSON export, strategy provenance, the unified ``Engine.stats()``
dict, the serving recompile detector, the always-on flight recorder,
request-scoped traces, and the roofline drift auditor."""
import json
import logging
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from repro import obs
from repro.models.common import ModelConfig
from repro.models.transformer import Model
from repro.serve.engine import ContinuousEngine, Request


@pytest.fixture(autouse=True)
def _clean_tracer():
    """Every test starts disabled with empty buffers and ends the same."""
    obs.disable()
    obs.clear_trace()
    yield
    obs.disable()
    obs.clear_trace()


def tiny_cfg(**kw):
    base = dict(name="obs-t", family="dense", n_layers=2, d_model=32,
                n_heads=4, n_kv_heads=2, d_ff=64, vocab=128, dtype="float32",
                remat=False, max_seq=64)
    base.update(kw)
    return ModelConfig(**base)


@pytest.fixture(scope="module")
def dense_model():
    cfg = tiny_cfg()
    model = Model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    return cfg, model, params


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------

class TestTracer:
    def test_disabled_records_nothing(self):
        with obs.span("a", x=1):
            obs.event("b")
        assert obs.trace_events() == []

    def test_span_event_shape(self):
        obs.enable()
        with obs.span("outer", label="L"):
            with obs.span("inner"):
                pass
            obs.event("point", n=3)
        evs = obs.trace_events()
        by_name = {e["name"]: e for e in evs}
        assert set(by_name) == {"outer", "inner", "point"}
        inner, outer = by_name["inner"], by_name["outer"]
        assert inner["ph"] == outer["ph"] == "X"
        assert inner["args"]["parent"] == "outer"
        assert outer["args"]["label"] == "L"
        # the child interval nests inside the parent interval
        assert outer["ts"] <= inner["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-6
        point = by_name["point"]
        assert point["ph"] == "i" and point["s"] == "t"
        assert point["args"]["n"] == 3

    def test_span_records_error_and_unwinds(self):
        obs.enable()
        with pytest.raises(RuntimeError):
            with obs.span("boom"):
                raise RuntimeError("x")
        (ev,) = obs.trace_events()
        assert ev["args"]["error"] == "RuntimeError"
        assert obs.tracer.depth() == 0

    def test_traced_decorator(self):
        calls = []

        @obs.traced("deco.fn")
        def fn(x):
            calls.append(x)
            return x + 1

        assert fn(1) == 2                       # disabled: calls through
        assert obs.trace_events() == []
        obs.enable()
        assert fn(2) == 3
        assert [e["name"] for e in obs.trace_events()] == ["deco.fn"]

    def test_thread_safety(self):
        """8 threads x 50 nested span pairs: every event lands, each
        thread's parent links are its own (no cross-thread stack bleed)."""
        obs.enable()
        n_threads, n_spans = 8, 50

        def work(tid):
            for i in range(n_spans):
                with obs.span(f"outer-{tid}"):
                    with obs.span(f"inner-{tid}"):
                        pass

        threads = [threading.Thread(target=work, args=(t,))
                   for t in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        evs = obs.trace_events()
        assert len(evs) == n_threads * n_spans * 2
        for e in evs:
            if e["name"].startswith("inner-"):
                tid = e["name"].split("-")[1]
                assert e["args"]["parent"] == f"outer-{tid}"

    def test_chrome_json_round_trip(self, tmp_path):
        obs.enable()
        with obs.span("a", arr=jnp.zeros(2)):    # exotic arg -> repr'd
            obs.event("b")
        path = tmp_path / "trace.json"
        obs.export_trace(str(path))
        doc = json.loads(path.read_text())
        assert isinstance(doc["traceEvents"], list)
        names = {e["name"] for e in doc["traceEvents"]}
        assert names == {"a", "b"}
        for ev in doc["traceEvents"]:
            assert isinstance(ev["ts"], (int, float))
            assert isinstance(ev["pid"], int) and isinstance(ev["tid"], int)
            assert ev["ph"] in ("X", "i")
            if ev["ph"] == "X":
                assert ev["dur"] >= 0

    def test_disabled_overhead_under_5_percent(self, dense_model):
        """The acceptance bound: tracing disabled, the per-span cost must
        be < 5% of one jitted-kernel call — measured directly (100k no-op
        spans) against the median of repeated kernel calls, so the test is
        robust to CI timing noise."""
        cfg, model, params = dense_model
        tok = jnp.zeros((4, 1), jnp.int32)
        cache = model.init_cache(4, 32)
        step = jax.jit(lambda p, t, c: model.decode_step(p, t, c,
                                                         jnp.int32(1)))
        jax.block_until_ready(step(params, tok, cache)[0])   # compile

        ts = []
        for _ in range(9):
            t0 = time.perf_counter()
            jax.block_until_ready(step(params, tok, cache)[0])
            ts.append(time.perf_counter() - t0)
        kernel_t = sorted(ts)[len(ts) // 2]

        n = 100_000
        assert not obs.enabled()
        t0 = time.perf_counter()
        for _ in range(n):
            with obs.span("x"):
                pass
        per_span = (time.perf_counter() - t0) / n
        assert per_span < 0.05 * kernel_t, (
            f"disabled span costs {per_span * 1e9:.0f} ns, kernel call "
            f"{kernel_t * 1e6:.1f} us — overhead {per_span / kernel_t:.2%}")


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

class TestMetrics:
    def test_counter_gauge_histogram(self):
        reg = obs.MetricsRegistry()
        reg.counter("c").inc()
        reg.counter("c").inc(2)
        assert reg.counter("c").value == 3
        reg.gauge("g").set(7)
        assert reg.gauge("g").value == 7
        h = reg.histogram("h")
        for v in (0.5, 1.5, 3.0, 0.0):
            h.observe(v)
        snap = reg.snapshot()
        assert snap["c"] == {"type": "counter", "value": 3}
        assert snap["h"]["count"] == 4
        assert snap["h"]["min"] == 0.0 and snap["h"]["max"] == 3.0
        assert "<=0" in snap["h"]["buckets"]    # the 0.0 observation
        json.dumps(snap)                         # JSON-able as-is
        reg.reset()
        assert reg.counter("c").value == 0
        assert reg.histogram("h").count == 0

    def test_type_mismatch_raises(self):
        reg = obs.MetricsRegistry()
        reg.counter("x")
        with pytest.raises(TypeError):
            reg.gauge("x")

    def test_export(self, tmp_path):
        reg = obs.MetricsRegistry()
        reg.counter("n").inc(5)
        path = tmp_path / "m.json"
        reg.export(str(path))
        assert json.loads(path.read_text())["n"]["value"] == 5

    def test_concurrent_increments(self):
        reg = obs.MetricsRegistry()
        c = reg.counter("c")

        def work():
            for _ in range(1000):
                c.inc()

        threads = [threading.Thread(target=work) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert c.value == 8000


# ---------------------------------------------------------------------------
# provenance
# ---------------------------------------------------------------------------

class TestProvenance:
    def test_tuned_kernels_have_decisions(self, tmp_path):
        """Every kernel the tuner decides on shows up in explain() with a
        roofline-backed origin."""
        from repro import autotune
        from repro.kernels import ops
        obs.clear_decisions()
        cache = autotune.TuningCache(str(tmp_path / "t.json"))
        from repro import compiler
        with compiler.options(tuning_cache=cache):
            x = jnp.ones((8, 64), jnp.float32)
            w = jnp.ones((64, 32), jnp.float32)
            ops.matmul(x, w, impl="dpia-jnp")   # the tuned dispatch path
        ds = obs.decisions()
        assert ds, "tuning produced no provenance decisions"
        mm = [d for d in ds if d.kernel == "matmul"]
        assert mm, f"no matmul decision in {[d.kernel for d in ds]}"
        d = mm[-1]
        assert d.origin in ("analytic", "measured", "cache(analytic)",
                            "cache(measured)")
        assert d.terms, "decision carries no roofline terms"
        report = obs.explain("matmul")
        assert "matmul" in report and d.origin in report
        # second lookup over the same cache (measure=False, the serving
        # path): origin becomes cache(...) and keeps the roofline terms
        obs.clear_decisions()
        autotune.tune("matmul", cache=cache, measure=False, m=8, k=64, n=32)
        (d2,) = [d for d in obs.decisions() if d.kernel == "matmul"]
        assert d2.origin.startswith("cache("), d2.origin
        assert d2.terms, "cache-hit decision lost its roofline terms"

    def test_explain_empty(self):
        obs.clear_decisions()
        assert "no decisions" in obs.explain("nope-no-such-kernel")


# ---------------------------------------------------------------------------
# Engine.stats() + recompile detector
# ---------------------------------------------------------------------------

class TestEngineStats:
    def test_unified_stats_dict(self, dense_model):
        cfg, model, params = dense_model
        eng = ContinuousEngine(model, params, max_seq=64, slots=2, chunk=4,
                               kv_layout="paged", block_size=16)
        reqs = [Request(prompt=jnp.arange(5) % cfg.vocab, max_new_tokens=6),
                Request(prompt=jnp.arange(9) % cfg.vocab, max_new_tokens=4)]
        eng.run(reqs)
        st = eng.stats()
        # one dict supersedes the scattered accessors — which must agree
        assert st["decode_compiles"] == eng.decode_cache_misses()
        assert st["prefill_entries"] == eng.prefill_cache_size()
        assert st["scheduler"]["admits"] == 2
        assert st["scheduler"]["retires"] == 2
        assert st["scheduler"]["pending"] == 0
        assert st["kv_pool"]["used"] == 0       # all pages returned
        assert st["recompiles_after_warm"] == 0
        assert "executor_cache" in st

    def test_lifecycle_metrics_observed(self, dense_model):
        cfg, model, params = dense_model
        obs.metrics_reset()
        eng = ContinuousEngine(model, params, max_seq=64, slots=2, chunk=4)
        eng.run([Request(prompt=jnp.arange(5) % cfg.vocab,
                         max_new_tokens=6)])
        snap = obs.metrics_snapshot()
        assert snap["serve.requests_submitted"]["value"] >= 1
        assert snap["serve.requests_retired"]["value"] >= 1
        assert snap["serve.ttft_s"]["count"] >= 1
        assert snap["serve.queue_wait_s"]["count"] >= 1
        assert snap["serve.e2e_s"]["count"] >= 1

    def test_recompile_detector_fires_on_bucket_miss(self, dense_model,
                                                     caplog):
        """Warm on a small bucket, then force a LONGER prompt through —
        the new prefill bucket grows the jit cache and the detector must
        flag it (counter + stats + log record), exactly once."""
        cfg, model, params = dense_model
        eng = ContinuousEngine(model, params, max_seq=64, slots=2, chunk=4)
        short = [Request(prompt=jnp.arange(5) % cfg.vocab, max_new_tokens=4)]
        eng.run(short)                          # first run() marks warm
        assert eng.stats()["recompiles_after_warm"] == 0

        with caplog.at_level(logging.WARNING, logger="repro.serve.engine"):
            eng.run([Request(prompt=jnp.arange(30) % cfg.vocab,
                             max_new_tokens=4)])
        st = eng.stats()
        assert st["recompiles_after_warm"] >= 1
        assert any("jit cache grew after warm-up" in r.message
                   for r in caplog.records)

        # warm traffic after the detector advanced its baseline: quiet
        caplog.clear()
        with caplog.at_level(logging.WARNING, logger="repro.serve.engine"):
            eng.run(short)
        assert st["recompiles_after_warm"] == \
            eng.stats()["recompiles_after_warm"]
        assert not caplog.records

    def test_traced_run_produces_loadable_trace(self, dense_model,
                                                tmp_path):
        """The acceptance criterion: a traced ContinuousEngine.run()
        yields a Chrome/Perfetto document with the serving spans nested
        correctly."""
        cfg, model, params = dense_model
        eng = ContinuousEngine(model, params, max_seq=64, slots=2, chunk=4)
        obs.enable()
        eng.run([Request(prompt=jnp.arange(5) % cfg.vocab,
                         max_new_tokens=6)])
        obs.disable()
        path = tmp_path / "serve-trace.json"
        obs.export_trace(str(path))
        doc = json.loads(path.read_text())
        names = [e["name"] for e in doc["traceEvents"]]
        assert "serve.step_chunk" in names
        assert "serve.decode_chunk" in names
        assert "serve.prefill_chunk" in names
        decode = next(e for e in doc["traceEvents"]
                      if e["name"] == "serve.decode_chunk")
        assert decode["args"]["parent"] == "serve.step_chunk"


def drive(eng, reqs, key=None):
    """submit + step_chunk to idle; returns per-request RequestResults."""
    with eng._options_scope():
        eng._run_key = key if key is not None else jax.random.PRNGKey(7)
        rids = [eng.submit(r, stream=i) for i, r in enumerate(reqs)]
        while not eng.sched.idle:
            eng.step_chunk()
    return [eng.take_result(rid) for rid in rids]


# ---------------------------------------------------------------------------
# histogram percentiles
# ---------------------------------------------------------------------------

class TestHistogramPercentiles:
    def test_interpolated_quantiles_plausible(self):
        reg = obs.MetricsRegistry()
        h = reg.histogram("lat")
        for v in range(1, 101):
            h.observe(float(v))
        snap = reg.snapshot()["lat"]
        # base-2 buckets are coarse: assert ordering + sane ranges, not
        # exact values
        assert 25 <= snap["p50"] <= 75
        assert snap["p50"] <= snap["p95"] <= snap["p99"] <= snap["max"]
        assert snap["p99"] >= 64.0            # the top bucket's floor
        assert h.percentile(0.0) >= snap["min"]

    def test_quantiles_clamped_to_observed_range(self):
        reg = obs.MetricsRegistry()
        h = reg.histogram("one")
        h.observe(3.0)
        snap = reg.snapshot()["one"]
        assert snap["p50"] == snap["p99"] == 3.0   # clamped to min/max

    def test_underflow_bucket(self):
        reg = obs.MetricsRegistry()
        h = reg.histogram("z")
        for _ in range(10):
            h.observe(0.0)
        assert reg.snapshot()["z"]["p99"] == 0.0

    def test_empty_histogram_has_no_quantiles(self):
        reg = obs.MetricsRegistry()
        assert reg.histogram("e").percentile(0.5) is None


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

class TestFlightRecorder:
    @pytest.fixture(autouse=True)
    def _clean_recorder(self):
        obs.configure_flight(dir=None)
        obs.flight_clear()
        yield
        obs.configure_flight(dir=None)
        obs.flight_clear()

    def test_events_ring_with_tracing_disabled(self):
        """The recorder is always on: obs.event lands in the ring even
        though the span tracer records nothing."""
        assert not obs.enabled()
        obs.event("unit.boundary", x=1)
        assert obs.trace_events() == []
        (e,) = [e for e in obs.flight_tail()
                if e["name"] == "unit.boundary"]
        assert e["kind"] == "event" and e["args"]["x"] == 1

    def test_spans_and_counter_deltas_tapped(self):
        obs.counter("unit.flight_c").inc(3)
        obs.enable()
        with obs.span("unit.flight_span"):
            pass
        seen = {(e["kind"], e["name"]) for e in obs.flight_tail()}
        assert ("metric", "unit.flight_c") in seen
        assert ("span", "unit.flight_span") in seen

    def test_ring_bounded(self):
        from repro.obs.recorder import FlightRecorder
        r = FlightRecorder(capacity=8)
        for i in range(100):
            r.record("event", f"e{i}")
        assert len(r) == 8
        assert r.tail(1)[0]["name"] == "e99"

    def test_dump_document_and_artefact(self, tmp_path):
        obs.configure_flight(dir=str(tmp_path))
        obs.event("pre.failure", req=7)
        doc = obs.flight_dump("unit_reason", req_id=7, why="test")
        assert doc["version"] == 1 and doc["reason"] == "unit_reason"
        assert doc["ctx"] == {"req_id": 7, "why": "test"}
        assert any(e["name"] == "pre.failure" for e in doc["events"])
        assert "metrics" in doc and "provenance" in doc
        assert obs.counter("obs.flight_dumps").value >= 1
        (path,) = tmp_path.glob("flight-*.json")
        loaded = json.loads(path.read_text())
        assert loaded["reason"] == "unit_reason"
        assert loaded["seq"] == doc["seq"]
        assert obs.flight_dumps()[-1]["reason"] == "unit_reason"

    def test_failed_request_dumps_clean_run_does_not(self, dense_model):
        """The resilience-bench contract as a unit drill: a clean run
        leaves the recorder silent; a NaN-poisoned request produces a
        ``request_failed`` dump attributing it by req_id."""
        from repro.testing import faults
        cfg, model, params = dense_model
        req = Request(prompt=jnp.arange(5) % cfg.vocab, max_new_tokens=4)

        eng = ContinuousEngine(model, params, max_seq=64, slots=2, chunk=4)
        (r,) = drive(eng, [req])
        assert r.state == "ok"
        assert obs.flight_dumps() == []

        eng = ContinuousEngine(model, params, max_seq=64, slots=2, chunk=4)
        with faults.inject("serve.nan_prefill(req_id=0)"):
            (r,) = drive(eng, [req])
        assert r.state == "failed"
        dumps = obs.flight_dumps()
        assert any(d["reason"] == "request_failed"
                   and d["ctx"]["req_id"] == 0 for d in dumps), \
            [d["reason"] for d in dumps]
        # the ring inside the dump shows the fault firing that caused it
        (d,) = [d for d in dumps if d["reason"] == "request_failed"]
        assert any(e["name"] == "faults.injected" for e in d["events"])

    def test_fault_event_carries_request_ctx(self, dense_model):
        """Satellite: a fault firing is attributed to the request(s) it
        hit via the site ctx riding in the event payload."""
        from repro.serve.resilience import ResilienceConfig
        from repro.testing import faults
        cfg, model, params = dense_model
        eng = ContinuousEngine(
            model, params, max_seq=64, slots=2, chunk=4,
            resilience=ResilienceConfig(retry_backoff_s=0.001))
        with faults.inject("serve.chunk_error(times=1)"):
            (r,) = drive(eng, [Request(prompt=jnp.arange(5) % cfg.vocab,
                                       max_new_tokens=4)])
        assert r.state == "ok"                  # retried through
        fired = [e for e in obs.flight_tail()
                 if e["name"] == "faults.injected" and e["kind"] == "event"]
        assert fired, "fault firing did not land in the recorder ring"
        assert any(e["args"].get("site") == "serve.chunk_error"
                   and "0" in e["args"].get("req_ids", "")
                   for e in fired), [e["args"] for e in fired]

    def test_recorder_overhead_under_5_percent(self, dense_model):
        """Satellite bound: one always-on boundary event (ring append +
        disabled instant) must cost < 5% of a jitted kernel call."""
        cfg, model, params = dense_model
        tok = jnp.zeros((4, 1), jnp.int32)
        cache = model.init_cache(4, 32)
        step = jax.jit(lambda p, t, c: model.decode_step(p, t, c,
                                                         jnp.int32(1)))
        jax.block_until_ready(step(params, tok, cache)[0])   # compile

        ts = []
        for _ in range(9):
            t0 = time.perf_counter()
            jax.block_until_ready(step(params, tok, cache)[0])
            ts.append(time.perf_counter() - t0)
        kernel_t = sorted(ts)[len(ts) // 2]

        n = 50_000
        assert not obs.enabled()
        t0 = time.perf_counter()
        for _ in range(n):
            obs.event("x", a=1)
        per_event = (time.perf_counter() - t0) / n
        assert per_event < 0.05 * kernel_t, (
            f"recorder event costs {per_event * 1e9:.0f} ns, kernel call "
            f"{kernel_t * 1e6:.1f} us — overhead {per_event / kernel_t:.2%}")


# ---------------------------------------------------------------------------
# request-scoped traces
# ---------------------------------------------------------------------------

class TestRequestScopedTraces:
    def test_lifecycle_events_carry_req_id(self, dense_model):
        from repro.obs import report
        cfg, model, params = dense_model
        eng = ContinuousEngine(model, params, max_seq=64, slots=2, chunk=4)
        obs.enable()
        drive(eng, [Request(prompt=jnp.arange(5) % cfg.vocab,
                            max_new_tokens=6),
                    Request(prompt=jnp.arange(9) % cfg.vocab,
                            max_new_tokens=4)])
        obs.disable()
        evs = obs.trace_events()
        by_name = {}
        for e in evs:
            by_name.setdefault(e["name"], []).append(e)
        assert {e["args"]["req_id"] for e in by_name["serve.submit"]} \
            == {0, 1}
        assert by_name["serve.first_token"], "no TTFT events"
        assert all("ttft_s" in e["args"]
                   for e in by_name["serve.first_token"])
        # decode chunks name the co-batched requests they advanced
        decode = by_name["serve.decode_chunk"]
        assert any(e["args"].get("req_ids") for e in decode)
        # terminal retire event present for both requests
        assert {e["args"]["req_id"]
                for e in by_name["serve.retire"]} == {0, 1}
        assert all(e["args"]["state"] == "ok"
                   for e in by_name["serve.retire"])

        # the report stitches one request's timeline out of the trace
        tl = report.request_timeline(evs, "0")
        assert "serve.submit" in tl and "serve.decode_chunk" in tl

    def test_timeline_empty_for_unknown_request(self):
        from repro.obs import report
        assert "no events" in report.request_timeline([], "42")


# ---------------------------------------------------------------------------
# roofline drift audit
# ---------------------------------------------------------------------------

class TestDriftAudit:
    @pytest.fixture(autouse=True)
    def _fresh_auditor(self):
        from repro.obs import audit
        audit.reset()
        obs.flight_clear()
        yield
        audit.reset()

    def test_ratio_drift_fires_once_after_shift(self):
        from repro.obs import audit
        before = obs.counter("tune.drift").value
        a = audit.DriftAuditor(min_samples=8, tolerance=2.0)
        # calibration: no baseline yet, returns None, never fires
        for _ in range(8):
            assert a.observe("unit|k", 1.0) is None
        assert a.observe("unit|k", 1.1) == pytest.approx(1.1, rel=1e-6)
        assert obs.counter("tune.drift").value == before   # within 2x
        assert a.observe("unit|k", 5.0) == pytest.approx(5.0, rel=1e-6)
        assert obs.counter("tune.drift").value == before + 1
        a.observe("unit|k", 5.0)                           # no re-fire
        assert obs.counter("tune.drift").value == before + 1
        snap = a.snapshot()
        assert snap["keys"]["unit|k"]["fired"] is True
        assert snap["fired"] == 1
        # the firing landed in the flight-recorder ring
        assert any(e["name"] == "tune.drift" for e in obs.flight_tail())

    def test_stable_measurements_stay_quiet(self):
        from repro.obs import audit
        before = obs.counter("tune.drift").value
        a = audit.DriftAuditor(min_samples=8, tolerance=2.0)
        for i in range(50):
            a.observe("unit|stable", 1.0 + 0.1 * (i % 3))  # small wobble
        assert obs.counter("tune.drift").value == before
        assert a.snapshot()["fired"] == 0

    def test_ranking_audit_miscalibrated_hw_fires_default_quiet(self):
        """The acceptance drill: timings agree with the default roofline's
        ranking (quiet), but a deliberately mis-calibrated HwModel ranks a
        measured-slow candidate first — the audit flags it."""
        import dataclasses

        from repro.autotune import cost
        from repro.obs import audit

        # measured timings consistent with the default model: the fused
        # vpu-leaf candidate IS fastest, the unblocked seq fallback slow
        record = {"kernel": "dot", "shape": {"n": 4096},
                  "timings": {"block=4096,leaf=vpu": 1.0e-5,
                              "block=None,leaf=seq": 2.0e-3}}

        before = obs.counter("tune.drift").value
        a = audit.DriftAuditor()
        f = a.audit_record("dot", "dot|n=4096|unit", record,
                           hw=cost.hw_model())
        assert f is not None and f["agree"], f
        assert obs.counter("tune.drift").value == before

        # a grid-overhead mis-calibration inverts the ranking: the model
        # now prefers the unblocked candidate the measurements refute
        bad = dataclasses.replace(cost.hw_model(),
                                  grid_overhead_s=1e-5 * 1e4)
        f = a.audit_record("dot", "dot|n=4096|unit", record, hw=bad)
        assert f is not None and not f["agree"], f
        assert f["predicted_best"] == "block=None,leaf=seq"
        assert f["measured_best"] == "block=4096,leaf=vpu"
        assert f["slowdown_x"] > 100
        assert obs.counter("tune.drift").value == before + 1
        snap = a.snapshot()
        assert snap["ranking"]["dot|n=4096|unit"]["agree"] is False
        # once per key: a second audit does not re-fire
        a.audit_record("dot", "dot|n=4096|unit", record, hw=bad)
        assert obs.counter("tune.drift").value == before + 1

    def test_ranking_fire_marks_provenance_stale(self):
        import dataclasses

        from repro.autotune import cost
        from repro.obs import audit, provenance

        key = "dot|n=4096|stale-unit"
        provenance.record("kernel", "dot", key, {"block": 4096},
                          "cache(measured)")
        record = {"kernel": "dot", "shape": {"n": 4096},
                  "timings": {"block=4096,leaf=vpu": 1.0e-5,
                              "block=None,leaf=seq": 2.0e-3}}
        bad = dataclasses.replace(cost.hw_model(), grid_overhead_s=0.1)
        audit.DriftAuditor().audit_record("dot", key, record, hw=bad)
        d = provenance.get(key)
        assert d.origin == "cache(measured)[stale]"
        assert "consider re-tuning" in d.note

    def test_record_without_timings_skipped(self):
        from repro.obs import audit
        assert audit.DriftAuditor().audit_record(
            "dot", "k", {"timings": {"block=64,leaf=vpu": 1e-5}}) is None
        assert audit.DriftAuditor().audit_record("dot", "k", {}) is None


# ---------------------------------------------------------------------------
# the report renderer
# ---------------------------------------------------------------------------

class TestReport:
    def test_render_metrics_and_drift(self):
        from repro.obs import report
        snap = {"a.count": {"type": "counter", "value": 3},
                "a.lat": {"type": "histogram", "count": 4, "mean": 1.0,
                          "p50": 1.0, "p95": 2.0, "p99": 2.5, "max": 3.0,
                          "min": 0.5, "buckets": {}}}
        out = report.render_metrics(snap)
        assert "a.count" in out and "p95=2" in out
        drift = {"tolerance": 2.0, "fired": 1,
                 "keys": {"k1": {"n": 9, "fired": True, "drift_x": 5.0}},
                 "ranking": {"k2": {"predicted_best": "a",
                                    "measured_best": "b",
                                    "slowdown_x": 3.0}}}
        out = report.render_drift(drift)
        assert "DRIFTED" in out and "MIS-RANKED" in out

    def test_render_dump_and_history(self):
        from repro.obs import report
        doc = {"seq": 3, "reason": "request_failed", "ctx": {"req_id": 1},
               "events": [{"kind": "event", "name": "serve.submit",
                           "t": 0.0, "args": {"req_id": 1}},
                          {"kind": "span", "name": "serve.decode_chunk",
                           "t": 0.0, "dur_us": 12.5}],
               "drift": {}}
        out = report.render_dump(doc)
        assert "request_failed" in out and "serve.decode_chunk" in out

    def test_live_render_smoke(self):
        from repro.obs import report
        obs.counter("unit.report_c").inc()
        obs.event("unit.report_e")
        out = report.render()
        assert "repro system report" in out
        assert "flight recorder" in out

    def test_cli_on_artefacts(self, tmp_path, capsys):
        from repro.obs import report
        obs.flight_clear()
        obs.configure_flight(dir=str(tmp_path / "fl"))
        obs.flight_dump("unit_cli", req_id=9)
        obs.configure_flight(dir=None)
        rc = report.main(["--flight", str(tmp_path / "fl")])
        assert rc == 0
        out = capsys.readouterr().out
        assert "unit_cli" in out
