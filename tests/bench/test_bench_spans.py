"""The engine layer's readers of the program's spans: each gives the value
worked out by hand from a span list, windowed to the traced boundaries,
and None where the program recorded nothing."""
import numpy as np
import pytest

from bench import harness, loop, spec
from bench_cells import CPU_PEAKS
from repro import obs

MS = 1_000_000
T0 = 10_000 * 10**9                  # the traced window: T0 .. T0 + 1 s


def _span(name, a_ms, b_ms, tid=1, ph="X", **args):
    return obs.Span(name, T0 + int(a_ms * MS), T0 + int(b_ms * MS), None,
                    args or None, tid, ph)


def _ctx(traced):
    win = loop.Window(0.0, 1.0, [], traced, [], [])
    return harness.Ctx({}, CPU_PEAKS, win, None, traced)


@pytest.fixture
def recorded(monkeypatch):
    """Stand the given span list in for the program's buffer."""
    def use(spans):
        def read(t0_ns=None, t1_ns=None):
            return [s for s in spans if t0_ns <= s.t1_ns <= t1_ns]
        monkeypatch.setattr(obs, "spans", read)
        return _ctx([loop.Boundary(T0 / 1e9, (T0 + 400 * MS) / 1e9, [], []),
                     loop.Boundary((T0 + 400 * MS) / 1e9,
                                   (T0 + 1000 * MS) / 1e9, [], [])])
    return use


def test_host_ms_per_boundary(recorded):
    ctx = recorded([
        _span("serve.step_chunk", 10, 30),
        _span("serve.device_wait", 12, 20, on="decode"),
        _span("serve.device_wait", 21, 23, on="first_token"),
        _span("serve.device_wait", 24, 29, tid=2),     # another thread
        _span("serve.step_chunk", 500, 504),
        _span("serve.device_wait", 501, 503, on="decode"),
        _span("serve.device_wait", 600, 650, on="decode"),   # no boundary
        _span("serve.step_chunk", -50, -10),           # before the window
    ])
    # (20 - 8 - 2) and (4 - 2) ms
    assert spec.metric_reader("engine.host_ms_per_boundary")(ctx) == \
        pytest.approx(6.0)
    assert any("2 boundaries" in n for n in ctx.notes)


def test_prefill_wait_p95(recorded):
    waits = [3, 150, 180, 400, 90, 700, 1200]
    ctx = recorded([_span("serve.request.prefill", 900 - w, 900, req_id=i)
                    for i, w in enumerate(waits)]
                   + [_span("serve.request.prefill", 1100, 2500, req_id=9)])
    assert spec.metric_reader("engine.prefill_wait_p95_ms")(ctx) == \
        pytest.approx(np.percentile(waits, 95))
    assert any("7 requests" in n for n in ctx.notes)


def test_kv_reserved_idle_share(recorded):
    ctx = recorded([
        _span("serve.kv_pages", 5, 5, ph="i", reserved=400, written=200,
              pool=500),
        _span("serve.kv_pages", 300, 300, ph="i", reserved=300, written=250,
              pool=500),
        _span("serve.kv_pages", 1500, 1500, ph="i", reserved=500, written=0,
              pool=500),
    ])
    # (200 + 50) / 500 / 2
    assert spec.metric_reader("engine.kv_reserved_idle_share")(ctx) == \
        pytest.approx(25.0)


@pytest.mark.parametrize("name", ["engine.host_ms_per_boundary",
                                  "engine.prefill_wait_p95_ms",
                                  "engine.kv_reserved_idle_share"])
def test_reader_finds_nothing_for_the_fake_engine(name):
    from test_bench_loop import Clock, FakeEngine, TwoRequests
    clock = Clock()
    win = loop.drive(FakeEngine(clock), TwoRequests(), warmup_s=0.0,
                     seconds=1.0, clock=clock, sleep=clock.sleep,
                     trace_from=0.5, on_trace=lambda start: None)
    a, b = win.trace_span
    ctx = harness.Ctx({}, CPU_PEAKS, win, None, win.boundaries[a:b + 1])
    assert spec.metric_reader(name)(ctx) is None
    assert spec.metric_reader(name)(_ctx([])) is None
