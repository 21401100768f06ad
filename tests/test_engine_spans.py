"""The serving engine's spans: off unless tracing is enabled or a profiler
session collects; on the profiler's host plane when one does; every
stretch of a boundary named; request lifecycle spans that tile each
request's life; the KV reservation counter; the tracer's bounded buffer;
and the kernel layer's named scopes in the compiled decode chunk."""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import TraceAnnotation

from repro import obs
from repro.models.common import ModelConfig
from repro.models.transformer import Model
from repro.serve.engine import ContinuousEngine, Request
from repro.serve.paged import BlockPool
from repro.serve.scheduler import Scheduler

BOUNDARY_CHILDREN = {"serve.boundary_checks", "serve.admissions",
                     "serve.prefill_chunk", "serve.decode_chunk",
                     "serve.device_wait", "serve.record",
                     "serve.bookkeeping"}


@pytest.fixture(autouse=True)
def _clean_tracer():
    obs.disable()
    obs.clear_trace()
    yield
    obs.disable()
    obs.clear_trace()


@pytest.fixture(scope="module")
def tiny():
    cfg = ModelConfig(name="spans-t", family="dense", n_layers=2, d_model=64,
                      n_heads=4, n_kv_heads=2, d_ff=128, vocab=128,
                      dtype="float32", remat=False, max_seq=128)
    model = Model(cfg)
    return cfg, model, model.init_params(jax.random.PRNGKey(0))


def _engine(tiny, chunk=4):
    cfg, model, params = tiny
    return ContinuousEngine(model, params, max_seq=128, slots=2, chunk=chunk,
                            kv_layout="paged", block_size=16)


def _requests(cfg, n=3):
    return [Request(prompt=(jnp.arange(5 + 7 * i) * 3) % cfg.vocab,
                    max_new_tokens=6 + 5 * i) for i in range(n)]


def _serve(eng, reqs, around=None):
    """submit + step_chunk to idle, each boundary inside ``around()``."""
    rids = [eng.submit(r) for r in reqs]
    while not eng.sched.idle:
        if around is None:
            eng.step_chunk()
        else:
            with around():
                eng.step_chunk()
    return rids


def test_off_records_no_span(tiny):
    eng = _engine(tiny)
    eng.run(_requests(tiny[0]))               # compile outside the check
    obs.clear_trace()
    assert not obs.recording()
    _serve(eng, _requests(tiny[0]))
    assert obs.spans() == [] and obs.trace_events() == []


def _host_events(tdir):
    (path,) = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                        recursive=True)
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                           for e in line.events)
    return out


def test_profiler_session_puts_spans_on_the_host_plane(tiny, tmp_path):
    eng = _engine(tiny)
    eng.run(_requests(tiny[0]))
    obs.clear_trace()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        assert obs.recording() and not obs.enabled()
        _serve(eng, _requests(tiny[0]),
               around=lambda: TraceAnnotation("client.step"))
    finally:
        jax.profiler.stop_trace()
    assert not obs.recording()
    host = _host_events(str(tmp_path))
    serve = [e for e in host if e[0].startswith("serve.")]
    names = {e[0] for e in serve}
    assert {"serve.step_chunk", "serve.finish_admit"} | BOUNDARY_CHILDREN \
        <= names
    # nested in the caller's own annotation, on the profiler's clock
    steps = [e for e in host if e[0] == "client.step"]
    for _, a, b in [e for e in serve if e[0] == "serve.step_chunk"]:
        assert any(s <= a and b <= t for _, s, t in steps)
    # obs's buffer holds the same spans (lifecycle spans and instants
    # cannot be opened on the profiler after the fact: obs only)
    recorded = [s.name for s in obs.spans() if s.ph == "X"
                and s.name.startswith("serve.")
                and not s.name.startswith("serve.request.")]
    assert sorted(recorded) == sorted(e[0] for e in serve)


def test_children_cover_each_boundary(tiny):
    eng = _engine(tiny, chunk=32)
    eng.run(_requests(tiny[0]))
    obs.enable()
    _serve(eng, _requests(tiny[0]))
    sp = obs.spans()
    steps = [s for s in sp if s.name == "serve.step_chunk"]
    assert steps
    for st in steps:
        kids = [s for s in sp if s.parent == "serve.step_chunk"
                and st.t0_ns <= s.t0_ns and s.t1_ns <= st.t1_ns]
        assert {k.name for k in kids} <= BOUNDARY_CHILDREN
        covered = sum(k.dur_ns for k in kids)
        assert covered >= 0.95 * st.dur_ns, (
            [(k.name, k.dur_ns) for k in kids], st.dur_ns)
    waits = {s.args["on"] for s in sp if s.name == "serve.device_wait"}
    assert waits == {"decode", "prefill_logits", "first_token"}
    assert {s.parent for s in sp if s.name == "serve.finish_admit"} == {
        "serve.prefill_chunk"}


def test_lifecycle_spans_tile_each_request(tiny):
    eng = _engine(tiny)
    eng.run(_requests(tiny[0]))
    obs.enable()
    rids = _serve(eng, _requests(tiny[0]))
    submitted = {rid: eng.sched.meta[rid]["t_submit"] for rid in rids}
    sp = obs.spans()
    retired = {s.args["req_id"]: s.t0_ns for s in sp
               if s.name == "serve.retire"}
    for rid in rids:
        mine = {s.name: s for s in sp if s.name.startswith("serve.request.")
                and s.args["req_id"] == rid}
        q, p, d = (mine["serve.request.queued"],
                   mine["serve.request.prefill"],
                   mine["serve.request.decode"])
        assert q.t1_ns == p.t0_ns and p.t1_ns == d.t0_ns
        assert q.t0_ns == int(submitted[rid] * 1e9)
        total = q.dur_ns + p.dur_ns + d.dur_ns
        assert total == d.t1_ns - q.t0_ns
        # retirement: the retire event is stamped just after the span ends
        assert 0 <= retired[rid] - d.t1_ns < 5_000_000
        assert d.args["state"] == "ok"
    kv = [s.args for s in sp if s.name == "serve.kv_pages"]
    assert kv and all(0 <= a["written"] <= a["reserved"] <= a["pool"]
                      for a in kv)


def test_written_blocks_counts_pages_holding_a_position():
    pool = BlockPool(16, 16)
    sched = Scheduler(2, pool=pool)
    sched.submit(0, prompt_len=20, max_new=40)
    sched.submit(1, prompt_len=3, max_new=4)
    sched.admissions()
    assert pool.used_blocks == 4 + 1 and sched.written_blocks() == 0
    sched.prefill_advance(0, 16)               # a chunk of request 0
    assert sched.written_blocks() == 1
    sched.prefill_advance(0, 4)
    sched.record_first(0, 7)                   # 20 prompt positions
    sched.record_first(1, 7)                   # 3
    assert sched.written_blocks() == 2 + 1
    sched.record_chunk(np.zeros((2, 13), np.int32))
    # request 0: 20 + 13 positions (the newest token is not yet written);
    # request 1 retired (4 tokens) and gave its page back
    assert sched.written_blocks() == 3 and pool.used_blocks == 4


def test_buffer_keeps_the_newest_and_counts_drops():
    tr = obs.Tracer()
    tr.enable()
    before = obs.counter("obs.trace.dropped").value
    n = obs.trace.CAPACITY + 12
    for i in range(n):
        tr.complete("s", i, i + 1)
    assert [s.t0_ns for s in tr.spans()] == list(range(12, n))
    assert obs.counter("obs.trace.dropped").value - before == 12
    with tr.span("last"):
        pass
    assert tr.spans()[-1].name == "last"
    assert len(tr.events()) == obs.trace.CAPACITY


def test_spans_are_windowed_by_their_end():
    obs.enable()
    obs.complete("a", 100, 200)
    obs.complete("b", 150, 400)
    obs.instant("c")
    assert [s.name for s in obs.spans(180, 300)] == ["a"]
    assert [s.name for s in obs.spans(300, None)] == ["b", "c"]
    assert obs.spans(0, 99) == []


def test_decode_chunk_carries_the_kernel_scopes(tiny):
    eng = _engine(tiny)
    args = (eng.params, eng.cache, eng.tokens, eng.pos, eng.keys, eng.temps,
            eng.top_ks, eng.block_tables)
    lowered = eng._chunk_fn.lower(*args)
    assert "module @jit_chunk_fn" in lowered.as_text()
    text = lowered.compile().as_text()
    for scope in ("/attention/", "/mlp/", "/head/", "/sample/"):
        assert f'op_name="jit(chunk_fn)' in text
        assert any(scope in line for line in text.splitlines()
                   if "op_name=" in line), scope
