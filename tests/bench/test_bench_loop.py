"""The client loop through its functions, and whole runs of the harness at
smoke widths on the CPU: a sound run is correct, and a run with a fault
planted in its timed path is not."""
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, loop, system
from bench_cells import CPU_PEAKS, smoke_cell


# ---------------------------------------------------------------------------
# the loop, on a fake engine and a fake clock
# ---------------------------------------------------------------------------

class Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def sleep(self, dt):
        self.t += dt


@dataclasses.dataclass
class R:
    idx: int
    prompt: np.ndarray
    max_new: int


class FakeEngine:
    """Each boundary takes 0.125 s, admits everything queued, gives each
    new request its prefill token and every request up to ``chunk`` more."""
    chunk = 2

    def __init__(self, clock):
        self.clock, self.n, self.want = clock, {}, {}
        self.queue, self.active = [], set()

    def submit(self, prompt, max_new):
        rid = len(self.want)
        self.want[rid] = max_new
        self.queue.append(rid)
        return rid

    def step(self):
        self.clock.t += 0.125
        for rid in self.queue:
            self.n[rid] = 1
            self.active.add(rid)
        self.queue = []
        done = []
        for rid in sorted(self.active):
            self.n[rid] = min(self.n[rid] + self.chunk, self.want[rid])
            if self.n[rid] == self.want[rid]:
                done.append(rid)
        self.active -= set(done)
        return done

    def count(self, rid):
        return self.n.get(rid, 0)

    def busy_share(self):
        return len(self.active) / 4


class TwoRequests:
    def __init__(self):
        self.reqs = [R(0, np.zeros(10, np.int32), 5),
                     R(1, np.zeros(20, np.int32), 9)]

    def start(self):
        return [(0.0, self.reqs[0]), (0.05, self.reqs[1])]

    def completed(self, req, t):
        return []


def test_loop_times_requests_from_their_due_time():
    clock = Clock()
    win = loop.drive(FakeEngine(clock), TwoRequests(), warmup_s=0.0,
                     seconds=1.0, clock=clock, sleep=clock.sleep)
    f0, f1 = win.flights
    assert f0.stamps == [(0.125, 3), (0.25, 5)]
    assert f1.submitted == 0.125           # due 0.05, a boundary late
    assert [n for _, n in f1.stamps] == [3, 5, 7, 9]
    # request 0: prompt 10, prefill token then decode from position 10
    b0 = win.boundaries[0]
    assert b0.prefills == [10] and b0.decode == [[11], [12]]
    assert win.boundaries[1].prefills == [20]
    assert win.boundaries[1].decode == [[13, 21], [14, 22]]
    e = loop.end_to_end(win)
    assert e["window_s"] == pytest.approx(1.0)       # idle to the target
    assert e["tokens"] == 14
    assert e["output_tok_s"] == pytest.approx(14.0)
    ttft = [0.125, 0.25 - 0.05]
    assert e["ttft_p95_s"] == pytest.approx(np.percentile(ttft, 95))
    # tokens after the first stamp over the time after it: 2 per boundary
    assert e["tpot_p95_s"] == pytest.approx(0.0625)
    assert e["lateness_max_s"] == pytest.approx(0.075)
    assert e["completed"] == 2


def test_a_stalled_request_counts_its_wait_in_the_tail():
    clock = Clock()

    class Stuck(FakeEngine):
        def step(self):
            self.clock.t += 0.5
            return []

    win = loop.drive(Stuck(clock), TwoRequests(), warmup_s=0.0,
                     seconds=1.0, clock=clock, sleep=clock.sleep)
    e = loop.end_to_end(win)
    # no first token ever: each request counts the time it waited
    assert e["ttft_n"] == 2
    assert e["ttft_p95_s"] == pytest.approx(
        np.percentile([1.0, 1.0 - 0.05], 95))
    assert e["tokens"] == 0 and e["tpot_p95_s"] is None


def test_trace_hooks_bracket_the_last_boundaries():
    clock, calls = Clock(), []

    class Busy(TwoRequests):
        def start(self):
            return [(0.0, R(0, np.zeros(4, np.int32), 1000))]

    win = loop.drive(FakeEngine(clock), Busy(), warmup_s=0.0, seconds=1.0,
                     clock=clock, sleep=clock.sleep, trace_from=0.375,
                     on_trace=calls.append)
    assert calls == [True, False]
    assert win.trace_span == (5, 7) and len(win.boundaries) == 8
    assert win.boundaries[5].t0 == 0.625


# ---------------------------------------------------------------------------
# whole runs at smoke widths, the program in float32
# ---------------------------------------------------------------------------

class Faulty(system.Engine):
    """The chip engine with a fault planted in its decode chunk once warm:
    only the measured window runs broken."""
    fault = None

    def warm(self, lengths):
        out = super().warm(lengths)
        inner = self.eng._chunk_fn.__wrapped__   # the unjitted chunk
        vocab = self.model.cfg.vocab

        def broken(params, cache, *a):
            c, t, p, k, toks, bad = inner(params, cache, *a)
            if self.fault == "token":      # a token altered where produced
                toks = toks.at[:, 0].set((toks[:, 0] + 1) % vocab)
            elif self.fault == "routing":  # two slots' tokens swapped
                toks = toks[jnp.array([1, 0, 2, 3])]
            elif self.fault == "state":    # the step returns its KV unchanged
                c = cache
            return c, t, p, k, toks, bad

        self.eng._chunk_fn = jax.jit(broken)
        return out


def run(cell, fault=None, **kw):
    cls = type("F", (Faulty,), {"fault": fault}) if fault else system.Engine
    return harness.run(cell, 2**31 + 17, 2.0, kw.pop("trace", False),
                       t_process=time.perf_counter(), make_engine=cls,
                       peaks=CPU_PEAKS, **kw)


def test_sound_run_is_correct():
    c = smoke_cell("qwen3-4b.decode")
    limit = c["check"]["limits"]["max_gap"]["limit"]
    out = run(c, trace=True)
    res = out["result"]
    assert res["correct"], res["check"]
    assert out["numbers"]["max_gap"] <= limit
    assert out["info"]["compiles_in_window"] == 0
    assert out["info"]["recompiles_after_warm"] == 0
    assert res["device"]["platform"] == "cpu"
    assert list(res)[-1] == "check"
    assert set(res["metrics"]) <= {m["name"] for m in c["per_layer"]}
    assert "engine.slot_occupancy" in res["metrics"]


@pytest.mark.parametrize("fault", ["token", "routing", "state"])
def test_a_broken_timed_path_is_not_correct(fault):
    out = run(smoke_cell("qwen3-4b.decode"), fault)
    assert not out["result"]["correct"], out["numbers"]
