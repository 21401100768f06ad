"""Traffic generators: deterministic for a seed, the same sizes for every
seed, lengths within their clips."""
import numpy as np
import pytest

from bench import spec, traffic
from bench_cells import CELLS


@pytest.mark.parametrize("cell", CELLS)
def test_generator_is_deterministic_for_a_seed(cell):
    t = spec.cell(cell)["traffic"]
    a = traffic.make(t, 2**40 + 3, 1000, 30.0)
    b = traffic.make(t, 2**40 + 3, 1000, 30.0)
    assert [r.max_new for r in a.requests] == [r.max_new for r in b.requests]
    for x, y in zip(a.requests, b.requests):
        np.testing.assert_array_equal(x.prompt, y.prompt)
    assert [(o, r.idx) for o, r in a.start()] == [
        (o, r.idx) for o, r in b.start()]


@pytest.mark.parametrize("cell", CELLS)
def test_lengths_keep_their_clips(cell):
    t = spec.cell(cell)["traffic"]
    g = traffic.make(t, 7, 1000, 51.0)
    plens = [len(r.prompt) for r in g.requests]
    outs = [r.max_new for r in g.requests]
    assert t["prompt"]["min"] <= min(plens) and max(plens) <= t["prompt"][
        "max"]
    assert t["output"]["min"] <= min(outs) and max(outs) <= t["output"][
        "max"]
    assert all(r.prompt.dtype == np.int32 and r.prompt.max() < 1000
               for r in g.requests)


@pytest.mark.parametrize("cell", CELLS)
def test_every_seed_gets_the_same_schedule_and_other_tokens(cell):
    t = spec.cell(cell)["traffic"]
    a = traffic.make(t, 1, 1000, 51.0)
    b = traffic.make(t, 2, 1000, 51.0)
    assert [(len(r.prompt), r.max_new) for r in a.requests] == [
        (len(r.prompt), r.max_new) for r in b.requests]
    assert [o for o, _ in a.start()] == [o for o, _ in b.start()]
    assert not np.array_equal(a.requests[0].prompt, b.requests[0].prompt)
    # the order is a shuffle, not sorted
    assert [len(r.prompt) for r in a.requests] != sorted(
        len(r.prompt) for r in a.requests)
    assert set(traffic.prompt_lengths(t, 51.0)) == {
        len(r.prompt) for r in a.requests}


def test_lognormal_quantiles_hold_the_median():
    spec_ = {"dist": "lognormal", "median": 128, "sigma": 0.6, "min": 64,
             "max": 512}
    q = traffic.quantiles(spec_, 101)
    assert q[50] == 128 and q.min() == 64 and q.max() <= 512
    assert list(q) == sorted(q)


def test_poisson_arrivals_keep_the_rate_and_cover_the_window():
    t = dict(spec.cell("yi-9b-l24.rag")["traffic"], rate=2.5, warmup_s=4)
    g = traffic.make(t, 5, 1000, 51.0)
    offs = [o for o, _ in g.start()]
    assert offs == sorted(offs) and offs[0] == 0.0
    assert offs[-1] > 4 + 51
    n_in = sum(1 for o in offs if o < 55)
    assert 0.8 * 2.5 * 55 < n_in < 1.2 * 2.5 * 55


def test_closed_loop_sends_the_next_turn_on_completion():
    t = spec.cell("qwen3-4b.decode")["traffic"]
    g = traffic.make(t, 9, 1000, 51.0)
    first = g.start()
    assert len(first) == t["clients"] and all(o == 0 for o, _ in first)
    _, r = first[3]
    (due, nxt), = g.completed(r, 12.5)
    assert due == 12.5 and nxt.client == r.client and nxt.idx != r.idx
    seen = {r.idx}
    for _ in range(3 * t["requests_per_client"]):
        (_, nxt), = g.completed(nxt, 1.0)
        assert nxt.client == r.client
        seen.add(nxt.idx)
    assert len(seen) > t["requests_per_client"]   # cycles on with new ids
