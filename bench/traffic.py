"""Traffic from a data file of parameters (``bench/traffic/<name>.json``).

One general generator per ``kind``.  Sizes are the quantiles
``(i + 0.5) / n`` of the stated distribution, clipped to its bounds, and
Poisson gaps are the same quantiles of the exponential, each shuffled in
one fixed order.  So every ``--seed`` gets the same
sizes and arrivals in the same order, and the work in a run does not
depend on it: in a window of tens of seconds with requests of tens of
seconds, the order alone decides which requests fall in the window.  The
seed draws the tokens of every prompt (and, elsewhere, the weights).
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List, Optional, Tuple

import numpy as np


@dataclasses.dataclass
class Req:
    idx: int
    prompt: np.ndarray       # (prompt_len,) int32
    max_new: int
    client: Optional[int] = None


def quantiles(spec: dict, n: int) -> np.ndarray:
    """n sizes at the quantiles (i + 0.5) / n of ``spec``, clipped."""
    q = (np.arange(n) + 0.5) / n
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(p)) for p in q])
        v = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        v = spec["min"] + q * (spec["max"] - spec["min"] + 1)
        v = np.floor(v)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(v), spec["min"], spec["max"]).astype(np.int64)


def _shuffle(stream: int, values):
    """``values`` in a fixed order; one stream per quantity, so prompt
    lengths, answer lengths and gaps are shuffled independently."""
    return np.random.default_rng([0, stream]).permutation(values)


def _requests(spec: dict, n: int, seed: int, vocab: int) -> List[Req]:
    plens = _shuffle(0, quantiles(spec["prompt"], n))
    outs = _shuffle(1, quantiles(spec["output"], n))
    rng = np.random.default_rng(seed)
    return [Req(i, rng.integers(0, vocab, int(p), dtype=np.int32), int(o))
            for i, (p, o) in enumerate(zip(plens, outs))]


class OpenLoop:
    """Poisson arrivals at ``rate`` per second, independent of completions.
    Offsets run from the start of traffic (warm-up included)."""

    def __init__(self, spec: dict, seed: int, vocab: int, seconds: float):
        self.rate = float(spec["rate"])
        span = spec.get("warmup_s", 0) + seconds
        n = int(math.ceil(self.rate * span * 1.25)) + 8
        self.requests = _requests(spec, n, seed, vocab)
        q = (np.arange(n) + 0.5) / n
        gaps = _shuffle(2, -np.log1p(-q) / self.rate)
        self.offsets = np.cumsum(gaps) - gaps[0]

    def start(self) -> List[Tuple[float, Req]]:
        return [(float(o), r) for o, r in zip(self.offsets, self.requests)]

    def completed(self, req: Req, t: float) -> List[Tuple[float, Req]]:
        return []


class ClosedLoop:
    """``clients`` callers, each sending its next request the moment the
    previous one completes (no think time).  Offsets are absolute times
    once traffic has started."""

    def __init__(self, spec: dict, seed: int, vocab: int, seconds: float):
        self.clients = int(spec["clients"])
        per = int(spec["requests_per_client"])
        self.requests = _requests(spec, self.clients * per, seed, vocab)
        self._next = list(range(self.clients))
        for r in self.requests:
            r.client = r.idx % self.clients

    def start(self) -> List[Tuple[float, Req]]:
        return [(0.0, self.requests[c]) for c in range(self.clients)]

    def completed(self, req: Req, t: float) -> List[Tuple[float, Req]]:
        """The client's next request, due at ``t``; a client that has sent
        its whole list starts it again (new ids, same prompts)."""
        c = req.client
        self._next[c] += self.clients
        base = self.requests[self._next[c] % len(self.requests)]
        return [(t, Req(self._next[c], base.prompt, base.max_new, c))]


KINDS = {"poisson": OpenLoop, "closed_loop": ClosedLoop}


def make(spec: dict, seed: int, vocab: int, seconds: float):
    try:
        kind = KINDS[spec["kind"]]
    except KeyError:
        raise ValueError(f"unknown traffic kind {spec.get('kind')!r}; "
                         f"known: {sorted(KINDS)}") from None
    return kind(spec, seed, vocab, seconds)


def prompt_lengths(spec: dict, seconds: float) -> List[int]:
    """Every prompt length the mix can send, whatever the seed."""
    vocab = 2
    return sorted({int(r.prompt.shape[0])
                   for r in make(spec, 0, vocab, seconds).requests})
