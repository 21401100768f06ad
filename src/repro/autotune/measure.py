"""Compile-and-time refinement of strategy candidates.

The analytic model (cost.py) ranks the whole space for free; this module
takes the top-k and actually pushes each through the formal pipeline
(Stage I -> II -> III, jnp or pallas-interpret backend), times it, and
reports microseconds per call.  Candidates that fail to compile or run
(e.g. a rewrite the chosen backend cannot lower) are skipped, not fatal —
the tuner falls back to the analytic ranking among survivors.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro import obs
from repro.core.dpia import phrases as P
from repro.core.dpia.types import dtype_of, shape_of

from .space import Candidate


def args_for(arg_vars: Sequence[P.Var], seed: int = 0) -> Tuple:
    """Deterministic random inputs matching the argument Vars' data types."""
    import jax.numpy as jnp
    rng = np.random.RandomState(seed)
    out = []
    for v in arg_vars:
        d = v.t.d
        shp = shape_of(d)
        dt = dtype_of(d)
        if dt.startswith("int"):
            a = rng.randint(0, 7, size=shp)
        else:
            a = rng.randn(*shp)
        out.append(jnp.asarray(a, dt))
    return tuple(out)


def compile_candidate(cand: Candidate, backend: str = "jnp",
                      compile_kw: Optional[dict] = None):
    """(jitted callable, concrete args) for a candidate, via the staged
    pipeline: the candidate becomes a ``repro.compiler.Program`` and runs
    ``check() -> lower() -> compile(backend)``.

    ``compile_kw`` carries backend compile arguments (the shardmap
    backend's ``mesh=``); mesh-level terms go straight to Stage III —
    shard_map consumes the functional term, and the per-shard bodies are
    checked by the inner backend."""
    prog = cand.program()
    kw = dict(compile_kw or {})
    if kw.get("mesh") is not None or backend == "shardmap":
        fn = prog.compile(backend, jit=True, **kw)
    else:
        fn = prog.check().lower().compile(backend, jit=True, **kw)
    return fn, args_for(prog.arg_vars)


def lowers_for_chip(cand: Candidate) -> bool:
    """Whether the candidate's Pallas kernels lower for the TPU: the program
    is traced with ``interpret=False`` (nothing is compiled), and the
    translation's NotImplementedError for an access Mosaic cannot tile
    means it does not."""
    import jax
    prog = cand.program()
    fn = prog.check().lower().compile("pallas", interpret=False, jit=False)
    try:
        jax.eval_shape(fn, *(jax.ShapeDtypeStruct(shape_of(v.t.d),
                                                  dtype_of(v.t.d))
                             for v in prog.arg_vars))
    except NotImplementedError:
        return False
    return True


def time_callable(fn, args, iters: int = 5, warmup: int = 1) -> float:
    """Median wall time in microseconds per call (after warmup/compile)."""
    import jax
    for _ in range(max(1, warmup)):
        jax.block_until_ready(fn(*args))
    samples = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        samples.append((time.perf_counter() - t0) * 1e6)
    return float(np.median(samples))


def measure_candidates(cands: Sequence[Candidate], *, backend: str = "jnp",
                       iters: int = 5, seed: int = 0,
                       verify_against: Optional[Candidate] = None,
                       compile_kw: Optional[dict] = None
                       ) -> Dict[str, float]:
    """Time each candidate; returns {params_key: us}.  Failures are dropped.

    When ``verify_against`` is given, every candidate's output is checked
    against that reference candidate's output (strategy preservation as a
    runtime assertion) and mismatching candidates are discarded.
    ``compile_kw`` is threaded to every compile (e.g. shardmap's mesh).
    """
    import jax

    ref_out = None
    if verify_against is not None:
        try:
            rfn, rargs = compile_candidate(verify_against, backend,
                                           compile_kw)
            ref_out = np.asarray(jax.block_until_ready(rfn(*rargs)))
        except Exception:
            ref_out = None

    out: Dict[str, float] = {}
    for c in cands:
        with obs.span("autotune.measure_candidate", backend=backend,
                      params=c.params_key()):
            try:
                fn, args = compile_candidate(c, backend, compile_kw)
                if ref_out is not None:
                    got = np.asarray(jax.block_until_ready(fn(*args)))
                    np.testing.assert_allclose(got, ref_out, rtol=1e-3,
                                               atol=1e-4)
                out[c.params_key()] = time_callable(fn, args, iters=iters)
            except Exception:
                obs.event("autotune.candidate_failed", backend=backend,
                          params=c.params_key())
                continue
    return out


def rank_by_cost(cands: Sequence[Candidate], hw=None
                 ) -> List[Tuple[Candidate, float]]:
    """(candidate, predicted seconds) sorted best-first; unbuildable or
    un-costable candidates sort last with +inf.

    ``hw`` is the roofline HwModel; None resolves the per-platform preset
    (``cost.hw_model()``), so analytic rankings use the hardware actually
    under the process instead of the single TPU-shaped default."""
    from . import cost as cost_mod
    if hw is None:
        hw = cost_mod.hw_model()
    scored = []
    for c in cands:
        try:
            expr, _ = c.build()
            s = cost_mod.predicted_seconds(expr, hw)
        except Exception:
            s = float("inf")
        scored.append((c, s))
    scored.sort(key=lambda cs: (cs[1], cs[0].params_key()))
    return scored
