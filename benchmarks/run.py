"""Benchmark harness — one section per paper table/figure + framework-level
measurements.  Prints ``name,us_per_call,derived`` CSV at the end.

  fig7      — formal-translation overhead on scal/asum/dot/gemv (paper 7.2)
  strategy  — strategy-choice spread on gemv (paper 2.1 motivation)
  kernels   — Pallas kernel vs XLA wall time (interpret-mode, CPU)
  roofline  — per (arch x shape) terms from the multi-pod dry-run
"""
from __future__ import annotations

import time
from typing import List

import jax
import jax.numpy as jnp
import numpy as np


def _time(fn, args, iters=10) -> float:
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6


def bench_strategy_spread(csv_rows: List[str]) -> None:
    from repro import compiler
    from repro.kernels import dpia_blas
    print("# strategy spread: the same gemv under different strategies")
    m, n = 1024, 1024
    rng = np.random.RandomState(0)
    A = jnp.asarray(rng.randn(m, n), "float32")
    x = jnp.asarray(rng.randn(n), "float32")
    for label, build in [
        ("naive", lambda: dpia_blas.naive_gemv(m, n)),
        ("rowblock64", lambda: dpia_blas.strategy_gemv(m, n, 64)),
        ("rowblock256", lambda: dpia_blas.strategy_gemv(m, n, 256)),
    ]:
        prog = compiler.Program.from_builder(build, name=f"gemv/{label}")
        fn = prog.check().lower().compile("jnp")
        t = _time(fn, (A, x))
        print(f"  gemv/{label:12s} {t:9.1f} us")
        csv_rows.append(f"strategy/gemv/{label},{t:.1f},")


def bench_autotune(csv_rows: List[str]) -> None:
    """Tuned-vs-default strategy choice (repro.autotune, jnp backend)."""
    import tempfile

    from repro import autotune
    from repro.autotune import space
    from repro.autotune.measure import compile_candidate, time_callable
    print("# autotune: cost-model-guided strategy vs the hard-coded default")
    cache = tempfile.mktemp(suffix=".json")  # fresh search for the benchmark
    for kernel, shape in [("dot", dict(n=8192)),
                          ("matmul", dict(m=512, k=512, n=512)),
                          ("rmsnorm", dict(rows=512, d=1024))]:
        res = autotune.tune(kernel, cache=cache, measure=True, top_k=3,
                            iters=5, **shape)
        shp = "x".join(str(v) for _, v in sorted(shape.items()))
        if res.measured_us is None:
            # every measured candidate failed to compile/run here
            print(f"  {kernel}/{shp:12s} analytic-only pick {res.params} "
                  f"(no candidate measurable on this backend)")
            continue
        default = space.candidate_from_params(
            kernel, space.default_params(kernel, **shape), **shape)
        t_def = res.timings.get(default.params_key())
        if t_def is None:
            try:
                fn, args = compile_candidate(default)
                t_def = time_callable(fn, args, iters=5)
            except Exception:
                t_def = float("nan")
        print(f"  {kernel}/{shp:12s} default {t_def:9.1f} us   "
              f"tuned {res.measured_us:9.1f} us   {res.params}")
        csv_rows.append(f"autotune/{kernel}/{shp}/default,{t_def:.1f},")
        # ';' inside the derived column: its values must stay comma-free
        params_s = space.params_key(res.params).replace(",", ";")
        csv_rows.append(
            f"autotune/{kernel}/{shp}/tuned,{res.measured_us:.1f},"
            f"params={params_s}")


def bench_kernels(csv_rows: List[str]) -> None:
    from repro.kernels import ref
    from repro.kernels.rmsnorm import rmsnorm
    print("# kernels: rmsnorm pallas(interpret) vs xla — correctness-parity "
          "timing (interpret mode emulates, not a TPU speed claim)")
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(512, 1024), "float32")
    w = jnp.asarray(rng.randn(1024), "float32")
    t_xla = _time(jax.jit(ref.rmsnorm), (x, w))
    t_pl = _time(lambda a, b: rmsnorm(a, b), (x, w))
    print(f"  rmsnorm/xla    {t_xla:9.1f} us")
    print(f"  rmsnorm/pallas {t_pl:9.1f} us (interpret)")
    csv_rows.append(f"kernel/rmsnorm/xla,{t_xla:.1f},")
    csv_rows.append(f"kernel/rmsnorm/pallas_interpret,{t_pl:.1f},")


def bench_train_step(csv_rows: List[str]) -> None:
    from repro.launch.mesh import make_mesh
    from repro.models.common import ModelConfig
    from repro.models.transformer import Model
    from repro.train.step import (make_train_state, make_train_step,
                                  state_specs)
    print("# train step: ~25M dense LM, CPU wall time per step")
    cfg = ModelConfig(name="bench-25m", family="dense", n_layers=6,
                      d_model=512, n_heads=8, n_kv_heads=4, d_ff=1536,
                      vocab=8192, dtype="float32", remat=False, max_seq=128)
    model = Model(cfg)
    mesh = make_mesh((1, 1), ("data", "model"), jax.devices()[:1])
    state = make_train_state(model, jax.random.PRNGKey(0))
    st_spec = state_specs(state, mesh, cfg)
    _, jit_with, _ = make_train_step(model, mesh)
    step = jit_with(st_spec)
    batch = {"tokens": jnp.zeros((4, 128), jnp.int32),
             "labels": jnp.zeros((4, 128), jnp.int32)}
    state, m = step(state, batch)
    jax.block_until_ready(m["loss"])
    t0 = time.perf_counter()
    iters = 3
    for _ in range(iters):
        state, m = step(state, batch)
    jax.block_until_ready(m["loss"])
    t = (time.perf_counter() - t0) / iters * 1e6
    toks = 4 * 128 / (t / 1e6)
    print(f"  train_step/25m {t:9.1f} us  ({toks:.0f} tok/s on 1 CPU core)")
    csv_rows.append(f"train_step/25m,{t:.1f},tok_per_s={toks:.0f}")


def main() -> None:
    csv_rows: List[str] = []

    from benchmarks import fig7_overhead, roofline
    fig7_overhead.run(csv_rows)
    print()
    bench_strategy_spread(csv_rows)
    print()
    bench_autotune(csv_rows)
    print()
    bench_kernels(csv_rows)
    print()
    bench_train_step(csv_rows)
    print()
    results = roofline.load()
    if results:
        roofline.print_table(results, "single", csv_rows)
        print()
        roofline.print_table(results, "multi", csv_rows)
    else:
        print("# roofline: run `python -m repro.launch.dryrun` first")

    print("\nname,us_per_call,derived")
    for row in csv_rows:
        print(row)


if __name__ == "__main__":
    main()
