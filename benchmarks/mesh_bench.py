"""Mesh strategy benchmark: per-op shardmap dispatch and sharded serving.

Runs on an 8-device CPU mesh (``JAX_PLATFORMS=cpu`` and
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` in the environment),
and measures two things:

  ops     — the six tuned kernels dispatched through ``dpia-shardmap``
            (mesh-level DPIA strategies -> shard_map + collectives) vs the
            single-device ``dpia-jnp`` pipeline and the plain XLA oracle:
            correctness (asserted) and wall time per call (reported);
  serving — ``serve.ShardedEngine`` (slot axis sharded over ``data``) vs the
            unsharded ``ContinuousEngine`` on the same traffic:
            token-identity (asserted), recompiles after warm-up (asserted
            zero), and tokens/s (reported).

Host-CPU "devices" share the same cores, so shardmap timings here measure
*dispatch overhead*, not speedup — the point of the benchmark is that the
mesh path is correct, cache-stable, and recompile-free; speedups come from
real accelerators.  Asserts cover exactly those invariants (``--no-assert``
to report only).

Usage:
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    PYTHONPATH=src python benchmarks/mesh_bench.py [--smoke] [--out FILE]

Writes BENCH_mesh.json (``--out`` to override) and prints a summary.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.mesh import make_mesh


def _best_of(fn, repeats: int = 5) -> float:
    jax.block_until_ready(fn())  # warm/compile
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def bench_ops(mesh, smoke: bool, repeats: int) -> dict:
    from repro import compiler
    from repro.kernels import ops

    n = 1 << 14 if smoke else 1 << 18
    rows, d = (64, 128) if smoke else (256, 512)
    m, k, nn = (64, 128, 64) if smoke else (256, 512, 256)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(n), "float32")
    y = jnp.asarray(rng.randn(n), "float32")
    X = jnp.asarray(rng.randn(rows, d), "float32")
    w = jnp.asarray(rng.randn(d), "float32")
    A = jnp.asarray(rng.randn(m, k), "float32")
    B = jnp.asarray(rng.randn(k, nn), "float32")

    cases = [
        ("dot", lambda impl: ops.dot(x, y, impl=impl)),
        ("asum", lambda impl: ops.asum(x, impl=impl)),
        ("scal", lambda impl: ops.scal(2.5, x, impl=impl)),
        ("matmul", lambda impl: ops.matmul(A, B, impl=impl)),
        ("rmsnorm", lambda impl: ops.rmsnorm(X, w, impl=impl)),
        ("softmax", lambda impl: ops.softmax(X, impl=impl)),
    ]

    out = {}
    print(f"# ops on mesh {dict(mesh.shape)} (n={n}, rows={rows}, "
          f"mkn={m}x{k}x{nn})")
    with compiler.options(mesh=mesh):
        for name, call in cases:
            want = np.asarray(call("xla"))
            got = np.asarray(call("dpia-shardmap"))
            np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3,
                                       err_msg=name)
            t_mesh = _best_of(lambda: call("dpia-shardmap"), repeats)
            t_one = _best_of(lambda: call("dpia-jnp"), repeats)
            t_xla = _best_of(lambda: call("xla"), repeats)
            out[name] = {"shardmap_us": t_mesh * 1e6,
                         "dpia_jnp_us": t_one * 1e6, "xla_us": t_xla * 1e6}
            print(f"  {name:8s} shardmap {t_mesh * 1e6:9.1f} us | "
                  f"dpia-jnp {t_one * 1e6:9.1f} us | "
                  f"xla {t_xla * 1e6:9.1f} us   (oracle-equal)")

    mesh_keys = [kk for kk in compiler.executor_cache().keys()
                 if "|shardmap|" in kk]
    out["mesh_executor_keys"] = len(mesh_keys)
    print(f"  mesh-keyed executors staged: {len(mesh_keys)}")
    return out


def bench_serving(mesh, smoke: bool, repeats: int, do_assert: bool) -> dict:
    from repro.models.common import ModelConfig
    from repro.models.transformer import Model
    from repro.serve.engine import ContinuousEngine, Request, ShardedEngine

    cfg = ModelConfig(name="mesh-bench", family="dense",
                      n_layers=2 if smoke else 4,
                      d_model=64 if smoke else 128, n_heads=4, n_kv_heads=2,
                      d_ff=128 if smoke else 256, vocab=256, dtype="float32",
                      remat=False, max_seq=128)
    model = Model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    slots = 8
    chunk = 8
    max_new = 16 if smoke else 32

    def reqs():
        key = jax.random.PRNGKey(42)
        return [Request(
            prompt=jax.random.randint(jax.random.fold_in(key, i),
                                      (8 + 2 * (i % 4),), 0, cfg.vocab),
            max_new_tokens=max_new) for i in range(slots + 4)]

    key = jax.random.PRNGKey(7)
    cont = ContinuousEngine(model, params, max_seq=cfg.max_seq, slots=slots,
                            chunk=chunk)
    shard = ShardedEngine(model, params, max_seq=cfg.max_seq, slots=slots,
                          chunk=chunk, mesh=mesh)

    want = cont.run(reqs(), key=key)        # warm + oracle
    got = shard.run(reqs(), key=key)        # warm + identity check
    identical = got == want
    compiles_warm = shard.decode_cache_misses()

    def run_cont():
        return cont.run(reqs(), key=key)

    def run_shard():
        return shard.run(reqs(), key=key)

    t_cont = t_shard = float("inf")
    n_tok = sum(len(o) for o in want)
    for _ in range(repeats):                 # interleaved best-of-N
        t0 = time.perf_counter()
        run_cont()
        t_cont = min(t_cont, time.perf_counter() - t0)
        t0 = time.perf_counter()
        run_shard()
        t_shard = min(t_shard, time.perf_counter() - t0)
    recompiles = shard.decode_cache_misses() - compiles_warm

    print(f"# serving: slots={slots} over {dict(mesh.shape)} "
          f"({len(reqs())} requests x {max_new} new tokens)")
    print(f"  continuous  {n_tok / t_cont:9.1f} tok/s")
    print(f"  sharded     {n_tok / t_shard:9.1f} tok/s   "
          f"(token-identical: {identical}, decode compiles "
          f"{compiles_warm}, recompiles after warm-up: {recompiles})")

    if do_assert:
        assert identical, "ShardedEngine tokens diverged from ContinuousEngine"
        assert recompiles == 0, f"{recompiles} recompiles after warm-up"
        assert compiles_warm == 1, f"{compiles_warm} decode chunk compiles"
        print("  asserts OK (token identity, 1 chunk compile, 0 recompiles)")

    return {"slots": slots, "chunk": chunk, "tokens": n_tok,
            "continuous_tok_s": n_tok / t_cont,
            "sharded_tok_s": n_tok / t_shard,
            "token_identical": bool(identical),
            "decode_compiles_warm": compiles_warm,
            "recompiles_after_warmup": recompiles}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="short runs (CI): small shapes, fewer repeats")
    ap.add_argument("--out", default="BENCH_mesh.json")
    ap.add_argument("--no-assert", action="store_true",
                    help="report only; do not enforce identity/recompiles")
    args = ap.parse_args()

    n_dev = len(jax.devices())
    if n_dev < 8:
        raise SystemExit(f"mesh_bench needs 8 forced host devices, got "
                         f"{n_dev} — run with JAX_PLATFORMS=cpu and "
                         f"XLA_FLAGS=--xla_force_host_platform_device_count=8")
    mesh = make_mesh((8,), ("data",))
    repeats = 2 if args.smoke else 5

    ops_doc = bench_ops(mesh, args.smoke, repeats)
    serve_doc = bench_serving(mesh, args.smoke, repeats,
                              do_assert=not args.no_assert)

    doc = {"mesh": "data=8", "smoke": bool(args.smoke),
           "ops": ops_doc, "serving": serve_doc}
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
    print(f"  wrote {args.out}")


if __name__ == "__main__":
    main()
