"""Strategy-mining benchmark: do mined abstractions pay for themselves?

The pipeline under test (all of it repro.strategy):

  1. warm a tuning corpus — tune the reduce/map kernels at several shapes;
     every winner's derivation (``StrategyTrace``) lands in the cache;
  2. mine the corpus — anti-unify winning traces into parameter-holed
     ``Abstraction`` s, persisted beside the cache;
  3. tune a NEW shape with the abstractions seeding the search, and count
     candidate evaluations until the incumbent-best strategy is reached:
     ``seeded_order`` must need no more evals than plain enumeration
     (asserted: seeded <= unseeded, and strictly fewer when the winner's
     derivation matches a mined abstraction);
  4. replay the winner's trace on the naive spec and require the rebuilt
     term to be structurally identical (fingerprint) to the winner —
     derivations are deterministic, not descriptive;
  5. the generic space on the fused RMSNorm->matmul term (an op with no
     hand-written space anywhere in the repo) must be non-trivial: the
     strategy language covers terms the params vocabulary never met.

Usage:
  PYTHONPATH=src python benchmarks/strategy_bench.py [--smoke] [--out FILE]

Writes BENCH_strategy.json (``--out`` to override) and prints a summary.
The output embeds the winning ``strategy_trace``, so
``validate_trace.py --strategy BENCH_strategy.json`` checks its schema.
"""
from __future__ import annotations

import argparse
import json
import os
import tempfile
import time

CORPUS = [
    ("dot", {"n": 1024}), ("dot", {"n": 2048}),
    ("asum", {"n": 1024}), ("asum", {"n": 2048}),
    ("scal", {"n": 1024}), ("scal", {"n": 2048}),
    ("rmsnorm", {"rows": 64, "d": 128}),
    ("rmsnorm", {"rows": 128, "d": 128}),
]
CORPUS_FULL = CORPUS + [
    ("dot", {"n": 8192}), ("asum", {"n": 8192}), ("scal", {"n": 8192}),
    ("softmax", {"rows": 64, "d": 128}),
    ("softmax", {"rows": 128, "d": 256}),
]


def warm_corpus(cache_path: str, smoke: bool) -> int:
    from repro import autotune
    n = 0
    for kernel, shape in (CORPUS if smoke else CORPUS_FULL):
        autotune.tune(kernel, cache=cache_path, measure=False, **shape)
        n += 1
    return n


def mine_corpus(cache_path: str):
    from repro.autotune.cache import TuningCache
    from repro.strategy import mine
    abstractions = mine.mine(TuningCache(cache_path))
    assert abstractions, "mining the warmed corpus produced no abstractions"
    mine.save_abstractions(mine.abstractions_path(cache_path), abstractions)
    return abstractions


def evals_to_best(kernel: str, shape: dict, abstractions) -> dict:
    """Candidate evaluations until the incumbent-best strategy is reached,
    with and without abstraction seeding.

    Incumbent best = the analytic-rank winner for the (new) shape; the
    "evaluation order" is the space's enumeration order, against
    ``seeded_order`` of the same list.  Seeding must never be worse, and is
    strictly better whenever the winner instantiates a mined abstraction
    (non-matching candidates ahead of it — the naive spec, at least — are
    deferred)."""
    from repro.autotune import measure as measure_mod
    from repro.autotune import space as space_mod
    from repro.strategy import mine
    cands = space_mod.enumerate_space(kernel, **shape)
    best = measure_mod.rank_by_cost(cands)[0][0]
    unseeded = [c.params for c in cands].index(best.params) + 1
    seeded_cands = mine.seeded_order(cands, abstractions)
    seeded = [c.params for c in seeded_cands].index(best.params) + 1
    doc = best.trace_doc()
    hit = bool(doc) and any(mine.matches(a, doc) for a in abstractions)
    assert seeded <= unseeded, (seeded, unseeded)
    if hit:
        assert seeded < unseeded, \
            f"winner matches an abstraction but seeding saved nothing " \
            f"({seeded} vs {unseeded})"
    return {"kernel": kernel, "shape": shape, "winner": dict(best.params),
            "evals_unseeded": unseeded, "evals_seeded": seeded,
            "winner_matches_abstraction": hit, "strategy_trace": doc}


def replay_identity(kernel: str, shape: dict, winner_params: dict) -> None:
    """A recorded derivation replays to the exact same term (fingerprint)."""
    from repro import strategy as st
    from repro.autotune import space as space_mod
    cand = space_mod.candidate_from_params(kernel, winner_params, **shape)
    doc = cand.trace_doc()
    assert doc is not None
    spec, _ = st.spec_builder(kernel, **shape)()
    res = st.replay(doc, spec)
    assert res.ok, res.reason
    expr, _ = cand.build()
    assert st.fingerprint(res.phrase) == st.fingerprint(expr), \
        "replayed derivation diverged from the winner's term"


def fused_demo(smoke: bool) -> dict:
    """The generic space on the fused RMSNorm->matmul term."""
    from repro import strategy as st
    rows, d, n = (32, 64, 32) if smoke else (64, 128, 64)
    expr, _ = st.fused_rmsnorm_matmul(rows, d, n)
    space = st.generic_space(expr, blocks=(8, 16, 32), tiles=(16, 32, 64))
    assert len(space) >= 2, "generic space degenerated to the identity"
    rewrites = sorted({str(p.get("rewrite")) for p, _, _ in space})
    return {"rows": rows, "d": d, "n": n, "n_candidates": len(space),
            "rewrites": rewrites}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small corpus + shapes (CI)")
    ap.add_argument("--out", default="BENCH_strategy.json")
    ap.add_argument("--cache", default=None,
                    help="tuning-cache path (default: a fresh temp file)")
    args = ap.parse_args()

    cache_path = args.cache or os.path.join(
        tempfile.mkdtemp(prefix="strategy_bench_"), "tuning_cache.json")

    t0 = time.perf_counter()
    corpus_n = warm_corpus(cache_path, args.smoke)
    t_warm = time.perf_counter() - t0

    t0 = time.perf_counter()
    abstractions = mine_corpus(cache_path)
    t_mine = time.perf_counter() - t0

    new_shapes = ([("dot", {"n": 4096}), ("asum", {"n": 4096})] if args.smoke
                  else [("dot", {"n": 16384}), ("asum", {"n": 16384}),
                        ("scal", {"n": 16384})])
    seeding = [evals_to_best(k, s, abstractions) for k, s in new_shapes]
    for row in seeding:
        replay_identity(row["kernel"], row["shape"], row["winner"])

    fused = fused_demo(args.smoke)

    doc = {
        "smoke": bool(args.smoke),
        "corpus": {"tunes": corpus_n, "cache": cache_path,
                   "warm_s": round(t_warm, 3)},
        "mining": {"n_abstractions": len(abstractions),
                   "mine_s": round(t_mine, 3),
                   "abstractions": [a.describe() for a in abstractions]},
        "seeding": seeding,
        "fused_rmsnorm_matmul": fused,
    }
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)

    print(f"strategy_bench: corpus={corpus_n} tunes ({t_warm:.2f}s), "
          f"mined {len(abstractions)} abstraction(s) ({t_mine:.2f}s)")
    print(f"  top: {abstractions[0].describe()}")
    for row in seeding:
        print(f"  {row['kernel']} {row['shape']}: evals to best "
              f"{row['evals_seeded']} seeded vs {row['evals_unseeded']} "
              f"unseeded (match={row['winner_matches_abstraction']})")
    print(f"  fused rmsnorm@matmul generic space: "
          f"{fused['n_candidates']} candidates, rewrites={fused['rewrites']}")
    print(f"  wrote {args.out}")


if __name__ == "__main__":
    main()
