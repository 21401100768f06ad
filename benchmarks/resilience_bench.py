"""Resilience benchmark: the fault-injection soak behind docs/resilience.md.

Drives the continuous-batching engine through a deterministic fault
schedule (``repro.testing.faults``) and asserts the three resilience
claims as *measured* outcomes, not code review:

  1. **zero crashes** — every phase runs to completion under injected
     NaN logits, transient chunk errors, stragglers, pool exhaustion,
     pool corruption, executor-build failures, and a corrupted tuning
     cache;
  2. **token identity for the innocent** — every request the faults did
     not target streams tokens bitwise-identical to the fault-free
     static-batch oracle, co-batched with the poisoned ones;
  3. **visible degradation for the rest** — faulted requests end in a
     terminal non-``ok`` state (never silently wrong), and every strategy
     fallback appears in obs provenance with origin ``degraded(a->b)``.

Phases (``--smoke`` keeps A + E and trims the request mix; the default
soak runs all of them):

  A  serving faults  — NaN prefill, NaN decode, transient chunk errors,
                       a straggler chunk, and an expired deadline, all in
                       one traffic mix;
  B  paged faults    — pool exhaustion (deferral, not drop) and a NaN
                       quarantine whose scrubbed pages are reused;
  C  pool corruption — paged -> dense degradation mid-traffic;
  D  kernel ladder   — executor build failures: tuned -> default -> jnp;
  E  artefact heal   — a corrupted tuning-cache record is quarantined at
                       load and rebuilt by the next ``tune()``;
  F  host loss       — (``--host-loss``, needs an 8-device platform) a
                       2-host ShardedEngine loses host 1 mid-decode: its
                       slots evacuate to the queue front, the mesh shrinks
                       ``data=8 -> data=4`` (recorded as provenance origin
                       ``degraded(mesh(...))`` + exactly ONE ``host_lost``
                       flight dump per loss event), and every request —
                       survivor and evacuee — retires token-identical to
                       the fault-free oracle; the checksummed scheduler
                       journal (``--journal-out``) verifies and replays.
                       A clean sharded run first proves zero dumps and
                       zero degradations without the fault.

The bench also exercises the flight recorder end to end: a clean phase
must produce ZERO dumps, and every request that ends ``failed``/``timeout``
must have a matching ``request_<state>`` dump attributing it by req_id
(``--flight-dir`` additionally writes each dump as a ``flight-*.json``
artefact for ``validate_trace.py --flight`` + CI upload).

Usage:
  PYTHONPATH=src python benchmarks/resilience_bench.py [--smoke]
      [--out FILE] [--trace FILE] [--metrics-out FILE]
      [--flight-dir DIR] [--no-assert]

Writes BENCH_resilience.json; ``--trace``/``--metrics-out`` export the
obs trace/metrics for ``benchmarks/validate_trace.py``.
"""
from __future__ import annotations

import argparse
import json
import os
import time
import warnings

import jax
import jax.numpy as jnp


def _mk_model():
    from repro.models.common import ModelConfig
    from repro.models.transformer import Model
    cfg = ModelConfig(name="resil-bench", family="dense", n_layers=2,
                      d_model=32, n_heads=4, n_kv_heads=2, d_ff=64,
                      vocab=128, dtype="float32", remat=False, max_seq=64)
    model = Model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    return cfg, model, params


def _mk_requests(cfg, n, Request):
    key = jax.random.PRNGKey(7)
    return [Request(
        prompt=jax.random.randint(jax.random.fold_in(key, 100 + i),
                                  (5 + 3 * i,), 0, cfg.vocab),
        max_new_tokens=4 + 3 * i, temperature=0.0) for i in range(n)]


def _drive(eng, reqs, key):
    """submit + step_chunk to idle; returns per-request RequestResults."""
    with eng._options_scope():
        eng._run_key = key
        rids = [eng.submit(r, stream=i) for i, r in enumerate(reqs)]
        while not eng.sched.idle:
            eng.step_chunk()
    return [eng.take_result(rid) for rid in rids]


def _tally(results, oracle, targeted, doc, phase):
    """Check the identity/terminal-state contract for one phase."""
    clean_ok, clean_bad, states = 0, 0, {}
    for i, r in enumerate(results):
        states[i] = r.state
        if i in targeted:
            assert r.state != "ok", \
                f"{phase}: faulted request {i} ended ok"
        else:
            if list(r.tokens) == oracle[i]:
                clean_ok += 1
            else:
                clean_bad += 1
    doc["phases"][phase] = {
        "states": {str(k): v for k, v in states.items()},
        "clean_identical": clean_ok,
        "clean_diverged": clean_bad,
    }
    assert clean_bad == 0, f"{phase}: {clean_bad} clean requests diverged"
    return clean_ok


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: NaN request + corrupt cache record only")
    ap.add_argument("--out", default="BENCH_resilience.json")
    ap.add_argument("--trace", default=None, metavar="FILE",
                    help="enable span tracing; export Chrome trace JSON")
    ap.add_argument("--metrics-out", default=None, metavar="FILE",
                    help="export the metrics registry snapshot as JSON")
    ap.add_argument("--flight-dir", default=None, metavar="DIR",
                    help="write flight-recorder dumps as flight-*.json "
                         "artefacts into DIR")
    ap.add_argument("--host-loss", action="store_true",
                    help="run phase F (ShardedEngine host-loss drill; "
                         "needs >= 8 devices, e.g. XLA_FLAGS="
                         "--xla_force_host_platform_device_count=8)")
    ap.add_argument("--journal-out", default=None, metavar="FILE",
                    help="phase F: write the scheduler journal here "
                         "(validate with validate_trace.py --journal)")
    ap.add_argument("--no-assert", action="store_true",
                    help="report only; do not enforce the contract")
    args = ap.parse_args()

    from repro import obs
    from repro.serve.engine import BatchedEngine, ContinuousEngine, Request
    from repro.serve.resilience import ResilienceConfig
    from repro.testing import faults

    if args.trace:
        obs.enable()
    if args.flight_dir:
        obs.configure_flight(dir=args.flight_dir)
    obs.flight_clear()

    cfg, model, params = _mk_model()
    key = jax.random.PRNGKey(7)
    n_req = 3 if args.smoke else 5
    reqs = _mk_requests(cfg, n_req, Request)

    print(f"# resilience_bench: {cfg.name} requests={n_req} "
          f"{'(smoke)' if args.smoke else '(soak)'}")

    t0 = time.perf_counter()
    oracle = BatchedEngine(model, params, max_seq=64, chunk=4).run(
        reqs, key=key)
    print(f"  oracle: {len(oracle)} requests, fault-free "
          f"({time.perf_counter() - t0:.1f}s)")

    doc = {"phases": {}, "fault_types": []}
    clean_identical = 0

    # every request that ends failed/timeout must leave a flight dump
    # attributing it; (req_id, state) pairs collected per faulted phase
    expect_dumps = []

    def _note_failures(results):
        for i, r in enumerate(results):
            if r.state in ("failed", "timeout"):
                expect_dumps.append((i, r.state))

    # -- phase 0: clean traffic must leave the flight recorder silent --------
    t0 = time.perf_counter()
    eng = ContinuousEngine(model, params, max_seq=64, slots=2, chunk=4,
                           min_bucket=8)
    results = _drive(eng, reqs, key)
    clean_identical += _tally(results, oracle, set(), doc, "0_clean")
    assert len(obs.flight_dumps()) == 0, \
        [d["reason"] for d in obs.flight_dumps()]
    doc["phases"]["0_clean"]["flight_dumps"] = 0
    print(f"  0 clean: states={[r.state for r in results]}, "
          f"no flight dumps ({time.perf_counter() - t0:.1f}s)")

    # -- phase A: serving faults in one mix ----------------------------------
    t0 = time.perf_counter()
    eng = ContinuousEngine(
        model, params, max_seq=64, slots=2, chunk=4, min_bucket=8,
        resilience=ResilienceConfig(retry_backoff_s=0.001,
                                    chunk_deadline_s=0.25))
    if args.smoke:
        spec = "serve.nan_prefill(req_id=1)"
        doc["fault_types"] += ["nan_prefill"]
        targeted = {1}
        phase_reqs = list(reqs)
    else:
        spec = ("serve.nan_prefill(req_id=1); serve.nan_decode(req_id=2); "
                "serve.chunk_error(times=2); "
                "serve.slow_chunk(times=1, value=0.4)")
        doc["fault_types"] += ["nan_prefill", "nan_decode", "chunk_error",
                               "slow_chunk", "deadline"]
        targeted = {1, 2, n_req}     # n_req: the doomed deadline request
        phase_reqs = list(reqs) + [Request(prompt=reqs[0].prompt,
                                           max_new_tokens=4,
                                           deadline_s=0.0)]
    with faults.inject(spec) as plan:
        results = _drive(eng, phase_reqs, key)
    _note_failures(results)
    clean_identical += _tally(results, oracle, targeted, doc, "A_serving")
    rs = eng.stats()["resilience"]
    doc["phases"]["A_serving"].update(
        {"resilience": rs, "faults_fired": sum(f.fired for f in plan)})
    if not args.smoke:
        assert rs["chunk_retries"] == 2, rs
        assert rs["stragglers"] >= 1, rs
    print(f"  A serving faults: states="
          f"{[r.state for r in results]} retries={rs['chunk_retries']} "
          f"({time.perf_counter() - t0:.1f}s)")

    # -- phase B: paged — exhaustion defers; scrubbed pages are reused -------
    if not args.smoke:
        t0 = time.perf_counter()
        eng = ContinuousEngine(model, params, max_seq=64, slots=2, chunk=4,
                               min_bucket=8, kv_layout="paged",
                               block_size=16, kv_blocks=10)
        with faults.inject("serve.pool_exhausted(req_id=0); "
                           "serve.nan_decode(req_id=2)"):
            results = _drive(eng, reqs, key)
        _note_failures(results)
        doc["fault_types"] += ["pool_exhausted"]
        clean_identical += _tally(results, oracle, {2}, doc, "B_paged")
        doc["phases"]["B_paged"]["deferrals"] = eng.sched.n_deferrals
        assert eng.sched.n_deferrals >= 1
        print(f"  B paged: deferrals={eng.sched.n_deferrals} states="
              f"{[r.state for r in results]} "
              f"({time.perf_counter() - t0:.1f}s)")

    # -- phase C: pool corruption degrades paged -> dense --------------------
    if not args.smoke:
        t0 = time.perf_counter()
        eng = ContinuousEngine(model, params, max_seq=64, slots=2, chunk=4,
                               min_bucket=8, kv_layout="paged",
                               block_size=16)
        with faults.inject("serve.pool_corrupt(after=1)"):
            results = _drive(eng, reqs, key)
        _note_failures(results)
        doc["fault_types"] += ["pool_corrupt"]
        in_flight_failed = {i for i, r in enumerate(results)
                            if r.state == "failed"}
        clean_identical += _tally(results, oracle, in_flight_failed, doc,
                                  "C_pool_corrupt")
        assert eng.kv_layout == "dense", "engine did not degrade"
        degr = [d for d in obs.decisions()
                if d.origin == "degraded(paged->dense)"]
        assert degr, "paged->dense degradation not in provenance"
        doc["phases"]["C_pool_corrupt"]["kv_layout_after"] = eng.kv_layout
        print(f"  C pool corrupt: paged->dense, states="
              f"{[r.state for r in results]} "
              f"({time.perf_counter() - t0:.1f}s)")

    # -- phase D: the kernel degradation ladder ------------------------------
    if not args.smoke:
        t0 = time.perf_counter()
        from repro.kernels import ops
        x = jnp.arange(64, dtype=jnp.float32)
        ref = ops.dot(x, x, impl="xla")
        ops.clear_caches()
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            with faults.inject(
                    "executor.build(key=dot*|pallas|*, times=-1)"):
                out = ops.dot(x, x, impl="dpia-pallas")
        assert jnp.allclose(out, ref), "degraded kernel wrong"
        doc["fault_types"] += ["executor_build"]
        origins = sorted({d.origin for d in obs.decisions()
                          if d.kernel == "dot"
                          and d.origin.startswith("degraded(")})
        assert "degraded(tuned->default)" in origins, origins
        assert "degraded(pallas->jnp)" in origins, origins
        ops.clear_caches()
        doc["phases"]["D_kernel_ladder"] = {"origins": origins}
        print(f"  D kernel ladder: {origins} "
              f"({time.perf_counter() - t0:.1f}s)")

    # -- phase E: corrupt tuning-cache record heals + rebuilds ---------------
    t0 = time.perf_counter()
    import tempfile
    from repro import autotune
    from repro.autotune.cache import TuningCache, make_key
    cache_path = os.path.join(tempfile.mkdtemp(prefix="resil-bench-"),
                              "tune.json")
    cache = TuningCache(cache_path)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        autotune.tune("dot", cache=cache, measure=False, n=64)
    k = make_key("dot", {"n": 64})
    assert cache.get(k) is not None
    raw = json.load(open(cache_path))
    raw.pop("checksum", None)
    raw["entries"][k] = "corrupt-record"
    with open(cache_path, "w") as f:
        json.dump(raw, f)
    before = obs.counter("artefact.entry_quarantined").value
    healed = TuningCache(cache_path)
    assert healed.get(k) is None, "corrupt record served"
    assert obs.counter("artefact.entry_quarantined").value > before
    assert os.path.isdir(cache_path + ".quarantine"), "no quarantine dir"
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        autotune.tune("dot", cache=healed, measure=False, n=64)
    assert TuningCache(cache_path).get(k) is not None, "not rebuilt"
    doc["fault_types"] += ["artefact_corrupt"]
    doc["phases"]["E_artefact_heal"] = {
        "quarantined": True, "rebuilt": True,
        "quarantine_dir": cache_path + ".quarantine"}
    print(f"  E artefact heal: entry quarantined + rebuilt by tune() "
          f"({time.perf_counter() - t0:.1f}s)")

    # -- phase F: host loss — evacuation, mesh shrink, checksummed journal ---
    if args.host_loss and len(jax.devices()) < 8:
        print("  F host loss: SKIPPED — needs an 8-device platform (run "
              "under XLA_FLAGS=--xla_force_host_platform_device_count=8)")
        doc["phases"]["F_host_loss"] = {"skipped": "needs 8 devices"}
    elif args.host_loss:
        t0 = time.perf_counter()
        import tempfile
        from repro.launch.mesh import make_mesh
        from repro.serve.domains import SchedulerJournal
        from repro.serve.engine import ShardedEngine
        # 8 phase-local requests so both hosts' slots carry work when the
        # fault fires; decodes long enough (16 tokens, chunk=4) that every
        # request is still in flight at the loss boundary
        fkey = jax.random.PRNGKey(7)
        f_reqs = [Request(
            prompt=jax.random.randint(jax.random.fold_in(fkey, 200 + i),
                                      (4 + i,), 0, cfg.vocab),
            max_new_tokens=16, temperature=0.0) for i in range(8)]
        f_oracle = ContinuousEngine(model, params, max_seq=64, slots=8,
                                    chunk=4, min_bucket=8).run(f_reqs,
                                                               key=fkey)

        # a clean sharded run first: zero NEW dumps, zero NEW degradations
        dumps0 = len(obs.flight_dumps())
        degr0 = obs.counter("serve.degradations").value
        eng = ShardedEngine(model, params, max_seq=64, slots=8, chunk=4,
                            min_bucket=8, mesh=make_mesh((8,), ("data",)),
                            hosts=2)
        clean = _drive(eng, f_reqs, fkey)
        assert all(r.state == "ok" for r in clean)
        assert [list(r.tokens) for r in clean] == f_oracle
        assert len(obs.flight_dumps()) == dumps0, \
            "clean sharded run left flight dumps"
        assert obs.counter("serve.degradations").value == degr0, \
            "clean sharded run recorded a degradation"

        # host 1 dies three boundaries in
        jpath = args.journal_out or os.path.join(
            tempfile.mkdtemp(prefix="resil-bench-"), "journal.jsonl")
        eng = ShardedEngine(model, params, max_seq=64, slots=8, chunk=4,
                            min_bucket=8, mesh=make_mesh((8,), ("data",)),
                            hosts=2, journal=jpath)
        with faults.inject("mesh.host_lost(host=1, after=3)") as plan:
            results = _drive(eng, f_reqs, fkey)
        st = eng.stats()
        n_events = st["resilience"]["host_losses"]
        assert plan[0].fired == 1 and n_events == 1, (plan[0].fired,
                                                      n_events)
        # zero crashes; survivors retired in place, evacuees re-admitted on
        # the shrunk mesh — ALL token-identical to the fault-free oracle
        assert all(r.state == "ok" for r in results), \
            [r.state for r in results]
        ident = sum(list(r.tokens) == f_oracle[i]
                    for i, r in enumerate(results))
        assert ident == len(f_reqs), f"{len(f_reqs) - ident} diverged"
        clean_identical += ident
        assert st["mesh"]["descriptor"] == "data=4", st["mesh"]
        assert eng.sched.n_evacuations >= 1
        # the shrink is a recorded strategy change...
        mesh_degr = sorted({d.origin for d in obs.decisions()
                            if d.origin.startswith("degraded(mesh(")})
        assert mesh_degr, "mesh shrink not in provenance"
        # ...with exactly ONE flight dump per host-loss event
        host_dumps = [d for d in obs.flight_dumps()
                      if d["reason"] == "host_lost"]
        assert len(host_dumps) == n_events, \
            (len(host_dumps), n_events)
        # the checksummed journal tells the whole story and verifies clean
        jstate = SchedulerJournal.load(jpath)
        assert jstate.clean, "journal failed checksum verification"
        assert len(jstate.shrinks) == 1, jstate.shrinks
        assert jstate.shrinks[0]["to"] == "data=4"
        assert jstate.evacuations == eng.sched.n_evacuations
        doc["fault_types"] += ["host_lost"]
        doc["phases"]["F_host_loss"] = {
            "states": {str(i): r.state for i, r in enumerate(results)},
            "clean_identical": ident, "clean_diverged": 0,
            "origins": mesh_degr,
        }
        doc["host_loss"] = {
            "events": n_events,
            "evacuations": eng.sched.n_evacuations,
            "descriptor_before": "data=8",
            "descriptor_after": st["mesh"]["descriptor"],
            "token_identical": ident,
            "requests": len(f_reqs),
            "host_lost_dumps": len(host_dumps),
            "journal": jpath,
            "journal_clean": jstate.clean,
        }
        print(f"  F host loss: data=8->data=4, "
              f"{eng.sched.n_evacuations} evacuated, {ident}/{len(f_reqs)} "
              f"token-identical, {len(host_dumps)} host_lost dump, "
              f"journal clean ({time.perf_counter() - t0:.1f}s)")

    # -- report ---------------------------------------------------------------
    doc.update({
        "smoke": bool(args.smoke),
        "requests": n_req,
        "fault_types": sorted(set(doc["fault_types"])),
        "faults_injected": obs.counter("faults.injected").value,
        "degradations": (obs.counter("serve.degradations").value
                         + obs.counter("kernels.degradations").value),
        "artefact_load_failures": obs.counter("artefact.load_failed").value,
        "clean_identical": clean_identical,
        "terminal_states": {
            s: obs.counter(f"serve.requests_{s}").value
            for s in ("timeout", "cancelled", "failed")},
        "nan_quarantines": obs.counter("serve.nan_quarantines").value,
        "chunk_failures": obs.counter("serve.chunk_failures").value,
    })
    flight = obs.flight_dumps()
    doc["flight"] = {
        "dumps": len(flight),
        "reasons": sorted({d["reason"] for d in flight}),
        "expected_request_dumps": len(expect_dumps),
        "dir": args.flight_dir or "",
    }
    for name, v in (("bench.resil.faults_injected", doc["faults_injected"]),
                    ("bench.resil.degradations", doc["degradations"]),
                    ("bench.resil.clean_identical", clean_identical)):
        obs.gauge(name).set(v)
    doc["metrics"] = obs.metrics_snapshot()

    with open(args.out, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
    print(f"  wrote {args.out}")
    if args.trace:
        obs.export_trace(args.trace)
        print(f"  wrote {args.trace} ({len(obs.trace_events())} events)")
    if args.metrics_out:
        obs.export_metrics(args.metrics_out)
        print(f"  wrote {args.metrics_out}")

    if not args.no_assert:
        want = 2 if args.smoke else 5
        assert len(doc["fault_types"]) >= want, doc["fault_types"]
        # phase E's cache damage is real file corruption, not a fault-site
        # firing, so it counts as a fault type but not an injection
        assert doc["faults_injected"] >= want - 1
        assert doc["clean_identical"] >= 1
        assert doc["terminal_states"]["failed"] >= 1
        # the flight-recorder contract: every failed/timeout request left a
        # dump attributing it by req_id, degradations dumped too
        assert expect_dumps, "no failed/timeout requests observed"
        for rid, state in expect_dumps:
            assert any(d["reason"] == f"request_{state}"
                       and d["ctx"].get("req_id") == rid
                       for d in flight), (rid, state, doc["flight"])
        if not args.smoke:
            assert any(d["reason"] == "degradation" for d in flight), \
                doc["flight"]
        if args.flight_dir:
            files = [n for n in os.listdir(args.flight_dir)
                     if n.startswith("flight-") and n.endswith(".json")]
            assert len(files) >= len(flight), (len(files), len(flight))
    print(f"  OK: {len(doc['fault_types'])} fault types, "
          f"{int(doc['faults_injected'])} injections, "
          f"{clean_identical} clean requests token-identical, "
          f"{len(flight)} flight dumps "
          f"({len(expect_dumps)} request failures attributed), 0 crashes")


if __name__ == "__main__":
    main()
