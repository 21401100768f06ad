"""Find the highest rate an open-loop cell sustains, on the chip:

    python3 bench/sweep.py --workload yi-9b-l24.rag --rates 1.5,2,2.5,3 \\
        --seconds 20 [--seed 1]

One process, one engine: each rate runs the cell's traffic with its
``rate`` replaced for ``--seconds`` after the mix's warm-up, from an idle
engine.  Printed per rate: arrivals in the window, the backlog (requests
due but without a first token) at the window's start and end and its
least-squares trend over the window's boundaries, ``ttft_p95_ms`` and
``output_tok_s``.  A rate is sustained when the backlog does not grow over
the window.  The cell's fixed rate is 0.8 of the highest sustained rate.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def backlog_trend(win):
    """(backlog at start, at end, slope per second) over the window."""
    import numpy as np
    pts = []
    for b in win.boundaries:
        if win.start <= b.t1 <= win.end:
            n = sum(1 for f in win.flights
                    if f.due <= b.t1 and not (f.stamps
                                              and f.stamps[0][0] <= b.t1))
            pts.append((b.t1 - win.start, n))
    if len(pts) < 2:
        return None, None, None
    t, n = np.array(pts, float).T
    return int(n[0]), int(n[-1]), float(np.polyfit(t, n, 1)[0])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from bench import harness, loop, spec, traffic
    from bench.system import Engine
    harness.enable_cache(ROOT)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 2
    c = spec.cell(args.workload, ROOT)
    cfg, base = c["config"], c["traffic"]
    rates = [float(r) for r in args.rates.split(",")]
    mixes = [dict(base, rate=r) for r in rates]
    eng = Engine(cfg, args.seed)
    lengths = sorted({n for m in mixes
                      for n in traffic.prompt_lengths(m, args.seconds)})
    harness.log(f"warm {eng.warm(lengths)}")
    rows = []
    for rate, mix in zip(rates, mixes):
        gen = traffic.make(mix, args.seed, cfg["vocab_size"], args.seconds)
        win = loop.drive(eng, gen, warmup_s=mix.get("warmup_s", 0),
                         seconds=args.seconds)
        e2e = loop.end_to_end(win)
        b0, b1, slope = backlog_trend(win)
        row = {"rate": rate, "due": e2e["ttft_n"], "backlog_start": b0,
               "backlog_end": b1, "backlog_per_s": slope,
               "ttft_p95_ms": harness._ms(e2e["ttft_p95_s"]),
               "tpot_p95_ms": harness._ms(e2e["tpot_p95_s"]),
               "output_tok_s": e2e["output_tok_s"],
               "occupancy": (sum(win.occupancy) / len(win.occupancy)
                             if win.occupancy else None)}
        rows.append(row)
        harness.log(json.dumps(row))
        while not eng.eng.sched.idle:         # drain before the next rate
            eng.step()
        time.sleep(0.5)
    print(json.dumps({"workload": args.workload, "rows": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
