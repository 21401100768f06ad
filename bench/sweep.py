"""Find the highest rate an open-loop cell sustains, on the chip:

    python3 bench/sweep.py --workload yi-9b-l24.rag --rates 1.5,2,2.5,3 \\
        --seconds 20 [--seed 1]

One process, one engine: each rate runs the cell's traffic for
``--seconds`` after the mix's warm-up, from an idle engine.  Every rate
sends the same requests, the mix drawn at the highest rate, with the
arrival times scaled to its own rate: the engine warms one set of prompt
lengths, not one per rate.  Printed per rate: arrivals in the window, the
backlog (requests due but without a first token) at the window's start
and end and its least-squares trend over the window's boundaries,
``ttft_p95_ms`` and ``output_tok_s``.  A rate is sustained when the
backlog does not grow over the window: it ends within a request or two of
where it started (Poisson bursts tilt the trend up a little at any rate).
The cell's fixed rate is 0.8 of the highest sustained rate.
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
    top = dict(base, rate=max(rates))
    eng = Engine(cfg, args.seed)
    harness.log(f"warm {eng.warm(traffic.prompt_lengths(top, args.seconds))}")
    rows = []
    for rate in rates:
        gen = traffic.make(top, args.seed, cfg["vocab_size"], args.seconds)
        gen.offsets = gen.offsets * (top["rate"] / rate)
        win = loop.drive(eng, gen, warmup_s=base.get("warmup_s", 0),
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
