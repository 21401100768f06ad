"""Readings for a cell's correctness limits, on the chip, in one process:

    python3 bench/calibrate.py --workload qwen3-4b.decode \\
        --seeds 1,2,...,12 --control 1,2,3 --seconds 40

Each seed is a full run of the cell (weights, warm-up, window, reference)
at the cell's own load; on the ``--control`` seeds the fp8 control is read
too, at the same positions.  One JSON line per seed, then the lower
reading (the largest program gap) and the upper (the smallest control
gap) of each number.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", default="")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from bench import harness, spec
    harness.enable_cache(ROOT)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 2
    c = spec.cell(args.workload, ROOT)
    control = {int(s) for s in args.control.split(",") if s}
    lower, upper = 0.0, float("inf")
    for seed in (int(s) for s in args.seeds.split(",")):
        out = harness.run(c, seed, args.seconds, False,
                          t_process=time.perf_counter(),
                          control=seed in control)
        n = out["numbers"]
        lower = max(lower, n["max_gap"])
        if "control_max_gap" in n:
            upper = min(upper, n["control_max_gap"])
        print(json.dumps({"seed": seed, "correct": out["result"]["correct"],
                          **n, **out["values"],
                          "finished": out["info"]["finished"]}), flush=True)
    print(json.dumps({"workload": args.workload, "max_gap_lower": lower,
                      "max_gap_upper": upper}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
