"""The benchmark's command:

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

from the root of a checkout, on a machine that holds the chips the cell
asks for.  Set-up (weights from the seed, warm-up of every shape the
traffic reaches, a few seconds of traffic) is ``setup_s``; then the window
of ``--seconds``; then the comparison with the reference.  The last line of
standard output is the result; the last lines of standard error are the
numbers compared, each beside its limit.  Without a TPU, or with fewer
chips than the cell asks for, it exits 2 and prints no result.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be non-negative")

    sys.path.insert(0, ROOT)
    from bench import harness, spec
    cell = spec.cell(args.workload, ROOT)
    chips = cell["workload"]["chips"]
    harness.enable_cache(ROOT)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"bench: needs {chips} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    from bench.peaks import peaks
    peaks(devices[0].device_kind)
    dev = harness.device_info()
    harness.log(f"platform {dev['platform']} device_kind {dev['kind']} "
                f"count {dev['count']}")

    out = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                      t_process=T_PROCESS)
    for k, v in out["info"].items():
        harness.log(f"{k} {v}")
    for k, v in out["values"].items():
        harness.log(f"{k} {v}")
    for n in out["notes"]:
        harness.log(n)
    res = out["result"]
    for name, c in res["check"].items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
