"""Record the small chip trace that ``test_bench_trace.py`` reduces.

Run on a TPU from the root of a checkout:

    python3 tests/bench/make_trace.py chip_trace.xplane.pb
    gzip -9 -c chip_trace.xplane.pb > tests/bench/data/chip_trace.xplane.pb.gz

It drives a two-layer, 64-wide model (bfloat16) through the benchmark's own
loop for two seconds of closed-loop traffic and traces the last 0.4 s, so
the trace holds the decode-chunk and prefill programs, the benchmark's
``bench.*`` annotations and idle gaps between them.  It prints the planes
and lines it found.
"""
import glob
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def smoke_config(cfg: dict) -> dict:
    from bench import spec
    cfg = dict(cfg, **spec.arch(cfg).SMOKE)
    cfg["engine"] = dict(cfg["engine"], max_seq=256, slots=4,
                         kv_blocks=4 * 16)
    return cfg


def main(out: str) -> None:
    sys.path[:0] = [ROOT]
    from bench import harness, loop, spec, traffic
    from bench.system import Engine
    harness.enable_cache(ROOT)
    import jax
    c = spec.cell("qwen3-4b.decode", ROOT)
    cfg = smoke_config(c["config"])
    mix = dict(c["traffic"], clients=6, warmup_s=0.5,
               prompt=dict(c["traffic"]["prompt"], min=16, max=100,
                           median=40),
               output=dict(c["traffic"]["output"], min=8, max=60,
                           median=24))
    eng = Engine(cfg, 1)
    eng.warm(traffic.prompt_lengths(mix, 2.0))
    tdir = tempfile.mkdtemp()
    loop.drive(eng, traffic.make(mix, 1, cfg["vocab_size"], 2.0),
               warmup_s=0.5, seconds=2.0, trace_from=0.4,
               on_trace=lambda on: (jax.profiler.start_trace(
                   tdir, profiler_options=harness.trace_options()) if on
                                    else jax.profiler.stop_trace()))
    path = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    shutil.copy(path, out)
    from jax.profiler import ProfileData
    for plane in ProfileData.from_file(out).planes:
        for line in plane.lines:
            ev = list(line.events)
            print(plane.name, "|", line.name, "|", len(ev), "|",
                  [e.name for e in ev[:3]])
    print("summary", harness.tr.summarise(harness.tr.load(out)))
    shutil.rmtree(tdir, ignore_errors=True)


if __name__ == "__main__":
    main(sys.argv[1])
