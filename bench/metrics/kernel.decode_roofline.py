"""Kernels (the jitted decode-chunk program): the least time the chip
needs for the decode steps of the traced window over the time the program
took.  A step's least time is the larger of its bytes (weights, valid KV
read, KV written) over peak HBM bandwidth and its FLOPs over peak FLOP/s;
steps in which no slot is owed a token need none."""
from bench import work
from bench.system import DECODE_PROGRAM


def read(ctx):
    if ctx.trace is None:
        return None
    _, secs = ctx.trace.programs.get(DECODE_PROGRAM, (0, 0.0))
    bw, peak = ctx.peaks["hbm_bytes_per_s"], ctx.peaks["flops"]
    by_bytes = by_flops = least = 0.0
    for b in ctx.traced:
        for step in b.decode:
            tb = work.decode_step_bytes(ctx.cfg, step) / bw
            tf = work.decode_step_flops(ctx.cfg, step) / peak
            by_bytes, by_flops, least = (by_bytes + tb, by_flops + tf,
                                         least + max(tb, tf))
    if not secs or not least:
        return None
    ctx.note(f"decode roofline: bytes bound {by_bytes:.6f} s, flops bound "
             f"{by_flops:.6f} s, decode program {secs:.6f} s")
    return 100.0 * least / secs
